"""The whole step's share of the chips' bf16 peak: useful model FLOPs
(`bench/work.py`: local SGD and the cloud's scoring per node update, the
test pass per record) over the traced window, over chips x peak."""


def read(run):
    from bench import work
    flops = (run.updates * work.update_flops(run.config)
             + run.records * work.record_flops(run.config))
    return 100.0 * flops / (run.window_s * run.chips
                            * run.peaks["bf16_flops_per_s"])
