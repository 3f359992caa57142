"""Device self time of local SGD per record: the ops whose HLO `op_name`
carries the ``fleet.local_sgd`` scope (the cohort gather, the vmapped
local steps with their per-step minibatch gather, the deltas), in ms over
the ``round`` spans in the traced window."""


def read(run):
    from bench import stages
    return stages.scope_ms_per_record(run, "fleet.local_sgd")
