#!/usr/bin/env python3
"""Reads the two ends that each limit of the correctness check is set
between, on the chip, at a cell's own size.  The benchmark's own runs do
not run this.

    python bench/control.py --workload fleet1k.aldpfl_sync \
        --seeds 11,12,13 --control-seeds 11,12,13 --fault-seeds 11,12,13

For every seed of `--seeds`: the program's first records, driven as a
run drives them, against the float32 reference (the lower readings).
For `--control-seeds`: the reference computed below the configuration's
stated precision (its model module's `control_kwargs`), put in the
program's place (the control, whose smallest reading is the upper end).
For `--fault-seeds`: the reference with each planted fault of
`reference.FAULTS` in the program's place.  One JSON line per reading,
each judged by the cell's own limits (`bench/limits/<cell>.json`) as a
run judges it: `correct`, and each compared number beside its limit."""
import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def readings(cell, seed: int, program: bool, control: bool, faults: bool):
    """The runs asked for on one seed, each judged against the float32
    reference by the cell's limits: one dict per run."""
    from bench import check, drive, population, reference
    from bench.cells import model_module
    config, traffic = cell.config, cell.traffic
    n, k = config["n_nodes"], traffic["check_records"]
    limits = check.limits(cell.name)
    inputs = population.make_inputs(config, traffic, seed)
    ref = reference.run(config, traffic, inputs, seed, k)
    runs = []
    if program:
        system = drive.build(config, traffic, seed, inputs)
        prog = drive.check_records(system, k)
        del system
        gc.collect()
        runs.append(("program", prog))
    if control:
        runs.append(("control", reference.run(
            config, traffic, inputs, seed, k,
            **model_module(config).control_kwargs(config))))
    if faults:
        runs += [(f"fault.{f}", reference.run(
            config, traffic, inputs, seed, k, fault=f))
            for f in reference.FAULTS]
    out = []
    for name, got in runs:
        numbers = check.compare(inputs.params, got, ref, n)
        out.append({
            "workload": cell.name, "seed": seed, "run": name,
            "correct": check.verdict(numbers, limits),
            "check": check.beside_limits(numbers, limits),
            "numbers": numbers,
            "leaves": check.leaf_gaps(got, ref, inputs.params),
            "accuracy": got.accuracy, "ref_accuracy": ref.accuracy,
            "rejected": got.rejected, "ref_rejected": ref.rejected})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from bench.cells import cell as find_cell
    from bench.run import place_compile_cache
    cell = find_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    place_compile_cache()
    seeds = _seeds(args.seeds)
    control, faults = _seeds(args.control_seeds), _seeds(args.fault_seeds)
    for seed in sorted(set(seeds) | set(control) | set(faults)):
        for line in readings(cell, seed, seed in seeds, seed in control,
                             seed in faults):
            print(json.dumps(line), flush=True)
            print(f"{line['run']} seed {seed} correct {line['correct']}: "
                  + ", ".join(f"{k} {v['value']!r} limit {v['limit']!r}"
                              for k, v in line["check"].items()),
                  file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
