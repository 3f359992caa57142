#!/usr/bin/env python3
"""Runs one benchmark cell once, on the chips of the machine it starts on.

    python bench/run.py --workload fleet1k.aldpfl_sync --seed 7 \
        --seconds 30 --trace 0

One process: it makes the cell's inputs from the seed, builds the system,
drives its first records (the ones the correctness check compares, which
also compile every program the window runs), measures a closed-loop
window of `--seconds`, then checks those first records against the plain
reference.  The last line of stdout is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device`, with `--trace 1` also
`breakdown`, and last `check`, each compared number beside its limit.
With `--trace 0` the metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from a profile of the window.

It exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for."""
import time

T_START = time.perf_counter()       # set-up is timed from process start

import argparse
import gc
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, ".bench_out")


def place_compile_cache() -> str:
    """JAX's persistent compile cache: `JAX_COMPILATION_CACHE_DIR` when
    set (JAX reads it itself), else `<checkout>/.jax_cache`, a fixed path
    because the path is part of the cache key.  Every program is cached,
    however small or quick to compile: the fleet runs many small ones."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class RunView:
    """What a per-layer metric's `read(run)` sees of a traced run."""

    def __init__(self, cell, trace, window, peaks):
        from bench import trace as tr, work
        self.config, self.trace = cell.config, trace
        self.chips, self.peaks = cell.chips, peaks
        self.records = window.records
        self.updates = window.records * cell.config["n_nodes"]
        self.n_params = work.n_params(cell.config)
        self.window_s = tr.window_s(trace)


def memory_peak(devices) -> int:
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in devices if d.memory_stats()]
    return int(max(peaks)) if peaks else 0


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_start: float = T_START) -> dict:
    """Everything after the look for a chip: returns the result line."""
    import jax
    from bench import check, drive, population, reference
    from bench import trace as tr
    from bench.cells import metric_reader
    from bench.peaks import peaks
    if os.path.join(ROOT, "src") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "src"))

    devices = jax.local_devices()[:cell.chips]
    config, traffic = cell.config, cell.traffic
    phases = {"start": time.perf_counter() - t_start}
    with drive.CompileCounter(drive.CACHE_EVENTS) as cache:
        inputs = population.make_inputs(config, traffic, seed)
        phases["inputs"] = time.perf_counter() - t_start
        system = drive.build(config, traffic, seed, inputs)
        phases["build"] = time.perf_counter() - t_start
        prog = drive.check_records(system, traffic["check_records"],
                                   phases, t_start)
    setup_s = time.perf_counter() - t_start
    print(json.dumps({"setup_s": setup_s, "phases_end_s": phases,
                      "compile_cache": cache.counts()}),
          file=sys.stderr, flush=True)

    log_dir = None
    if trace:
        log_dir = os.path.join(OUT, f"{cell.name}.trace")
        shutil.rmtree(log_dir, ignore_errors=True)
        seconds = min(seconds, traffic.get("trace_seconds", seconds))
    with drive.profiled(log_dir):
        w = drive.window(system, seconds)
    n = config["n_nodes"]
    updates = w.records * n
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak(devices)}
    print(json.dumps({"window_s": w.seconds, "records": w.records,
                      "updates": updates, "compiles_in_window": w.compiles}),
          file=sys.stderr, flush=True)

    metrics, breakdown = {}, None
    if trace:
        path = tr.find_xplane(log_dir)
        with open(os.path.join(OUT, f"{cell.name}.trace_summary.json"),
                  "w") as f:
            json.dump(tr.describe_xplane(path), f)
        t = tr.load_xplane(path)
        shutil.rmtree(log_dir, ignore_errors=True)
        view = RunView(cell, t, w, peaks(device["kind"]))
        for m in cell.per_layer:
            value = metric_reader(m["name"])(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s(t)
        device["window_s"] = tr.window_s(t)
        breakdown = {"device_ops": tr.top_ops(t), "idle_gaps":
                     tr.attribute_gaps(t)}
    else:
        e2e = {"updates_per_s": updates / w.seconds, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    # the check: the program's state goes first, then the reference runs
    del system
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference.run(config, traffic, inputs, seed,
                        traffic["check_records"])
    print(json.dumps({"reference_s": time.perf_counter() - t_ref}),
          file=sys.stderr, flush=True)
    numbers = check.compare(inputs.params, prog, ref, n)
    limits = check.limits(cell.name)
    print(json.dumps({"not_compared": {k: v for k, v in numbers.items()
                                       if k not in limits}}),
          file=sys.stderr, flush=True)
    for k, v in limits.items():
        print(f"check {k} {numbers[k]!r} limit {v!r}", file=sys.stderr,
              flush=True)
    line = {"correct": check.verdict(numbers, limits), "attempted": updates,
            "failed": 0, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["check"] = check.beside_limits(numbers, limits)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench.cells import cell as find_cell
    try:
        cell = find_cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"sees {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    place_compile_cache()
    os.makedirs(OUT, exist_ok=True)
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
