"""Mesh-sharded fleet weak-scaling benchmark: n = 1000 x D nodes on a
D-device host mesh (``--xla_force_host_platform_device_count``).

Each swept point runs in its own subprocess (the forced host device count is
fixed at process start) and shards the node axis of the `honest` scenario
over a `FleetMesh`: one timed synchronous round (local SGD + detection +
aggregation under shard_map) and a few timed asynchronous arrival windows.
Per-device residual-shard bytes are recorded alongside wall-clock, so the
JSON trajectory at ``results/fleet_shard.json`` tracks both the weak-scaling
time curve and the memory win that motivates sharding (per-device state is
O(N/D), letting 10k+ node fleets fit where a single device can't).

  PYTHONPATH=src python -m benchmarks.fleet_shard            # 1k..16k sweep
  PYTHONPATH=src python -m benchmarks.fleet_shard --smoke    # 4-device CI run
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

RESULTS_PATH = os.path.join(os.path.dirname(__file__), "..", "results",
                            "fleet_shard.json")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")
DEVICE_SWEEP = (1, 2, 4, 8, 16)
NODES_PER_DEVICE = 1000
TIMED_WINDOWS = 3

_CHILD = r"""
import sys
n, d, timed_windows = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
import os
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={d}"
import dataclasses
import json
import time
import jax
from repro.fleet import (FleetMesh, build_async_engine, build_engine,
                         get_scenario)

mesh = FleetMesh.create(d)
sc = dataclasses.replace(get_scenario("honest").with_nodes(n),
                         samples_per_node=20)

eng = build_engine(sc, seed=0, mesh=mesh)
eng.run_round()                               # compile + warm
t0 = time.perf_counter()
eng.run_round()
sync_s = time.perf_counter() - t0
res_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.state.residuals))

aeng = build_async_engine(sc, seed=0, mesh=mesh)
for _ in range(2):
    aeng.run_window(evaluate=False)           # compile likely buckets
warm = len(aeng.history)
t0 = time.perf_counter()
for _ in range(timed_windows):
    aeng.run_window(evaluate=False)
async_s = (time.perf_counter() - t0) / timed_windows
arrivals = sum(r.n_processed for r in aeng.history[warm:]) / timed_windows

print(json.dumps({
    "n_nodes": n, "n_devices": d, "n_pad": eng.n_pad,
    "sync_s_per_round": sync_s, "async_s_per_window": async_s,
    "arrivals_per_window": arrivals,
    "residual_bytes_per_device": res_bytes // d,
    "final_acc": eng.history[-1].accuracy,
}))
"""


def _run_child(n: int, d: int, timed_windows: int = TIMED_WINDOWS) -> dict:
    # the child rehearses a forced host mesh: it stays on the CPU, so it
    # never reaches for a chip that this (or another) process holds
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)        # the child forces its own device count
    r = subprocess.run(
        [sys.executable, "-c", _CHILD, str(n), str(d), str(timed_windows)],
        capture_output=True, text=True, env=env, timeout=3600)
    if r.returncode != 0:
        raise RuntimeError(f"fleet_shard child (n={n}, d={d}) failed:\n"
                           f"{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def run() -> None:
    from .common import append_trajectory, emit
    stamp = time.strftime("%Y-%m-%dT%H:%M:%S")
    records = []
    for d in DEVICE_SWEEP:
        n = NODES_PER_DEVICE * d
        rec = _run_child(n, d)
        rec["ts"] = stamp
        emit(f"fleet_shard_n{n}_d{d}", rec["sync_s_per_round"] * 1e6,
             f"async_window_s={rec['async_s_per_window']:.4f};"
             f"res_bytes_per_dev={rec['residual_bytes_per_device']}")
        records.append(rec)
    append_trajectory(RESULTS_PATH, records)


def smoke() -> None:
    """One 4-device subprocess, uneven n=30 fleet — the CI liveness check
    for the sharded round + window programs."""
    rec = _run_child(30, 4, timed_windows=2)
    print(json.dumps(rec))
    assert rec["n_devices"] == 4
    assert rec["n_pad"] == 32                  # 30 padded to a multiple of 4
    assert rec["arrivals_per_window"] >= 1
    assert 0.0 <= rec["final_acc"] <= 1.0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="4-device 30-node sharded run (CI)")
    args = ap.parse_args()
    if args.smoke:
        smoke()
    else:
        run()
