"""Local SGD's minibatch selection on the chip: the one-hot product of
`fleet.stages.select_rows` against the gather ``x[idx]`` it replaced.

    python -m benchmarks.local_select

For each (cohort C, shard length M) in ``SHAPES`` it times, on the first
device, with the host clock around work that ends in `block_until_ready`:

- ``select_ms``: one step's selection alone, vmapped over the cohort
  (B=128 rows of a 28x28x1 image from each of C shards);
- ``local_sgd_ms``: the whole vmapped `make_local_train` of the paper CNN
  (10 steps of B=128, lr 0.1), the stage a sync round runs once;

each through the product and through the gather, after a warm-up call, as
the median of ``REPS`` calls.  ``rows_equal`` says whether the two paths
selected the same bits, ``params_equal`` whether training on them gave
the same params, and ``params_max_abs_diff`` by how much they part.

Then, at the 1,000-node cell's shape, for 1 and ``STEPS`` local steps on
each of ``SEEDS``, it trains through either path at the CNN's default
precision and once more through the gather with every convolution and
product at ``Precision.HIGHEST`` (the reference).  ``dist.<path>`` gives,
per parameter leaf, ``||p - ref|| / ||ref - p0||``: a path that rounded
the CNN's operands more coarsely would lie farther from the reference.

One JSON line per row.  It runs only where JAX finds a TPU, and exits
non-zero elsewhere: a time from the CPU backend says nothing about the
chip."""
from __future__ import annotations

import contextlib
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.fleet import stages
from repro.models.cnn import cnn_loss, init_cnn

# (cohort, shard length): the 1,000-node cell's shards, longer ones, and
# the paper's own 10 x 6,000 (with 100 nodes of 6,000 beside it)
SHAPES = ((1000, 60), (1000, 512), (10, 6000), (100, 6000))
BATCH, STEPS, LR, REPS = 128, 10, 0.1, 5
SEEDS = (0, 1, 2)


def _gather(x, idx):
    return x[idx]


@contextlib.contextmanager
def _selection(path: str):
    """Trace `make_local_train` with the product ("onehot") or the
    gather ("gather") as its `select_rows`."""
    product = stages.select_rows
    stages.select_rows = product if path == "onehot" else _gather
    try:
        yield stages.select_rows
    finally:
        stages.select_rows = product


def _median_ms(fn, *args) -> float:
    jax.block_until_ready(fn(*args))            # compile and warm up
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def _inputs(c: int, m: int, seed: int = 0):
    kx, ky, ki, kk, kp = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.uniform(kx, (c, m, 28, 28, 1), jnp.float32)
    y = jax.random.randint(ky, (c, m), 0, 10)
    sizes = jnp.full((c,), m, jnp.int32)
    idx = jax.random.randint(ki, (c, BATCH), 0, m)
    return init_cnn(kp), x, y, sizes, idx, jax.random.split(kk, c)


def _train(path: str, steps: int):
    """The vmapped local SGD through ``path``'s selection, which is looked
    up when the jit traces, on its first call."""
    f = jax.jit(jax.vmap(stages.make_local_train(cnn_loss, steps, LR, BATCH),
                         in_axes=(None, 0, 0, 0, 0)))

    def run(*args):
        with _selection(path):
            return f(*args)
    return run


def _leaves(tree):
    return [np.asarray(a, np.float64) for a in jax.tree.leaves(tree)]


def measure(c: int, m: int) -> dict:
    """Both paths at one (C, M): times in ms and whether they agree."""
    params, x, y, sizes, idx, keys = _inputs(c, m)
    row = {"cohort": c, "shard": m, "batch": BATCH, "steps": STEPS}
    out = {}
    for path in ("onehot", "gather"):
        with _selection(path) as select:
            select = jax.jit(jax.vmap(select))
        train = _train(path, STEPS)
        row[f"{path}.select_ms"] = _median_ms(select, x, idx)
        row[f"{path}.local_sgd_ms"] = _median_ms(train, params, x, y, sizes,
                                                 keys)
        out[path] = (select(x, idx), train(params, x, y, sizes, keys))
    (rows_a, params_a), (rows_b, params_b) = out["onehot"], out["gather"]
    row["rows_equal"] = bool(np.array_equal(np.asarray(rows_a),
                                            np.asarray(rows_b)))
    pairs = list(zip(_leaves(params_a), _leaves(params_b)))
    row["params_equal"] = all(np.array_equal(p, q) for p, q in pairs)
    row["params_max_abs_diff"] = max(float(np.max(np.abs(p - q)))
                                     for p, q in pairs)
    return row


def precision(c: int, m: int, steps: int) -> list:
    """Each path's distance from the HIGHEST-precision reference, per
    seed, after ``steps`` local steps at (C, M)."""
    trains = {path: _train(path, steps) for path in ("onehot", "gather")}
    with jax.default_matmul_precision("highest"):
        ref_train = _train("gather", steps)
    names = ["/".join(str(getattr(k, "key", k)) for k in kp) for kp, _ in
             jax.tree_util.tree_leaves_with_path(init_cnn(
                 jax.random.PRNGKey(0)))]
    rows = []
    for seed in SEEDS:
        params, x, y, sizes, _, keys = _inputs(c, m, seed)
        args = (params, x, y, sizes, keys)
        with jax.default_matmul_precision("highest"):
            ref = _leaves(ref_train(*args))
        p0 = [np.broadcast_to(a, r.shape) for a, r in
              zip(_leaves(params), ref)]
        row = {"cohort": c, "shard": m, "steps": steps, "seed": seed}
        for path, train in trains.items():
            got = _leaves(train(*args))
            row[f"dist.{path}"] = {
                n: float(np.linalg.norm(g - r) / np.linalg.norm(r - q))
                for n, g, r, q in zip(names, got, ref, p0)}
        rows.append(row)
    return rows


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"local_select: no TPU found (JAX platform is "
              f"{dev.platform!r}); it times the chip only", file=sys.stderr)
        return 1
    for c, m in SHAPES:
        row = measure(c, m)
        row["device"] = dev.device_kind
        print(json.dumps(row), flush=True)
    for steps in (1, STEPS):
        for row in precision(*SHAPES[0], steps):
            row["device"] = dev.device_kind
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
