"""The plain reference: ALDPFL's synchronous round (arXiv 2012.04436 §5,
Alg. 2, Eq. 6) in straightforward `jax.numpy`, importing nothing of the
program.

`run_sync`: one round, for every node k of the fleet:
  local SGD from the global model (`local_steps` minibatches of
  `batch_size`, drawn with `randint` from the node's own key),
  delta = local - global; combined = delta + the node's DGC residual;
  per leaf, keep |combined| >= its (1 - ratio) quantile, the rest stays
  behind as the residual (§5.1);
  clip the kept update to L2 norm S and add N(0, (sigma S)^2) to every
  coordinate (§5.2);
  the cloud scores the node's model (global + upload) on its test set.
Then Alg. 2 keeps the nodes scoring above the s-th percentile, the kept
models are averaged, and Eq. (6) mixes: w <- alpha w + (1 - alpha) mean.

What the reference shares with the program is the definition of its
random streams, so that the two compute the same numbers: the per-node
key chain (`key, k1, k2 = split(key, 3)` per node in node order, k1 for
the minibatches, k2 for the noise seed), and the counter-hash Box-Muller
noise the fused upload kernel draws (a murmur3 finalizer of each
element's in-block index, seeded per node and block).  Both are written
out here from their definitions.

The model is the configuration's module (`bench/models/<model>.py`):
its `loss` and `forward` in plain `jax.numpy`, its `accuracy` as the
program reports it, `NODE_BLOCK` nodes trained at once, `TEST_BLOCK`
rows of a test pass, and `ALTERED`, the leaf the `altered` fault
doubles.  The module's own arrays (`Inputs.extra`, frozen weights, say)
are placed on the device once per run, in the dtype the module stored
them in, and reach `loss` and `forward` as their last argument, traced
and never trained: the gradient, the flat upload, DGC, the residuals and
the noise cover the trainable tree (`Inputs.params`) alone.  The round
above is written once for every model; a node's flat upload takes the
leaves in sorted-key order (`check.leaf_paths`).

`dtype` is what the round computes in, at `HIGHEST` precision in
float32 and `DEFAULT` below it.  The module's `control_kwargs` give the
control's: the round computed below the configuration's stated
precision.  `fault` plants one of the faults the check has to catch (see
`FAULTS`)."""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .cells import model_module
from .check import Readings, leaf, leaf_paths
from .population import Inputs

LANE = 1024
FAULTS = ("half", "altered")


def flatten(tree) -> jnp.ndarray:
    return jnp.concatenate([leaf(tree, n).reshape(-1)
                            for n in leaf_paths(tree)])


def unflatten(flat, like) -> dict:
    out, off = {}, 0
    for path in leaf_paths(like):
        shape = leaf(like, path).shape
        size = int(np.prod(shape))
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = flat[off:off + size].reshape(shape)
        off += size
    return out


def leaf_offset(tree, path) -> int:
    """Where a leaf starts in the flat upload."""
    paths = leaf_paths(tree)
    return sum(int(np.prod(leaf(tree, q).shape))
               for q in paths[:paths.index(tuple(path))])


def noise_seed(k2) -> jnp.ndarray:
    raw = jax.random.key_data(k2) if jnp.issubdtype(
        k2.dtype, jax.dtypes.prng_key) else k2
    return (raw[..., 0] ^ raw[..., -1]).astype(jnp.int32)


def hash_noise(seed, p: int, sigma_s: float) -> jnp.ndarray:
    """The (P,) noise of one node: element e lies in row e // LANE of the
    node's (rows, LANE) layout, in blocks of `block_rows` rows; its
    uniforms are murmur3-finalized counters (in-block index + block seed
    x 2654435761 + stream x 0x9E3779B9), the block seed being the node's
    seed + block x 7919."""
    rows = -(-p // LANE)
    block_rows = min(256, -(-rows // 8) * 8)
    e = jnp.arange(p, dtype=jnp.int32)
    r, col = e // LANE, (e % LANE).astype(jnp.uint32)
    blk, in_blk = r // block_rows, (r % block_rows).astype(jnp.uint32)
    count = in_blk * jnp.uint32(LANE) + col
    blk_seed = (seed + blk * 7919).astype(jnp.uint32)

    def uniform(stream):
        x = count + blk_seed * jnp.uint32(2654435761)
        x = x + jnp.uint32((stream * 0x9E3779B9) & 0xFFFFFFFF)
        x = x ^ (x >> 16)
        x = x * jnp.uint32(0x7FEB352D)
        x = x ^ (x >> 15)
        x = x * jnp.uint32(0x846CA68B)
        x = x ^ (x >> 16)
        return (x >> 8).astype(jnp.int32).astype(jnp.float32) / (1 << 24)

    u1 = jnp.maximum(uniform(1), 1e-12)
    return sigma_s * jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(
        2.0 * math.pi * uniform(2))


@partial(jax.jit, static_argnames=("cfg", "model", "dtype", "precision"))
def _block(p, x, y, k1s, k2s, res, cx, extra, cfg, model, dtype,
           precision):
    """One block of nodes: local SGD, DGC, clip and noise; returns the
    uploads, the new residuals, the nonzero counts and the cloud's logits
    of each node's model."""
    steps, bs, lr, ratio, clip_s, sigma = cfg
    size = x.shape[1]
    pf = flatten(p)
    n_par = pf.shape[0]

    def node(x, y, k1, k2, r):
        def body(q, k):
            idx = jax.random.randint(k, (bs,), 0, size)
            g = jax.grad(model.loss)(q, x[idx], y[idx], precision, extra)
            return jax.tree.map(lambda a, b: a - jnp.asarray(lr, dtype) * b,
                                q, g), None

        q, _ = jax.lax.scan(body, p, jax.random.split(k1, steps))
        comb = flatten(q) - pf + r
        thr, off = [], 0
        for name in leaf_paths(p):
            n = int(np.prod(leaf(p, name).shape))
            seg = jnp.abs(comb[off:off + n])
            thr.append(jnp.full((n,), jnp.quantile(seg, 1.0 - ratio)))
            off += n
        keep = jnp.abs(comb) >= jnp.concatenate(thr)
        sp = jnp.where(keep, comb, 0)
        nnz = jnp.sum(sp != 0)
        norm = jnp.sqrt(jnp.sum(jnp.square(sp)))
        scale = 1.0 / jnp.maximum(1.0, norm / clip_s)
        up = sp * scale + hash_noise(noise_seed(k2), n_par,
                                     sigma * clip_s).astype(dtype)
        return up.astype(dtype), jnp.where(keep, 0, comb), nnz

    ups, newr, nnz = jax.vmap(node)(x, y, k1s, k2s, res)
    logits = jax.lax.map(
        lambda u: model.forward(unflatten(pf + u, p), cx, precision, extra),
        ups)
    return ups, newr, nnz, logits


@partial(jax.jit, static_argnames=("n",))
def _key_chain(key, n):
    """Per node, in node order: key, k1, k2 = split(key, 3)."""
    def body(k, _):
        k, k1, k2 = jax.random.split(k, 3)
        return k, (k1, k2)
    key, (k1s, k2s) = jax.lax.scan(body, key, None, length=n)
    return key, k1s, k2s


@partial(jax.jit, static_argnames=("model", "precision"))
def _logits(p, x, extra, model, precision):
    return model.forward(p, x, precision, extra)


def _record(out: Readings, p, tx, ty, extra, model, precision) -> None:
    """A record's params (host copy) and test accuracy, in blocks of the
    test set."""
    b = model.TEST_BLOCK
    logits = np.concatenate([np.asarray(_logits(p, tx[i:i + b], extra,
                                                model, precision))
                             for i in range(0, tx.shape[0], b)])
    out.params.append(jax.tree.map(lambda a: np.asarray(a, np.float32), p))
    out.accuracy.append(float(model.accuracy(logits, ty)))


def wire_bytes(nnz: np.ndarray, n_params: int) -> float:
    """sparse_bitpack: a u32 count, ceil(log2 P)-bit indices, f32 values."""
    bits = max(1, int(n_params - 1).bit_length())
    nnz = np.asarray(nnz, np.int64)
    return float(np.sum(4 + (nnz * bits + 7) // 8 + 4 * nnz))


def _cast(a, dtype):
    """Floating inputs in the round's dtype; tokens and labels as they
    are."""
    a = np.asarray(a)
    return jnp.asarray(a, dtype) if np.issubdtype(a.dtype, np.floating) \
        else jnp.asarray(a)


def run_sync(config: dict, inputs: Inputs, seed: int, rounds: int, *,
             dtype=jnp.float32, fault: Optional[str] = None) -> Readings:
    """`rounds` synchronous ALDPFL rounds from the inputs' weights."""
    model = model_module(config)
    precision = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    cfg = (config["local_steps"], config["batch_size"], config["lr"],
           config["sparsify_ratio"], config["clip_s"], config["sigma"])
    n = inputs.x.shape[0]
    p = jax.tree.map(lambda a: jnp.asarray(a, dtype), inputs.params)
    n_par = int(flatten(p).shape[0])
    res = jnp.zeros((n, n_par), dtype)
    cx = _cast(inputs.cloud[0], dtype)
    tx = _cast(inputs.test[0], dtype)
    extra = jax.device_put(inputs.extra)
    key = jax.random.PRNGKey(int(seed))
    alpha = config["alpha"]
    out = Readings([], [], [], [])
    for _ in range(rounds):
        key, k1s, k2s = _key_chain(key, n)
        ups, nnz, accs, new_res = [], [], [], []
        for lo in range(0, n, model.NODE_BLOCK):
            hi = min(n, lo + model.NODE_BLOCK)
            u, r, z, lg = _block(
                p, _cast(inputs.x[lo:hi], dtype),
                jnp.asarray(inputs.y[lo:hi]), k1s[lo:hi], k2s[lo:hi],
                res[lo:hi], cx, extra, cfg, model, dtype, precision)
            ups.append(u)
            new_res.append(r)
            nnz.append(np.asarray(z))
            accs.append(model.accuracy(lg, inputs.cloud[1]))
        res = jnp.concatenate(new_res)
        accs = np.concatenate(accs)
        thr = np.percentile(accs, config["detect_s"])
        mask = accs > thr
        if not mask.any():
            mask = accs >= thr
        out.rejected.append(int(n - mask.sum()))
        if fault == "half":         # half of the kept uploads left out
            mask[np.flatnonzero(mask)[mask.sum() // 2:]] = False
        pf = flatten(p)
        omegas = pf + jnp.concatenate(ups)
        w = jnp.asarray(mask, dtype)[:, None]
        mean = (w * omegas).sum(0) / jnp.maximum(w.sum(), 1)
        new = alpha * pf + (1 - alpha) * mean
        if fault == "altered":      # the fold's answer altered for one leaf
            lo = leaf_offset(p, model.ALTERED)
            new = new.at[lo:lo + leaf(p, model.ALTERED).size].multiply(2)
        p = unflatten(new.astype(dtype), p)
        _record(out, p, tx, inputs.test[1], extra, model, precision)
        out.comm_bytes.append(wire_bytes(np.concatenate(nnz), n_par))
    return out


def run(config: dict, traffic: dict, inputs: Inputs, seed: int,
        records: int, **kw) -> Readings:
    """The reference of the traffic's schedule: the synchronous round is
    the only one written."""
    if traffic["schedule"] != "sync":
        raise ValueError(f"no reference for schedule {traffic['schedule']!r}")
    return run_sync(config, inputs, seed, records, **kw)
