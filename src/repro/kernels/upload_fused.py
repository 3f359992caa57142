"""Fused upload-pipeline megakernel — Pallas TPU kernel.

The per-arrival upload pipeline (`fleet.stages.upload_pipeline`) used to be
a dispatch chain over the flattened (C, P) cohort: a DGC sparsify kernel per
leaf (`kernels.sparsify`), a standalone nonzero-count kernel
(`kernels.wire_bytes`) so `repro.net` can price the wire message, a jnp
norm reduction for the ALDP clip scale, and the clip+noise kernel
(`kernels.ldp_noise`) — ~9 HBM passes over the cohort plus the flatten /
concat glue between them.  This kernel fuses the whole thing into ONE pass:

  read (delta, residual) block -> combined = delta + residual
    -> keep = |combined| >= per-leaf DGC threshold       (§4.1)
    -> upload  = keep ? combined : 0;  residual' = keep ? 0 : combined
    -> nnz    += count(upload != 0)     (post-sparsify, pre-noise — the
                                         sparse coordinate set the wire
                                         codecs price)
    -> upload  = clip_scale * upload + N(0, (sigma·S)^2)  (§4.2, Eq. 10)
  write (upload, residual', nnz)

so wire-byte counting is free and noise never touches HBM.  Two reductions
stay outside by data dependency: the per-leaf quantile *threshold* needs a
sort over the whole leaf, and the clip scale needs the post-sparsify global
L2 norm before any output element — both run as one jnp pre-pass over the
`combined` cohort in `fleet.stages`.

Parity contract (tested in tests/test_upload_fused.py): bit-equal to the
unfused `sparsify_fleet` -> `nnz_fleet` -> `ldp_perturb_fleet` chain — same
block decomposition, same per-block seeding (`seed + block·7919`), same
counter-based Box–Muller streams — and float-close to the reference jnp
pipeline at sigma=0.  Like the reference pipeline, noise is applied to
*every* coordinate (the documented dense-noise simulation artifact); the
nnz output prices the intended sparse wire message.

Grid is (node, block): shard-oblivious — every output depends only on its
own node row, so the mesh engines call this inside `shard_map` unchanged.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import LANE, block_layout, interpret_mode, pad_blocks
from .ldp_noise import _finalize_uniform, _hash_uniform
from .wire_bytes import accumulate_nnz, nnz_out


def _fused_kernel(*refs, sigma_s: float, apply_ldp: bool, do_sparsify: bool,
                  need_nnz: bool, block_rows: int,
                  boundaries: Tuple[int, ...]):
    """One (1, block_rows, LANE) block of one node through the whole upload
    pipeline.  The ref list is built to match the wrapper's dynamic
    in_specs/out_specs (features compiled out drop their refs entirely, so
    e.g. a no-noise program carries no seed/scale operands)."""
    it = iter(refs)
    thr_ref = next(it) if do_sparsify else None          # (C, L) SMEM
    seed_ref = next(it) if sigma_s > 0.0 else None       # (C,)  SMEM
    scale_ref = next(it) if apply_ldp else None          # (C,)  SMEM
    g_ref = next(it)
    r_ref = next(it) if do_sparsify else None
    up_ref = next(it)
    newr_ref = next(it) if do_sparsify else None
    nnz_ref = next(it) if need_nnz else None

    node = pl.program_id(0)
    blk = pl.program_id(1)
    g = g_ref[0].astype(jnp.float32)
    if do_sparsify:
        c = g + r_ref[0].astype(jnp.float32)
        shape = c.shape
        # per-element threshold: leaf l covers flat positions
        # boundaries[l] <= p < boundaries[l+1] (static leaf layout)
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        p = (blk * block_rows + rows) * shape[1] + cols
        thr = jnp.full(shape, thr_ref[node, 0], jnp.float32)
        for leaf in range(1, len(boundaries)):
            thr = jnp.where(p >= boundaries[leaf], thr_ref[node, leaf], thr)
        keep = jnp.abs(c) >= thr
        up = jnp.where(keep, c, 0.0)
        newr_ref[0] = jnp.where(keep, 0.0, c).astype(newr_ref.dtype)
    else:
        up = g
    if need_nnz:
        accumulate_nnz(nnz_ref, blk, up)
    if apply_ldp:
        up = up * scale_ref[node]
        if sigma_s > 0.0:
            shape = up.shape
            blk_seed = seed_ref[node] + blk * 7919
            u1 = jnp.maximum(_hash_uniform(blk_seed, 1, shape), 1e-12)
            u2 = _hash_uniform(blk_seed, 2, shape)
            r = jnp.sqrt(-2.0 * jnp.log(u1))
            theta = (2.0 * math.pi) * u2
            up = up + sigma_s * r * jnp.cos(theta)
    up_ref[0] = up.astype(up_ref.dtype)


def upload_fused_fleet(flat: jnp.ndarray,
                       residuals: Optional[jnp.ndarray],
                       thresholds: Optional[jnp.ndarray],
                       seeds: Optional[jnp.ndarray],
                       clip_scales: Optional[jnp.ndarray],
                       sigma: float, clip_s: float, *,
                       boundaries: Sequence[int] = (0,),
                       need_nnz: bool = False,
                       block_rows: int = 256,
                       interpret: Optional[bool] = None):
    """Whole-cohort fused upload pipeline: one kernel launch for every
    node's sparsify + nnz + clip + noise.

    flat (C, N) stacked per-node deltas (flattened cohort layout);
    residuals (C, N) DGC residuals, or None to skip sparsification
    (ratio >= 1); thresholds (C, L) per-node per-leaf DGC cutoffs (None iff
    residuals is None); seeds (C,) int32 node-distinct noise seeds;
    clip_scales (C,) f32 = 1/max(1, ‖upload_k‖/S), or None to skip the
    ALDP stage entirely (sigma == 0 — matching the reference pipeline,
    which leaves the deltas untouched rather than clipping noiselessly);
    boundaries: static start offset of each leaf in the flat layout.

    Returns (upload (C, N), residual' (C, N) or None, nnz (C,) i32 or
    None) — bit-equal to the unfused sparsify/nnz/ldp kernel chain.
    """
    k, n = flat.shape
    rows, block_rows, nb = block_layout(n, block_rows)
    do_sparsify = residuals is not None
    apply_ldp = clip_scales is not None
    sigma_s = float(sigma) * float(clip_s) if apply_ldp else 0.0

    args, in_specs = [], []
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    blkspec = pl.BlockSpec((1, block_rows, LANE), lambda i, j: (i, j, 0))
    if do_sparsify:
        args.append(thresholds.astype(jnp.float32))
        in_specs.append(smem)
    if sigma_s > 0.0:
        args.append(seeds.astype(jnp.int32))
        in_specs.append(smem)
    if apply_ldp:
        args.append(clip_scales.astype(jnp.float32))
        in_specs.append(smem)
    x = pad_blocks(flat, rows, block_rows, nb)
    args.append(x)
    in_specs.append(blkspec)
    if do_sparsify:
        args.append(pad_blocks(residuals, rows, block_rows, nb))
        in_specs.append(blkspec)

    out_specs = [blkspec]
    out_shape = [jax.ShapeDtypeStruct(x.shape, flat.dtype)]
    if do_sparsify:
        out_specs.append(blkspec)
        out_shape.append(jax.ShapeDtypeStruct(x.shape, residuals.dtype))
    if need_nnz:
        nnz_spec, nnz_shape = nnz_out(k)
        out_specs.append(nnz_spec)
        out_shape.append(nnz_shape)

    kernel = functools.partial(
        _fused_kernel, sigma_s=sigma_s, apply_ldp=apply_ldp,
        do_sparsify=do_sparsify, need_nnz=need_nnz, block_rows=block_rows,
        boundaries=tuple(int(b) for b in boundaries))
    outs = pl.pallas_call(
        kernel, grid=(k, nb), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret_mode(interpret),
        name="upload_fused")(*args)
    outs = list(outs)
    up = outs.pop(0).reshape(k, -1)[:, :n]
    newr = outs.pop(0).reshape(k, -1)[:, :n] if do_sparsify else None
    nnz = outs.pop(0)[:, 0, 0] if need_nnz else None
    return up, newr, nnz


# ---------------------------------------------------------------------------
# jnp mirror — the interpret-mode-safe fallback and the parity oracle
# ---------------------------------------------------------------------------

def block_noise(k: int, n: int, seeds: jnp.ndarray, sigma_s: float, *,
                block_rows: int = 256) -> jnp.ndarray:
    """The kernel's per-block counter-based Box–Muller noise, vectorized in
    plain jnp over the same padded (rows, LANE) layout: element e of block b
    of node i draws from hash(seeds[i] + b·7919, stream, e) exactly as the
    in-kernel generator does.  Returns the (k, n) noise the kernel adds."""
    rows, block_rows, _ = block_layout(n, block_rows)
    r = jnp.arange(rows, dtype=jnp.int32)
    blk = r // block_rows
    in_blk = (r % block_rows).astype(jnp.uint32)
    col = jnp.arange(LANE, dtype=jnp.uint32)
    # in-block element index, matching the kernel's broadcasted_iota layout
    x_idx = in_blk[:, None] * jnp.uint32(LANE) + col[None, :]
    blk_seed = (seeds.astype(jnp.int32)[:, None, None]
                + blk[None, :, None] * 7919)

    def hash_u(stream: int) -> jnp.ndarray:
        x = x_idx[None] + blk_seed.astype(jnp.uint32) * jnp.uint32(2654435761)
        x = x + jnp.uint32((stream * 0x9E3779B9) & 0xFFFFFFFF)
        return _finalize_uniform(x)

    u1 = jnp.maximum(hash_u(1), 1e-12)
    u2 = hash_u(2)
    noise = sigma_s * jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(
        (2.0 * math.pi) * u2)
    return noise.reshape(k, -1)[:, :n]


def spread_thresholds(thresholds: jnp.ndarray, boundaries: Sequence[int],
                      n: int) -> jnp.ndarray:
    """(C, L) per-leaf thresholds -> (C, N) per-element thresholds under the
    static leaf layout `boundaries` (start offsets, leaf L ends at n)."""
    ends = list(boundaries[1:]) + [n]
    return jnp.concatenate(
        [jnp.broadcast_to(thresholds[:, i:i + 1],
                          (thresholds.shape[0], ends[i] - int(b)))
         for i, b in enumerate(boundaries)], axis=1)


def upload_fused_reference(flat, residuals, thresholds, seeds, clip_scales,
                           sigma: float, clip_s: float, *,
                           boundaries: Sequence[int] = (0,),
                           need_nnz: bool = False, block_rows: int = 256):
    """Pure-jnp mirror of `upload_fused_fleet` — same signature and the
    same noise (replaying the kernel's blockwise hash streams bit-exactly).
    Sparsify/nnz outputs are bit-equal; the noised upload may differ by
    ~1 ulp where XLA contracts the kernel's scale-multiply + noise-add
    into an FMA."""
    k, n = flat.shape
    g = flat.astype(jnp.float32)
    newr = None
    if residuals is not None:
        c = g + residuals.astype(jnp.float32)
        keep = jnp.abs(c) >= spread_thresholds(thresholds, boundaries, n)
        up = jnp.where(keep, c, 0.0)
        newr = jnp.where(keep, 0.0, c).astype(residuals.dtype)
    else:
        up = g
    nnz = jnp.sum(up != 0.0, axis=1).astype(jnp.int32) if need_nnz else None
    if clip_scales is not None:
        up = up * clip_scales.astype(jnp.float32)[:, None]
        sigma_s = float(sigma) * float(clip_s)
        if sigma_s > 0.0:
            up = up + block_noise(k, n, seeds, sigma_s,
                                  block_rows=block_rows)
    return up.astype(flat.dtype), newr, nnz
