"""Fused DGC sparsify + residual accumulate — Pallas TPU kernel.

The paper's gradient-accumulation container (§5.1): combined = residual + g;
elements with |combined| >= threshold are uploaded, the rest stay in the
residual. Naively that is 4 HBM passes (add, compare, two selects); the
kernel does one read of (g, residual) and one write of (upload, residual').
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import LANE, block_layout, interpret_mode, pad_blocks


def _kernel(thr_ref, g_ref, r_ref, up_ref, newr_ref):
    c = g_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    keep = jnp.abs(c) >= thr_ref[0]
    up_ref[...] = jnp.where(keep, c, 0.0).astype(up_ref.dtype)
    newr_ref[...] = jnp.where(keep, 0.0, c).astype(newr_ref.dtype)


def _fleet_kernel(thr_ref, g_ref, r_ref, up_ref, newr_ref):
    """Node-batched variant: grid (node, block); per-node threshold in SMEM."""
    node = pl.program_id(0)
    c = g_ref[0].astype(jnp.float32) + r_ref[0].astype(jnp.float32)
    keep = jnp.abs(c) >= thr_ref[node]
    up_ref[0] = jnp.where(keep, c, 0.0).astype(up_ref.dtype)
    newr_ref[0] = jnp.where(keep, 0.0, c).astype(newr_ref.dtype)


def sparsify_flat(grad: jnp.ndarray, residual: jnp.ndarray,
                  threshold: jnp.ndarray, *, block_rows: int = 256,
                  interpret: Optional[bool] = None):
    """grad, residual (N,); threshold () f32 -> (upload (N,), residual' (N,))."""
    n = grad.shape[0]
    rows, block_rows, nb = block_layout(n, block_rows)
    g = pad_blocks(grad, rows, block_rows, nb)
    r = pad_blocks(residual, rows, block_rows, nb)
    blk = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))

    up, newr = pl.pallas_call(
        _kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), blk, blk],
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct(g.shape, grad.dtype),
                   jax.ShapeDtypeStruct(g.shape, residual.dtype)],
        interpret=interpret_mode(interpret),
        name="sparsify",
    )(threshold.reshape(1).astype(jnp.float32), g, r)
    return up.reshape(-1)[:n], newr.reshape(-1)[:n]


def sparsify_fleet(grads: jnp.ndarray, residuals: jnp.ndarray,
                   thresholds: jnp.ndarray, *, block_rows: int = 256,
                   interpret: Optional[bool] = None):
    """Whole-cohort DGC pass: one kernel launch for every node's upload split.

    grads, residuals (K, N); thresholds (K,) f32 — per-node magnitude cutoffs.
    Returns (uploads (K, N), residuals' (K, N)). Grid is (node, block) so the
    cohort shares a single device program instead of K dispatches.
    """
    k, n = grads.shape
    rows, block_rows, nb = block_layout(n, block_rows)
    g = pad_blocks(grads, rows, block_rows, nb)
    r = pad_blocks(residuals, rows, block_rows, nb)
    blk = pl.BlockSpec((1, block_rows, LANE), lambda i, j: (i, j, 0))

    up, newr = pl.pallas_call(
        _fleet_kernel,
        grid=(k, nb),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), blk, blk],
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct(g.shape, grads.dtype),
                   jax.ShapeDtypeStruct(g.shape, residuals.dtype)],
        interpret=interpret_mode(interpret),
        name="sparsify",
    )(thresholds.astype(jnp.float32), g, r)
    return (up.reshape(k, -1)[:, :n], newr.reshape(k, -1)[:, :n])
