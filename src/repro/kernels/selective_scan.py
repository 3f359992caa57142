"""Fused Mamba1 selective scan — Pallas TPU kernel.

§Perf pair 3 (EXPERIMENTS.md) showed falcon-mamba's training memory term is
dominated by the XLA scan's materialisation of the (B, c, d_inner, N)
decay/input tensors. This kernel is the structural fix: the SSM state h
(block_d, N) lives in VMEM scratch across the *sequential* L-grid dimension,
decays and input terms are built on-core per tile, and only x-sized inputs
and y-sized outputs ever touch HBM — the h_all tensor never exists.

Layout: x, dt (B, L, D); Bm, Cm (B, L, N); A (D, N); grid (B, D/bd, L/bl)
with L innermost (sequential ⇒ carry persists).

    h_t = exp(dt_t · A) ∘ h_{t-1} + (dt_t · x_t) ⊗ B_t
    y_t = (h_t · C_t) + D ∘ x_t        (D-residual applied by the caller)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, hout_ref, h_ref, *,
            bl: int, nl: int):
    il = pl.program_id(2)

    @pl.when(il == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...].astype(jnp.float32)                    # (bd, N)

    def step(t, h):
        xt = x_ref[0, t].astype(jnp.float32)              # (bd,)
        dtt = dt_ref[0, t].astype(jnp.float32)            # (bd,)
        bt = b_ref[0, t].astype(jnp.float32)              # (N,)
        ct = c_ref[0, t].astype(jnp.float32)              # (N,)
        decay = jnp.exp(dtt[:, None] * a)                 # (bd, N)
        h = decay * h + (dtt * xt)[:, None] * bt[None, :]
        y_ref[0, t] = (h @ ct).astype(y_ref.dtype)        # (bd,)
        return h

    h = jax.lax.fori_loop(0, bl, step, h_ref[...])
    h_ref[...] = h

    @pl.when(il == nl - 1)
    def _finish():
        hout_ref[0] = h.astype(hout_ref.dtype)


def selective_scan(x: jnp.ndarray, dt: jnp.ndarray, Bm: jnp.ndarray,
                   Cm: jnp.ndarray, A: jnp.ndarray, *, block_l: int = 128,
                   block_d: int = 256, interpret: Optional[bool] = None):
    """x, dt (B, L, D); Bm, Cm (B, L, N); A (D, N).

    Returns (y (B, L, D), h_final (B, D, N)). The caller applies the D-skip
    (`y + D*x`) and gating, matching `repro.models.ssm.mamba1_fwd` internals.
    """
    B, L, D = x.shape
    N = A.shape[1]
    bl = min(block_l, L)
    bd = min(block_d, D)
    nl = -(-L // bl)
    nd = -(-D // bd)
    pad_l = nl * bl - L
    pad_d = nd * bd - D
    if pad_l or pad_d:
        x = jnp.pad(x, ((0, 0), (0, pad_l), (0, pad_d)))
        dt = jnp.pad(dt, ((0, 0), (0, pad_l), (0, pad_d)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad_l), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad_l), (0, 0)))
        A = jnp.pad(A, ((0, pad_d), (0, 0)))

    kernel = functools.partial(_kernel, bl=bl, nl=nl)
    y, h = pl.pallas_call(
        kernel,
        grid=(B, nd, nl),
        in_specs=[
            pl.BlockSpec((1, bl, bd), lambda b, d, l: (b, l, d)),   # x
            pl.BlockSpec((1, bl, bd), lambda b, d, l: (b, l, d)),   # dt
            pl.BlockSpec((1, bl, N), lambda b, d, l: (b, l, 0)),    # B
            pl.BlockSpec((1, bl, N), lambda b, d, l: (b, l, 0)),    # C
            pl.BlockSpec((bd, N), lambda b, d, l: (d, 0)),          # A
        ],
        out_specs=[
            pl.BlockSpec((1, bl, bd), lambda b, d, l: (b, l, d)),   # y
            pl.BlockSpec((1, bd, N), lambda b, d, l: (b, d, 0)),    # h_final
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nl * bl, nd * bd), x.dtype),
            jax.ShapeDtypeStruct((B, nd * bd, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(x, dt, Bm, Cm, A)
    return y[:, :L, :D], h[:, :D]
