"""Fused Mamba2 SSD chunk scan — Pallas TPU kernel (zamba2 family).

The XLA path (`repro.models.ssm.mamba2_fwd`) materialises per-chunk decay
matrices and carries chunk states through HBM. Here the running state
h (bh, P, N) lives in VMEM scratch across the sequential chunk-grid
dimension; the intra-chunk SSD matmuls (scores = C·Bᵀ masked by the decay
kernel) and the inter-chunk state propagation happen on-core, so HBM sees
only x-sized inputs and y-sized outputs.

Shapes: x (B, L, H, P); dt (B, L, H); Bm, Cm (B, L, N) (n_groups == 1,
broadcast over heads); A (H,). Grid (B, H/bh, L/c), L innermost.
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
            c: int, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...].astype(jnp.float32)                 # (bh,)
    dt = dt_ref[0].astype(jnp.float32)                 # (c, bh)
    x = x_ref[0].astype(jnp.float32)                   # (c, bh, P)
    Bm = b_ref[0].astype(jnp.float32)                  # (c, N)
    Cm = c_ref[0].astype(jnp.float32)                  # (c, N)

    la = dt * a[None, :]                               # (c, bh) log decay
    lcum = jnp.cumsum(la, axis=0)                      # (c, bh)
    dx = dt[..., None] * x                             # (c, bh, P)

    # intra-chunk (diagonal) term: masked decay kernel × scores
    scores = Cm @ Bm.T                                 # (c, c) group-shared
    decay = jnp.exp(lcum[:, None, :] - lcum[None, :, :])   # (c_t, c_s, bh)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))
    M = jnp.where(tri[..., None], decay * scores[..., None], 0.0)
    y = jnp.einsum("tsh,shp->thp", M, dx)

    # inter-chunk: contribution of the carried state
    h = h_ref[...]                                     # (bh, P, N)
    y += jnp.einsum("tn,hpn->thp", Cm, h) * jnp.exp(lcum)[..., None]

    # state update
    tail = jnp.exp(lcum[-1:, :] - lcum)                # (c, bh)
    h_ref[...] = (jnp.exp(lcum[-1])[:, None, None] * h
                  + jnp.einsum("sn,shp->hpn", Bm, dx * tail[..., None]))
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _finish():
        hout_ref[0] = h_ref[...].astype(hout_ref.dtype)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, Bm: jnp.ndarray,
             Cm: jnp.ndarray, A: jnp.ndarray, *, chunk: int = 128,
             block_h: int = 8, interpret: Optional[bool] = None):
    """Returns (y (B, L, H, P), h_final (B, H, P, N)).

    Caller applies the D-skip and gated norm (`models.ssm.mamba2_fwd`)."""
    B, L, H, P = x.shape
    N = Bm.shape[2]
    c = min(chunk, L)
    bh = min(block_h, H)
    nc = -(-L // c)
    nh = -(-H // bh)
    pad_l = nc * c - L
    pad_h = nh * bh - H
    if pad_l or pad_h:
        x = jnp.pad(x, ((0, 0), (0, pad_l), (0, pad_h), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad_l), (0, pad_h)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad_l), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad_l), (0, 0)))
        A = jnp.pad(A, (0, pad_h))

    kernel = functools.partial(_kernel, c=c, nc=nc)
    y, h = pl.pallas_call(
        kernel,
        grid=(B, nh, nc),
        in_specs=[
            pl.BlockSpec((1, c, bh, P), lambda b, h_, l: (b, l, h_, 0)),  # x
            pl.BlockSpec((1, c, bh), lambda b, h_, l: (b, l, h_)),        # dt
            pl.BlockSpec((1, c, N), lambda b, h_, l: (b, l, 0)),          # B
            pl.BlockSpec((1, c, N), lambda b, h_, l: (b, l, 0)),          # C
            pl.BlockSpec((bh,), lambda b, h_, l: (h_,)),                  # A
        ],
        out_specs=[
            pl.BlockSpec((1, c, bh, P), lambda b, h_, l: (b, l, h_, 0)),
            pl.BlockSpec((1, bh, P, N), lambda b, h_, l: (b, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nc * c, nh * bh, P), x.dtype),
            jax.ShapeDtypeStruct((B, nh * bh, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bh, P, N), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(x, dt, Bm, Cm, A)
    return y[:, :L, :H], h[:, :H]
