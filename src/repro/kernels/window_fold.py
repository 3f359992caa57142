"""Arrival-ordered window fold — Pallas TPU kernel.

The async engines' sequential window fold (Eq. (6)/`mix_stale` in arrival
order, `async_engine.make_window_folds`) is a `lax.scan` whose carry is the
whole parameter vector: every arrival reads the running params + its omega
from HBM and writes the new params + a per-arrival snapshot back (~4P of
traffic per arrival).  The detection ring / staleness / version bookkeeping
in that scan is all scalar work, so the fold splits exactly in two:

  1. a scalar *control scan* (in the engine) over (accuracy, staleness,
     arrival) that pushes the detection ring and emits, per arrival, a gate
     bit and the two mix coefficients (a_i, b_i) such that
     params_i = gate_i ? a_i·params_{i-1} + b_i·omega_i : params_{i-1}
     — (α, 1−α) for Eq. (6), ((1−w), w) with w = (1−α)(τ+1)^−a for the
     FedAsync staleness-adaptive mix;
  2. this kernel: grid (param_block, arrival) with arrivals innermost, so
     each param block stays resident in VMEM as the running accumulator
     across the whole window — per arrival it reads one omega block and
     writes one snapshot block (~2P per arrival, and the carry never
     round-trips HBM).

The per-arrival snapshots are still produced (they are the redispatch
payload — each processed node receives the model right after its own
arrival), but the running carry is not materialized per step.

Parity: bit-equal to the reference scan for float32 params — same
multiply/add expression `a·params + b·omega`, same `where(gate, ...)`
selection (a gated-off arrival leaves params bitwise untouched).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import LANE, block_layout, interpret_mode, pad_blocks


def _fold_kernel(gate_ref, a_ref, b_ref, p_ref, om_ref, seq_ref, out_ref):
    i = pl.program_id(1)                 # arrival (innermost: the out_ref
                                         # block is the resident accumulator)
    @pl.when(i == 0)
    def _init():
        out_ref[...] = p_ref[...]
    cur = out_ref[...]
    new = a_ref[i] * cur + b_ref[i] * om_ref[0]
    cur = jnp.where(gate_ref[i] != 0, new, cur)
    seq_ref[0] = cur
    out_ref[...] = cur


def window_fold_fleet(p_flat: jnp.ndarray, om_flat: jnp.ndarray,
                      gates: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray, *,
                      block_rows: int = 256,
                      interpret: Optional[bool] = None):
    """Fold a window of arrivals into the flattened global params.

    p_flat (N,) f32 params; om_flat (C, N) f32 per-arrival node models in
    arrival order; gates (C,) bool/int mix gates (False = rejected or
    padded slot, params pass through bitwise); a, b (C,) f32 coefficients
    on (params, omega) per arrival.

    Returns (final params (N,), per-arrival snapshots (C, N)).
    """
    c, n = om_flat.shape
    rows, block_rows, nb = block_layout(n, block_rows)
    p = pad_blocks(p_flat.astype(jnp.float32), rows, block_rows, nb)
    om = pad_blocks(om_flat.astype(jnp.float32), rows, block_rows, nb)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    p_blk = pl.BlockSpec((block_rows, LANE), lambda j, i: (j, 0))
    om_blk = pl.BlockSpec((1, block_rows, LANE), lambda j, i: (i, j, 0))
    seq, final = pl.pallas_call(
        _fold_kernel,
        grid=(nb, c),
        in_specs=[smem, smem, smem, p_blk, om_blk],
        out_specs=[om_blk, p_blk],
        out_shape=[jax.ShapeDtypeStruct(om.shape, jnp.float32),
                   jax.ShapeDtypeStruct(p.shape, jnp.float32)],
        interpret=interpret_mode(interpret),
        name="window_fold",
    )(gates.astype(jnp.int32), a.astype(jnp.float32),
      b.astype(jnp.float32), p, om)
    return final.reshape(-1)[:n], seq.reshape(c, -1)[:, :n]


def window_fold_reference(p_flat: jnp.ndarray, om_flat: jnp.ndarray,
                          gates: jnp.ndarray, a: jnp.ndarray,
                          b: jnp.ndarray):
    """Pure-jnp mirror of `window_fold_fleet` (a lax.scan) — the parity
    oracle; bit-equal for f32 inputs.

    It folds the vector zero-padded to whole LANE rows, as the kernel
    does, not the bare n-vector, so that XLA:CPU compiles
    `a·params + b·omega` to the same vector loop as the interpreted
    kernel's block and contracts it into the same FMA.  On the bare
    vector, one arrival of n=1 compiles to scalar code that contracts the
    other product, one ulp off the kernel."""
    n = p_flat.shape[0]
    pad = -n % LANE
    p = jnp.pad(p_flat.astype(jnp.float32), (0, pad))
    om = jnp.pad(om_flat.astype(jnp.float32), ((0, 0), (0, pad)))

    def body(cur, inp):
        om_i, g_i, a_i, b_i = inp
        new = a_i * cur + b_i * om_i
        cur = jnp.where(g_i, new, cur)
        return cur, cur

    final, seq = jax.lax.scan(
        body, p, (om, gates.astype(bool), a.astype(jnp.float32),
                  b.astype(jnp.float32)))
    return final[:n], seq[:, :n]
