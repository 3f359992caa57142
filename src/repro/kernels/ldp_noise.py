"""Fused ALDP perturbation — Pallas TPU kernel.

The paper's node-side hot loop (Eq. 8) is three memory-bound passes in naive
form: scale-by-clip, sample Gaussian noise, add. This kernel fuses them into
a single HBM pass over the flattened gradient: each (rows × 1024) VMEM block
is scaled by the precomputed clip factor and perturbed with Gaussian noise
generated on-core (counter hash + Box–Muller), so noise never touches HBM.

The global L2 norm is a separate reduction pass (unavoidable data dependency:
the clip scale needs the whole-tensor norm before any output element).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import LANE, block_layout, interpret_mode, pad_blocks


def _hash_uniform(seed: jnp.ndarray, stream: int, shape) -> jnp.ndarray:
    """Counter-based uniform(0,1) from a murmur3-finalizer hash of the
    per-element index — pure u32 VPU ops, identical on CPU interpret and TPU.
    (pltpu.prng_random_bits has no CPU-interpret lowering in this jax build.)
    """
    rows = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    x = rows * jnp.uint32(shape[1]) + cols
    x = x + seed.astype(jnp.uint32) * jnp.uint32(2654435761)
    x = x + jnp.uint32((stream * 0x9E3779B9) & 0xFFFFFFFF)
    return _finalize_uniform(x)


def _finalize_uniform(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 finalizer of u32 counters -> uniform [0, 1) f32.  The top 24
    bits fit an int32 exactly, and the TPU has no u32 -> f32 convert, so the
    cast goes through int32 (same values on every backend)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return ((x >> 8).astype(jnp.int32).astype(jnp.float32)
            / jnp.float32(1 << 24))


def _kernel(seed_ref, scale_ref, g_ref, o_ref, *, sigma_s: float):
    pid = pl.program_id(0)
    g = g_ref[...].astype(jnp.float32) * scale_ref[0]
    if sigma_s > 0.0:
        shape = g.shape
        blk_seed = seed_ref[0] + pid * 7919
        # Box–Muller from two independent uniform draws
        u1 = jnp.maximum(_hash_uniform(blk_seed, 1, shape), 1e-12)
        u2 = _hash_uniform(blk_seed, 2, shape)
        r = jnp.sqrt(-2.0 * jnp.log(u1))
        theta = (2.0 * math.pi) * u2
        g = g + sigma_s * r * jnp.cos(theta)
    o_ref[...] = g.astype(o_ref.dtype)


def _fleet_kernel(seed_ref, scale_ref, g_ref, o_ref, *, sigma_s: float):
    """Node-batched variant: grid (node, block); per-node seed/scale in SMEM.

    Per-block seeding matches the flat kernel (`seed + block*7919`) so each
    node's output is bit-identical to a standalone `ldp_perturb_flat` call
    with that node's seed.
    """
    node = pl.program_id(0)
    pid = pl.program_id(1)
    g = g_ref[0].astype(jnp.float32) * scale_ref[node]
    if sigma_s > 0.0:
        shape = g.shape
        blk_seed = seed_ref[node] + pid * 7919
        u1 = jnp.maximum(_hash_uniform(blk_seed, 1, shape), 1e-12)
        u2 = _hash_uniform(blk_seed, 2, shape)
        r = jnp.sqrt(-2.0 * jnp.log(u1))
        theta = (2.0 * math.pi) * u2
        g = g + sigma_s * r * jnp.cos(theta)
    o_ref[0] = g.astype(o_ref.dtype)


def ldp_perturb_flat(flat: jnp.ndarray, seed: jnp.ndarray,
                     clip_scale: jnp.ndarray, sigma: float, clip_s: float,
                     *, block_rows: int = 256,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """flat (N,) float; seed () int32; clip_scale () float32 = 1/max(1,‖g‖/S).

    Returns clip_scale·flat + N(0, (σS)²) with the same shape/dtype.
    """
    n = flat.shape[0]
    rows, block_rows, nb = block_layout(n, block_rows)
    x = pad_blocks(flat, rows, block_rows, nb)

    kernel = functools.partial(_kernel, sigma_s=float(sigma) * float(clip_s))
    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, flat.dtype),
        interpret=interpret_mode(interpret),
        name="ldp_noise",
    )(seed.reshape(1).astype(jnp.int32), clip_scale.reshape(1).astype(jnp.float32), x)
    return out.reshape(-1)[:n]


def ldp_perturb_fleet(flat: jnp.ndarray, seeds: jnp.ndarray,
                      clip_scales: jnp.ndarray, sigma: float, clip_s: float,
                      *, block_rows: int = 256,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """Whole-cohort ALDP pass: one kernel launch perturbs every node's delta.

    flat (K, N) stacked per-node deltas; seeds (K,) int32 (must be distinct
    per node — node-local noise); clip_scales (K,) f32 = 1/max(1,‖g_k‖/S).
    Returns clip_scales[:,None]·flat + N(0, (σS)²), shape/dtype preserved.
    """
    k, n = flat.shape
    rows, block_rows, nb = block_layout(n, block_rows)
    x = pad_blocks(flat, rows, block_rows, nb)

    kernel = functools.partial(_fleet_kernel,
                               sigma_s=float(sigma) * float(clip_s))
    blk = pl.BlockSpec((1, block_rows, LANE), lambda i, j: (i, j, 0))
    out = pl.pallas_call(
        kernel,
        grid=(k, nb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            blk,
        ],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct(x.shape, flat.dtype),
        interpret=interpret_mode(interpret),
        name="ldp_noise",
    )(seeds.astype(jnp.int32), clip_scales.astype(jnp.float32), x)
    return out.reshape(k, -1)[:, :n]
