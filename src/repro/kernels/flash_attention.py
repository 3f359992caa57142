"""Blocked causal GQA flash attention — Pallas TPU kernel.

TPU-native adaptation of the attention hot loop (dominates prefill_32k):
q/k/v tiles live in VMEM (BlockSpec below), the kv axis is the innermost
*sequential* grid dimension so the online-softmax accumulators persist in
VMEM scratch across kv steps, and fully-masked kv blocks are skipped with
``pl.when`` (causal/sliding-window block skipping — the structural win over
the jnp reference, which masks but still computes).

Layout: q (B, H, Sq, D), k/v (B, KV, Sk, D), GQA via h -> h // (H // KV).
Block sizes default to MXU/VPU-aligned (128, 128).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, nk: int, causal: bool, window: int,
            sq: int, sk: int, scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # block-level relevance: skip blocks that are entirely masked
    first_q = iq * bq
    last_q = iq * bq + bq - 1
    first_k = ik * bk
    last_k = ik * bk + bk - 1
    relevant = True
    if causal:
        relevant = jnp.logical_and(relevant, first_k <= last_q)
    if window > 0:
        relevant = jnp.logical_and(relevant, last_k > first_q - window)

    @pl.when(relevant)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = kpos < sk
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window > 0:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q (B, H, Sq, D); k, v (B, KV, Sk, D) -> (B, H, Sq, D)."""
    B, H, Sq, D = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    bq = min(bq, max(Sq, 8))
    bk = min(bk, max(Sk, 8))
    nq = -(-Sq // bq)
    nk = -(-Sk // bk)
    pad_q = nq * bq - Sq
    pad_k = nk * bk - Sk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _kernel, bq=bq, bk=bk, nk=nk, causal=causal, window=window,
        sq=Sq, sk=Sk, scale=1.0 / math.sqrt(D))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max m
            pltpu.VMEM((bq, 1), jnp.float32),     # running denom l
            pltpu.VMEM((bq, D), jnp.float32),     # output accumulator
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
    return out[:, :, :Sq]
