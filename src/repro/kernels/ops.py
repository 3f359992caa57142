"""Jitted public wrappers around the Pallas kernels.

`attention_pallas` adapts the model layout (B, S, H, D) to the kernel layout;
`aldp_perturb_pallas` applies the fused clip+noise kernel across a parameter
pytree (one flat pass per leaf, node-seeded); `sparsify_pallas` runs the DGC
container update on a pytree with a given keep-ratio.

All wrappers take `interpret=` (None = by platform, see
`kernels.interpret_mode`; True = interpreter; False = compiled).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.aldp import global_norm
from .flash_attention import flash_attention
from .ldp_noise import ldp_perturb_flat
from .sparsify import sparsify_flat


@partial(jax.jit, static_argnames=("causal", "window", "interpret"))
def attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                     interpret: Optional[bool] = None):
    """Model layout: q (B, S, H, D); k, v (B, S, KV, D)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = flash_attention(qt, kt, vt, causal=causal, window=window,
                        interpret=interpret)
    return o.transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("sigma", "clip_s", "interpret"))
def aldp_perturb_pallas(tree, seed: jnp.ndarray, *, sigma: float,
                        clip_s: float, interpret: Optional[bool] = None):
    """Pytree clip-at-S + Gaussian noise, fused per leaf (Eq. 8)."""
    nrm = global_norm(tree)
    scale = 1.0 / jnp.maximum(1.0, nrm / clip_s)
    leaves, treedef = jax.tree.flatten(tree)
    out = []
    for i, leaf in enumerate(leaves):
        flat = leaf.reshape(-1)
        pert = ldp_perturb_flat(flat, seed + i * 7919, scale, sigma, clip_s,
                                interpret=interpret)
        out.append(pert.reshape(leaf.shape).astype(leaf.dtype))
    return jax.tree.unflatten(treedef, out), nrm


@partial(jax.jit, static_argnames=("ratio", "interpret"))
def sparsify_pallas(grad_tree, residual_tree, *, ratio: float,
                    interpret: Optional[bool] = None) -> Tuple[object, object]:
    """DGC container update at keep-`ratio` (threshold from |combined|
    quantile, computed in jnp; the elementwise pass is the fused kernel)."""
    g_leaves, treedef = jax.tree.flatten(grad_tree)
    r_leaves = jax.tree.leaves(residual_tree)
    combined_abs = jnp.concatenate(
        [jnp.abs(g.reshape(-1).astype(jnp.float32) +
                 r.reshape(-1).astype(jnp.float32))
         for g, r in zip(g_leaves, r_leaves)])
    thr = jnp.quantile(combined_abs, 1.0 - ratio) if ratio < 1.0 else \
        jnp.zeros((), jnp.float32)
    ups, news = [], []
    for g, r in zip(g_leaves, r_leaves):
        up, nr = sparsify_flat(g.reshape(-1), r.reshape(-1), thr,
                               interpret=interpret)
        ups.append(up.reshape(g.shape))
        news.append(nr.reshape(r.shape))
    return jax.tree.unflatten(treedef, ups), jax.tree.unflatten(treedef, news)
