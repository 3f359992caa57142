"""Local gradient accumulation + magnitude-first upload — paper §5.1.

"we prefer to upload gradients with large values … small gradient updates are
accumulated in the gradient accumulation container" — the DGC-style scheme
(Lin et al. 2018) the paper adopts. Each node keeps a residual pytree; at
upload time the combined (residual + new gradient) tensor is split into a
sparse large-magnitude part (uploaded) and a small-magnitude part (kept).

The Pallas kernel `repro.kernels.upload_fused` fuses the
threshold+accumulate pass with the rest of the upload for TPU; this module
is the jnp reference and the pytree-level orchestration.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def init_residual(params):
    return jax.tree.map(lambda x: jnp.zeros_like(x, dtype=jnp.float32), params)


def leaf_threshold(combined: jnp.ndarray, ratio: float) -> jnp.ndarray:
    """The DGC magnitude cutoff for one leaf: |value| quantile at 1−ratio.

    Single source of the threshold rule — the batched Pallas backend
    (`repro.fleet.engine`) uses the same cutoff with a `>=` keep test, so
    both paths stay in lockstep by construction.
    """
    flat = jnp.abs(combined.reshape(-1)).astype(jnp.float32)
    return jnp.quantile(flat, 1.0 - ratio)


def sparsify_leaf(combined: jnp.ndarray, ratio: float
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Keep the top-`ratio` fraction by |value|; rest becomes the residual."""
    if ratio >= 1.0:
        return combined, jnp.zeros_like(combined)
    thr = leaf_threshold(combined, ratio)
    mask = jnp.abs(combined) >= thr
    upload = jnp.where(mask, combined, 0)
    residual = jnp.where(mask, 0, combined)
    return upload, residual


def accumulate_and_sparsify(residual, grad, ratio: float):
    """Returns (upload_tree, new_residual_tree, upload_fraction).

    upload_tree is dense-with-zeros (the sparse gradient); on a real wire it
    would be sent as (indices, values) — `upload_bytes` reports that size.
    """
    combined = jax.tree.map(
        lambda r, g: r + g.astype(jnp.float32), residual, grad)
    pairs = jax.tree.map(lambda c: sparsify_leaf(c, ratio), combined)
    upload = jax.tree.map(lambda p: p[0], pairs,
                          is_leaf=lambda x: isinstance(x, tuple))
    new_residual = jax.tree.map(lambda p: p[1], pairs,
                                is_leaf=lambda x: isinstance(x, tuple))
    nnz = sum(jnp.sum(u != 0) for u in jax.tree.leaves(upload))
    total = sum(u.size for u in jax.tree.leaves(upload))
    return upload, new_residual, nnz / total


def upload_bytes(tree, ratio: float, bytes_per_value: int = 4,
                 bytes_per_index: int = 4) -> int:
    """Analytic wire size of a sparsified upload (values + indices) —
    delegates to the shared `repro.net` fallback so this and
    `fleet.stages.bytes_per_node` can never drift (tests/test_net.py pins
    both).  Byte-accurate measured accounting lives in `repro.net`."""
    from ..net.codecs import analytic_upload_bytes
    total = sum(x.size for x in jax.tree.leaves(tree))
    return analytic_upload_bytes(total, ratio, bytes_per_value,
                                 bytes_per_index)
