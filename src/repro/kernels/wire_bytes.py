"""Node-batched wire accounting — Pallas TPU kernel.

`repro.net` prices every upload from its nonzero count (sparse codecs
encode exactly the nonzero coordinates).  Counting nonzeros over a stacked
(K, P) cohort naively reads the whole cohort once per reduction step; this
kernel uses the fleet kernels' grid (node, block), one
VMEM pass per block — and accumulates each node's count into a revisited
lane-dense (1, 1, NNZ_LANES) output block, so the whole cohort is priced
in a single launch.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import LANE, block_layout, interpret_mode, pad_blocks

# The per-node count is broadcast across one lane row: a (1, 1) block of a
# (K, 1) output is not tileable on the TPU, a (1, 1, 128) block of a
# (K, 1, 128) output is.
NNZ_LANES = 128


def nnz_out(k: int):
    """(BlockSpec, ShapeDtypeStruct) of the (K, 1, NNZ_LANES) per-node
    count output of a grid-(node, block) kernel."""
    return (pl.BlockSpec((1, 1, NNZ_LANES), lambda i, j: (i, 0, 0)),
            jax.ShapeDtypeStruct((k, 1, NNZ_LANES), jnp.int32))


def accumulate_nnz(nnz_ref, blk, up) -> None:
    """Add the block's nonzero count into the node's count row (zeroed on
    the node's first block)."""
    cnt = jnp.sum((up != 0.0).astype(jnp.int32))

    @pl.when(blk == 0)
    def _init():
        nnz_ref[...] = jnp.zeros(nnz_ref.shape, jnp.int32)

    nnz_ref[...] += cnt


def _fleet_kernel(g_ref, out_ref):
    """Grid (node, block): out[node] accumulates the block's nonzero count
    (zero padding contributes nothing by construction)."""
    accumulate_nnz(out_ref, pl.program_id(1), g_ref[0])


def nnz_fleet(flat: jnp.ndarray, *, block_rows: int = 256,
              interpret: Optional[bool] = None) -> jnp.ndarray:
    """Per-node nonzero counts of a stacked cohort in one kernel launch.

    flat (K, N) — stacked flattened uploads.  Returns (K,) int32.
    """
    k, n = flat.shape
    rows, block_rows, nb = block_layout(n, block_rows)
    g = pad_blocks(flat, rows, block_rows, nb)
    out_spec, out_shape = nnz_out(k)
    out = pl.pallas_call(
        _fleet_kernel,
        grid=(k, nb),
        in_specs=[
            pl.BlockSpec((1, block_rows, LANE), lambda i, j: (i, j, 0)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret_mode(interpret),
        name="wire_bytes",
    )(g)
    return out[:, 0, 0]
