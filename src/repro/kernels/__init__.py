"""Pallas kernels plus the two things every wrapper shares: how interpret
mode is chosen, and the padded (rows, LANE) block layout of a flat
per-node vector."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

LANE = 1024          # columns of the padded (rows, LANE) layout


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas call runs in the interpreter.

    ``None`` (every wrapper's default) follows the platform: the CPU has no
    Pallas compiler, so it interprets; a TPU compiles.  Any other platform
    is an error rather than a silent interpreter run.  An explicit bool
    wins — tests pass ``False`` to compile against a described chip."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas mode for platform {backend!r}: the "
                       f"kernels compile for 'tpu' and interpret on 'cpu'")


def block_layout(n: int, block_rows: int) -> Tuple[int, int, int]:
    """(rows, block_rows, n_blocks) of an n-element vector laid out as
    (rows, LANE).  ``block_rows`` is clamped to the rows one vector needs,
    rounded up to a multiple of 8, so a small model is not padded out to a
    full 256-row block.  A single block keeps its in-block element index
    ``row * LANE + col`` either way, so the clamp never moves a noise
    stream."""
    rows = -(-n // LANE)
    block_rows = min(int(block_rows), -(-rows // 8) * 8)
    return rows, block_rows, -(-rows // block_rows)


def pad_blocks(a: jnp.ndarray, rows: int, block_rows: int,
               n_blocks: int) -> jnp.ndarray:
    """(..., n) -> (..., n_blocks * block_rows, LANE), zero padded."""
    lead, n = a.shape[:-1], a.shape[-1]
    x = jnp.pad(a, [(0, 0)] * len(lead) + [(0, rows * LANE - n)])
    x = x.reshape(lead + (rows, LANE))
    pad_r = n_blocks * block_rows - rows
    if pad_r:
        x = jnp.pad(x, [(0, 0)] * len(lead) + [(0, pad_r), (0, 0)])
    return x
