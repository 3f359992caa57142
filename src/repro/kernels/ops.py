"""Jitted public wrapper around the attention Pallas kernel.

`attention_pallas` adapts the model layout (B, S, H, D) to the kernel
layout.  It takes `interpret=` (None = by platform, see
`kernels.interpret_mode`; True = interpreter; False = compiled).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from .flash_attention import flash_attention


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
