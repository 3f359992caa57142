"""Operations and bytes the algorithm needs, counted from shapes.

The model's own counts are its module's (`bench/models/<model>.py`):
`n_params`, P, the length of a node's flat upload; `update_flops`, the
useful FLOPs of one node update; `record_flops`, those of a record beyond
its updates.  What the fleet's kernels move is counted here, once for
every model."""
from __future__ import annotations

from .cells import model_module


def n_params(config: dict) -> int:
    return model_module(config).n_params(config)


def update_flops(config: dict) -> int:
    return model_module(config).update_flops(config)


def record_flops(config: dict) -> int:
    return model_module(config).record_flops(config)


def upload_fused_bytes(c: int, p: int) -> int:
    """HBM bytes of one fused upload over a C-node cohort of P-float
    nodes: delta and residual in, upload and residual out, f32."""
    return 16 * c * p
