"""Operations and bytes the algorithm needs, counted from shapes.

The model is the paper's CNN (`cnn_forward`): two 3x3 stride-2 SAME
convolutions and one dense layer.  A multiply-add counts as two FLOPs; a
training step counts as three forward passes (forward, and the backward
pass's two products).  Bias adds, activations and the loss are left out:
they are a rounding error next to the products."""
from __future__ import annotations


def _same_out(n: int, stride: int) -> int:
    return -(-n // stride)


def cnn_forward_flops(hw=(28, 28), ch: int = 1, c1: int = 16, c2: int = 32,
                      n_classes: int = 10) -> int:
    """FLOPs of one sample's forward pass."""
    h1, w1 = _same_out(hw[0], 2), _same_out(hw[1], 2)
    h2, w2 = _same_out(h1, 2), _same_out(w1, 2)
    macs = (h1 * w1 * c1 * 9 * ch          # conv1
            + h2 * w2 * c2 * 9 * c1        # conv2
            + h2 * w2 * c2 * n_classes)    # fc
    return 2 * macs


def cnn_params(hw=(28, 28), ch: int = 1, c1: int = 16, c2: int = 32,
               n_classes: int = 10) -> int:
    """P: the CNN's parameter count, the length of a node's flat upload."""
    h2, w2 = _same_out(_same_out(hw[0], 2), 2), _same_out(_same_out(hw[1], 2), 2)
    return (9 * ch * c1 + c1 + 9 * c1 * c2 + c2
            + h2 * w2 * c2 * n_classes + n_classes)


def model_dims(config: dict) -> dict:
    """The `cnn_forward_flops` keywords of a configuration file."""
    return {"hw": tuple(config["hw"]), "ch": config["channels"],
            "c1": config["c1"], "c2": config["c2"],
            "n_classes": config["n_classes"]}


def update_flops(config: dict) -> int:
    """Useful FLOPs of one node update: local SGD on the node, and the
    cloud's forward pass of the uploaded model over its test set."""
    f = cnn_forward_flops(**model_dims(config))
    return (3 * f * config["local_steps"] * config["batch_size"]
            + f * config["n_cloud_test"])


def record_flops(config: dict) -> int:
    """FLOPs of one record beyond its updates: the global model's forward
    pass over the test set."""
    return cnn_forward_flops(**model_dims(config)) * config["n_test"]


def upload_fused_bytes(c: int, p: int) -> int:
    """HBM bytes of one fused upload over a C-node cohort of P-float
    nodes: delta and residual in, upload and residual out, f32."""
    return 16 * c * p
