"""The paper's CNN (arXiv 2012.04436 §6.1): two 3x3 stride-2 SAME
convolutions with ReLU and one dense layer, on MNIST-shaped images.

Inputs.  The shared MNIST-shaped images and label flip
(`bench/models/_images.py`), then the initial weights.

Counts.  A multiply-add counts as two FLOPs; a training step counts as
three forward passes (forward, and the backward pass's two products).
Bias adds, activations and the loss are left out: they are a rounding
error next to the products."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import _images

# the configuration's keys this module reads
SETTINGS = _images.SETTINGS + ("c1", "c2")
# nodes the reference trains at once, and test rows a forward pass takes
NODE_BLOCK = 100
TEST_BLOCK = 2000
# the leaf the reference's `altered` fault doubles
ALTERED = ("conv1", "b")


# ---------------------------------------------------------------------------
# inputs: nothing here imports the program
# ---------------------------------------------------------------------------

def init_params(config: dict, rng: np.random.Generator) -> dict:
    """He-style normal weights (1/sqrt(fan_in)) and zero biases, in the
    program's parameter tree."""
    ch, c1, c2, n_cls = (config["channels"], config["c1"], config["c2"],
                         config["n_classes"])
    h, w = config["hw"]
    flat = -(-h // 4) * -(-w // 4) * c2

    def normal(shape, fan_in):
        return (rng.standard_normal(shape) / np.sqrt(fan_in)).astype(np.float32)

    return {"conv1": {"w": normal((3, 3, ch, c1), 9 * ch),
                      "b": np.zeros((c1,), np.float32)},
            "conv2": {"w": normal((3, 3, c1, c2), 9 * c1),
                      "b": np.zeros((c2,), np.float32)},
            "fc": {"w": normal((flat, n_cls), flat),
                   "b": np.zeros((n_cls,), np.float32)}}


def make_inputs(config: dict, seed: int) -> dict:
    rng, data = _images.image_data(config, seed)
    return dict(data, params=init_params(config, rng))


# ---------------------------------------------------------------------------
# program hooks
# ---------------------------------------------------------------------------

spec_fields = _images.spec_fields
population_fields = _images.population_fields


def program_fns():
    """`loss_fn` and `acc_fn` of the program's `Population`."""
    from repro.models.cnn import cnn_accuracy, cnn_loss
    return cnn_loss, cnn_accuracy


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------

def forward(p, x, precision, extra):
    def conv(h, w, b):
        return jax.lax.conv_general_dilated(
            h, w, (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=precision) + b
    h = jax.nn.relu(conv(x, p["conv1"]["w"], p["conv1"]["b"]))
    h = jax.nn.relu(conv(h, p["conv2"]["w"], p["conv2"]["b"]))
    h = h.reshape(h.shape[0], -1)
    return jnp.dot(h, p["fc"]["w"], precision=precision) + p["fc"]["b"]


def loss(p, x, y, precision, extra):
    logp = jax.nn.log_softmax(forward(p, x, precision, extra))
    return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()


accuracy = _images.accuracy


def control_kwargs(config: dict) -> dict:
    """The control: everything in bfloat16, below the stated float32."""
    return {"dtype": jnp.bfloat16}


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------

def _dims(config: dict):
    h1, w1 = -(-config["hw"][0] // 2), -(-config["hw"][1] // 2)
    return h1, w1, -(-h1 // 2), -(-w1 // 2)


def forward_flops(config: dict) -> int:
    """FLOPs of one sample's forward pass."""
    ch, c1, c2, n_cls = (config["channels"], config["c1"], config["c2"],
                         config["n_classes"])
    h1, w1, h2, w2 = _dims(config)
    macs = (h1 * w1 * c1 * 9 * ch          # conv1
            + h2 * w2 * c2 * 9 * c1        # conv2
            + h2 * w2 * c2 * n_cls)        # fc
    return 2 * macs


def n_params(config: dict) -> int:
    """P: the parameter count, the length of a node's flat upload."""
    ch, c1, c2, n_cls = (config["channels"], config["c1"], config["c2"],
                         config["n_classes"])
    _, _, h2, w2 = _dims(config)
    return 9 * ch * c1 + c1 + 9 * c1 * c2 + c2 + h2 * w2 * c2 * n_cls + n_cls


def update_flops(config: dict) -> int:
    """Useful FLOPs of one node update: local SGD on the node, and the
    cloud's forward pass of the uploaded model over its test set."""
    f = forward_flops(config)
    return (3 * f * config["local_steps"] * config["batch_size"]
            + f * config["n_cloud_test"])


def record_flops(config: dict) -> int:
    """FLOPs of one record beyond its updates: the global model's forward
    pass over the test set."""
    return forward_flops(config) * config["n_test"]
