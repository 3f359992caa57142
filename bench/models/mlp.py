"""The fleet's own two-layer MLP (`repro.models.mlp`, `FleetSpec.model
= "mlp"`): logits = tanh(x W1 + b1) W2 + b2 over flattened images.

Its inputs are the shared images (`bench/models/_images.py`), each
flattened to one row of H W C floats, and its initial weights are drawn
from the same generator after them.  Counts as the CNN module counts them: a
multiply-add is two FLOPs, a training step three forward passes."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import _images

SETTINGS = _images.SETTINGS + ("hidden",)
NODE_BLOCK = 100
TEST_BLOCK = 2000
ALTERED = ("fc1", "b")


def _in_dim(config: dict) -> int:
    return config["hw"][0] * config["hw"][1] * config["channels"]


# ---------------------------------------------------------------------------
# inputs: nothing here imports the program
# ---------------------------------------------------------------------------

def make_inputs(config: dict, seed: int) -> dict:
    rng, data = _images.image_data(config, seed)
    d, hid, n_cls = _in_dim(config), config["hidden"], config["n_classes"]

    def normal(shape, fan_in):
        return (rng.standard_normal(shape) / np.sqrt(fan_in)).astype(np.float32)

    params = {"fc1": {"w": normal((d, hid), d),
                      "b": np.zeros((hid,), np.float32)},
              "fc2": {"w": normal((hid, n_cls), hid),
                      "b": np.zeros((n_cls,), np.float32)}}
    x = data["x"]
    return dict(data, params=params, x=x.reshape(x.shape[:2] + (d,)),
                test=(data["test"][0].reshape(-1, d), data["test"][1]),
                cloud=(data["cloud"][0].reshape(-1, d), data["cloud"][1]))


# ---------------------------------------------------------------------------
# program hooks
# ---------------------------------------------------------------------------

spec_fields = _images.spec_fields
population_fields = _images.population_fields


def program_fns():
    from repro.models.mlp import mlp_accuracy, mlp_loss
    return mlp_loss, mlp_accuracy


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------

def forward(p, x, precision, extra):
    h = jnp.tanh(jnp.dot(x.reshape(x.shape[0], -1), p["fc1"]["w"],
                         precision=precision) + p["fc1"]["b"])
    return jnp.dot(h, p["fc2"]["w"], precision=precision) + p["fc2"]["b"]


def loss(p, x, y, precision, extra):
    logp = jax.nn.log_softmax(forward(p, x, precision, extra))
    return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()


accuracy = _images.accuracy


def control_kwargs(config: dict) -> dict:
    return {"dtype": jnp.bfloat16}


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------

def forward_flops(config: dict) -> int:
    return 2 * config["hidden"] * (_in_dim(config) + config["n_classes"])


def n_params(config: dict) -> int:
    hid = config["hidden"]
    return (_in_dim(config) + 1) * hid + (hid + 1) * config["n_classes"]


def update_flops(config: dict) -> int:
    f = forward_flops(config)
    return (3 * f * config["local_steps"] * config["batch_size"]
            + f * config["n_cloud_test"])


def record_flops(config: dict) -> int:
    return forward_flops(config) * config["n_test"]
