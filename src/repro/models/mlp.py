"""Tiny MLP edge model for fleet-scale runs.

The paper's CNN (`models/cnn.py`) is the faithful edge model; at thousand-node
fleet scale a vmapped CNN forward over every node dominates the round, so the
scale benchmarks and scenario sweeps use this 2-layer MLP on flattened
images instead — same (params, batch) contract as the CNN, orders of
magnitude cheaper per node.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .cnn import predicted_class


def init_mlp(key, in_dim: int, hidden: int = 32, n_classes: int = 10) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "fc1": {"w": jax.random.normal(k1, (in_dim, hidden)) / np.sqrt(in_dim),
                "b": jnp.zeros((hidden,))},
        "fc2": {"w": jax.random.normal(k2, (hidden, n_classes)) / np.sqrt(hidden),
                "b": jnp.zeros((n_classes,))},
    }


def mlp_forward(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """x (B, ...) — trailing dims are flattened — -> logits (B, n_classes)."""
    h = x.reshape(x.shape[0], -1)
    h = jnp.tanh(h @ params["fc1"]["w"] + params["fc1"]["b"])
    return h @ params["fc2"]["w"] + params["fc2"]["b"]


def mlp_loss(params: dict, batch: dict) -> Tuple[jnp.ndarray, dict]:
    logits = mlp_forward(params, batch["x"])
    logp = jax.nn.log_softmax(logits)
    y = batch["y"].astype(jnp.int32)
    loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))
    acc = jnp.mean((predicted_class(logits) == y).astype(jnp.float32))
    return loss, {"accuracy": acc}


def mlp_accuracy(params: dict, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    logits = mlp_forward(params, x)
    return jnp.mean((predicted_class(logits) == y.astype(jnp.int32))
                    .astype(jnp.float32))
