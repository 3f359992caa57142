"""The paper's edge model: CNN with 2 convolutional layers + 1 FC layer.

Used for the MNIST/CIFAR-style federated experiments (paper §6.1: "a simple
deep learning model (i.e., CNN with 2 convolutional layers followed by 1
fully connected layer)"). Pure JAX; params are a pytree so every core
mechanism (ALDP, detection, async mixing) applies unchanged.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def init_cnn(key, in_hw: Tuple[int, int] = (28, 28), in_ch: int = 1,
             n_classes: int = 10, c1: int = 16, c2: int = 32) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    h, w = in_hw
    # two stride-2 3x3 convs (SAME) halve each spatial dim twice
    fh, fw = -(-h // 4), -(-w // 4)
    return {
        "conv1": {"w": jax.random.normal(k1, (3, 3, in_ch, c1)) * (1.0 / np.sqrt(9 * in_ch)),
                  "b": jnp.zeros((c1,))},
        "conv2": {"w": jax.random.normal(k2, (3, 3, c1, c2)) * (1.0 / np.sqrt(9 * c1)),
                  "b": jnp.zeros((c2,))},
        "fc": {"w": jax.random.normal(k3, (fh * fw * c2, n_classes)) * (1.0 / np.sqrt(fh * fw * c2)),
               "b": jnp.zeros((n_classes,))},
    }


def cnn_flat_layout(in_hw: Tuple[int, int] = (28, 28)
                    ) -> Tuple[int, Tuple[int, ...]]:
    """(P, start offset of each leaf) of the CNN's params flattened in leaf
    order: the (C, P) layout the fleet's cohort kernels see."""
    shapes = jax.eval_shape(lambda k: init_cnn(k, in_hw=in_hw),
                            jax.random.PRNGKey(0))
    sizes = [int(l.size) for l in jax.tree.leaves(shapes)]
    return sum(sizes), tuple(sum(sizes[:i]) for i in range(len(sizes)))


@jax.custom_vjp
def relu(x: jnp.ndarray) -> jnp.ndarray:
    """``jax.nn.relu`` whose backward pass keeps the bfloat16 cast of its
    output, not the pre-activation, as the derivative's mask.

    The forward value is untouched: ``max(x, 0)`` in ``x``'s dtype.  The
    mask ``bf16(max(x, 0)) > 0`` holds exactly where ``x > 0`` does:
    bfloat16 has float32's exponent range, so a positive normal float32
    never rounds to zero, and a subnormal one is flushed to zero in both
    the compare and the cast on the TPU and under XLA's CPU runtime.  On
    the TPU that cast is the tensor the next layer's default-precision
    convolution or matmul already reads, so the backward pass stores
    nothing beside it; ``jax.nn.relu``'s mask keeps a float32 copy of the
    pre-activation instead, written by the forward pass and read back only
    to pack the mask.  Gradients are ``jax.nn.relu``'s bit for bit (0 at
    ``x == 0``)."""
    return jnp.maximum(x, 0)


def _relu_fwd(x):
    y = jnp.maximum(x, 0)
    return y, y.astype(jnp.bfloat16)


def _relu_bwd(mask_source, g):
    return (jnp.where(mask_source > 0, g, jnp.zeros_like(g)),)


relu.defvjp(_relu_fwd, _relu_bwd)


def cnn_forward(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """x (B, H, W, C) -> logits (B, n_classes)."""
    def conv(p, h, stride):
        out = jax.lax.conv_general_dilated(
            h, p["w"].astype(h.dtype), window_strides=(stride, stride),
            padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return out + p["b"].astype(h.dtype)

    h = relu(conv(params["conv1"], x, 2))
    h = relu(conv(params["conv2"], h, 2))
    h = h.reshape(h.shape[0], -1)
    return h @ params["fc"]["w"].astype(h.dtype) + params["fc"]["b"].astype(h.dtype)


def predicted_class(logits: jnp.ndarray) -> jnp.ndarray:
    """`logits.argmax(-1)`, the first index of each row's maximum, written
    as max / compare / min.  On TPU v5e an argmax fused into an accuracy
    vmapped over a cohort whose size is not a multiple of 8 returns wrong
    classes, and those accuracies are Alg. 2's detection scores; this form
    is exact there and bit-equal to argmax wherever no logit is NaN."""
    n = logits.shape[-1]
    top = logits.max(-1, keepdims=True)
    return jnp.where(logits == top, jnp.arange(n), n).min(-1)


def cnn_loss(params: dict, batch: dict) -> Tuple[jnp.ndarray, dict]:
    logits = cnn_forward(params, batch["x"])
    labels = batch["y"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
    acc = (predicted_class(logits) == labels).mean()
    return nll, {"accuracy": acc}


def cnn_accuracy(params: dict, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    return (predicted_class(cnn_forward(params, x)) == y).mean()


def per_class_accuracy(params: dict, x: jnp.ndarray, y: jnp.ndarray,
                       cls: int) -> jnp.ndarray:
    """Accuracy restricted to one class (the paper's 'special task')."""
    pred = predicted_class(cnn_forward(params, x))
    sel = (y == cls)
    return jnp.where(sel, pred == y, 0).sum() / jnp.maximum(sel.sum(), 1)
