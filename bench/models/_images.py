"""MNIST-shaped images and the label-flip attack, shared by the image
models (`cnn.py`, `mlp.py`); not a model itself.

The data is learnable: each class is a smooth random prototype image,
and a sample is its class's prototype plus Gaussian noise, clipped to
[0, 1].  The label-flipping nodes relabel `flip_src` as `flip_dst` in
their own shards.  As MNIST is one fixed data set, the class prototypes,
the test set and the cloud's test set are the same for every seed.
`--seed` draws the training samples, which nodes flip labels and, after
them from the same generator, the model's initial weights.  The program
compiles the cloud's test set into its round program as a constant, so
a seed that changed it would recompile that program in every run."""
from __future__ import annotations

import numpy as np

# the configuration's keys this file reads
SETTINGS = ("hw", "channels", "n_classes", "data_noise", "malicious_frac",
            "flip_src", "flip_dst")


def _box_blur(img: np.ndarray, k: int) -> np.ndarray:
    """Mean over k x k windows of (..., H + k - 1, W + k - 1, C)."""
    c = img.cumsum(-3).cumsum(-2)
    c = np.pad(c, [(0, 0)] * (c.ndim - 3) + [(1, 0), (1, 0), (0, 0)])
    s = c[..., k:, k:, :] - c[..., :-k, k:, :] - c[..., k:, :-k, :] \
        + c[..., :-k, :-k, :]
    return s / (k * k)


def _samples(rng, protos, n: int, noise: float):
    y = rng.integers(0, protos.shape[0], size=n).astype(np.int32)
    x = rng.standard_normal((n,) + protos.shape[1:], dtype=np.float32)
    x *= np.float32(noise)
    x += protos[y]
    np.clip(x, 0.0, 1.0, out=x)
    return x, y


def image_data(config: dict, seed: int):
    """The seed's generator, drawn as far as the weights, and the images:
    node shards x (N, M, H, W, C) with labels y (N, M), the test and cloud
    sets, the malicious ids."""
    h, w = config["hw"]
    ch, n_cls = config["channels"], config["n_classes"]
    n, m = config["n_nodes"], config["samples_per_node"]
    noise = config["data_noise"]

    data = np.random.default_rng(0xDA7A5E7)         # the fixed data set
    protos = _box_blur(data.standard_normal((n_cls, h + 4, w + 4, ch)), 5)
    protos = ((protos - protos.min()) / np.ptp(protos)).astype(np.float32)
    test = _samples(data, protos, config["n_test"], noise)
    cloud = _samples(data, protos, config["n_cloud_test"], noise)

    rng = np.random.default_rng([int(seed), 0xBE7C4])
    x, y = _samples(rng, protos, n * m, noise)
    n_mal = int(round(config["malicious_frac"] * n))
    malicious = sorted(int(i) for i in rng.choice(n, n_mal, replace=False))
    yn = y.reshape(n, m)
    flip = np.zeros((n, 1), bool)
    flip[malicious] = True
    yn[flip & (yn == config["flip_src"])] = config["flip_dst"]
    return rng, {"x": x.reshape(n, m, h, w, ch), "y": yn, "test": test,
                 "cloud": cloud, "malicious": malicious}


def spec_fields(config: dict) -> dict:
    """The `FleetSpec` fields of an image model and of the label flip its
    shards carry."""
    from repro import api
    return {"model": config["model"], "hw": tuple(config["hw"]),
            "n_classes": config["n_classes"],
            "attack": api.AttackMix(malicious_frac=config["malicious_frac"],
                                    flip_src=config["flip_src"],
                                    flip_dst=config["flip_dst"])}


def population_fields(config: dict, inputs) -> dict:
    """The image models bring no arrays of their own: no `Population`
    fields beyond those every model sets."""
    return {}


def accuracy(logits, y) -> np.ndarray:
    """Share of rows whose first largest logit is the label, in float32
    as the program reports it."""
    hits = (np.asarray(logits).argmax(-1) == np.asarray(y)).sum(-1)
    return np.float32(hits) / np.float32(np.asarray(y).shape[-1])
