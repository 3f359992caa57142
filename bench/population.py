"""The benchmark's inputs: data, initial weights and the nodes' system
profile.

Nothing here imports the program, so the reference (`bench/reference.py`)
and the program start from the same arrays without the reference taking
anything the program made.  The data is MNIST-shaped and learnable: each
class is a smooth random prototype image, and a sample is its class's
prototype plus Gaussian noise, clipped to [0, 1].  The label-flipping
nodes relabel `flip_src` as `flip_dst` in their own shards.

As MNIST is one fixed data set, the class prototypes, the test set and the
cloud's test set are the same for every seed, and so are the nodes'
compute times (the fleet's hardware).  `--seed` draws the training
samples, which nodes flip labels, the initial weights and, in the
program, the key chain.  The program compiles the cloud's test set into
its round program as a constant, so a seed that changed it would
recompile that program in every run.

Under the synchronous schedule the nodes' compute times and uplink rates
set only the simulated clock and the link accounting that each record
reports: every node trains every round, so they change no device work
and nothing the benchmark measures or compares."""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np


@dataclasses.dataclass
class Inputs:
    params: dict                        # the CNN's initial weights, f32
    x: np.ndarray                       # (N, M, H, W, C) node shards
    y: np.ndarray                       # (N, M) int32
    test: Tuple[np.ndarray, np.ndarray]
    cloud: Tuple[np.ndarray, np.ndarray]
    malicious: List[int]
    compute_s: np.ndarray               # (N,) seconds per local round
    bandwidth_bps: np.ndarray           # (N,)


def _box_blur(img: np.ndarray, k: int) -> np.ndarray:
    """Mean over k x k windows of (..., H + k - 1, W + k - 1, C)."""
    c = img.cumsum(-3).cumsum(-2)
    c = np.pad(c, [(0, 0)] * (c.ndim - 3) + [(1, 0), (1, 0), (0, 0)])
    s = c[..., k:, k:, :] - c[..., :-k, k:, :] - c[..., k:, :-k, :] \
        + c[..., :-k, :-k, :]
    return s / (k * k)


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


def _samples(rng, protos, n: int, noise: float):
    y = rng.integers(0, protos.shape[0], size=n).astype(np.int32)
    x = rng.standard_normal((n,) + protos.shape[1:], dtype=np.float32)
    x *= np.float32(noise)
    x += protos[y]
    np.clip(x, 0.0, 1.0, out=x)
    return x, y


def make_inputs(config: dict, traffic: dict, seed: int) -> Inputs:
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

    prof = traffic["profile"]
    fleet = np.random.default_rng([n, 0xF1EE7])     # the fixed hardware
    compute_s = prof["base_compute_s"] * np.exp(
        fleet.normal(0.0, prof["heterogeneity"], n))
    return Inputs(
        params=init_params(config, rng), x=x.reshape(n, m, h, w, ch), y=yn,
        test=test, cloud=cloud, malicious=malicious, compute_s=compute_s,
        bandwidth_bps=np.full(n, float(prof["bandwidth_bps"])))
