"""The benchmark's inputs: the model's data and initial weights, and the
nodes' system profile.

Nothing here imports the program, so the reference (`bench/reference.py`)
and the program start from the same arrays without the reference taking
anything the program made.  The configuration's model module
(`bench/models/<model>.py`, `make_inputs`) draws the initial trainable
tree, the node shards, the test and cloud sets and the malicious ids from
the seed; it may add arrays of its own (frozen weights, say) under
`extra`.  Both sides of the check get them, and generic code never looks
inside them: the reference passes them, placed on the device once per
run, as the last argument of the module's `loss` and `forward`, and the
program gets them through the module's `population_fields`.  The
trainable tree `params` alone is trained, uploaded and compared.

The nodes' compute times are the same for every seed (the fleet's
hardware).  Under the synchronous schedule they and the uplink rates set
only the simulated clock and the link accounting that each record
reports: every node trains every round, so they change no device work
and nothing the benchmark measures or compares."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import numpy as np

from .cells import model_module


@dataclasses.dataclass
class Inputs:
    params: dict                        # the initial trainable tree, f32
    x: np.ndarray                       # (N, M, ...) node shards
    y: np.ndarray                       # (N, M, ...) their targets
    test: Tuple[np.ndarray, np.ndarray]
    cloud: Tuple[np.ndarray, np.ndarray]
    malicious: List[int]
    compute_s: np.ndarray               # (N,) seconds per local round
    bandwidth_bps: np.ndarray           # (N,)
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)


def make_inputs(config: dict, traffic: dict, seed: int) -> Inputs:
    n = config["n_nodes"]
    prof = traffic["profile"]
    fleet = np.random.default_rng([n, 0xF1EE7])     # the fixed hardware
    compute_s = prof["base_compute_s"] * np.exp(
        fleet.normal(0.0, prof["heterogeneity"], n))
    return Inputs(
        **model_module(config).make_inputs(config, seed), compute_s=compute_s,
        bandwidth_bps=np.full(n, float(prof["bandwidth_bps"])))
