"""Finds a cell's files by the names in `BENCHMARK.json`.

A workload entry names a configuration and a traffic mix.  The
configuration's `file` holds its sizes; the traffic mix is
`bench/traffic/<traffic>.json`; the limits of the correctness check are
`bench/limits/<cell>.json`; a per-layer metric is read by
`bench/metrics/<metric>.py`; the model a configuration names under its
`"model"` key is `bench/models/<model>.py`.  Adding a cell, a
configuration, a model or a metric adds files and entries and edits
none."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: List[dict]      # the BENCHMARK.json metric entries it reports
    per_layer: List[dict]


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def cell(name: str, bench: Optional[dict] = None, root: str = ROOT) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", f"{w['traffic']}.json")) as f:
        traffic = json.load(f)
    return Cell(name, config, traffic, int(w["chips"]),
                [m for m in bench["end_to_end"] if _reports(m, name)],
                [m for m in bench["per_layer"] if _reports(m, name)])


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The `read(run)` function of `bench/metrics/<name>.py`."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    return _load(path, f"bench_metric_{name.replace('.', '_')}").read


_MODELS: Dict[str, object] = {}


def model_module(config: dict):
    """`bench/models/<config["model"]>.py`, loaded once per path: the
    reference's jitted functions take the module as a static argument, so
    one module object keeps one compiled program.

    What a model module gives the benchmark, each from the configuration
    (`SETTINGS` names the configuration's keys it reads):
      inputs     `make_inputs(config, seed)`: a dict of the initial
                 trainable tree `params`, the node shards `x` and targets
                 `y` (any shape and dtype, nodes first), the `test` and
                 `cloud` sets, the `malicious` ids, and optionally
                 `extra`, a dict of the module's own arrays (frozen
                 weights, say), which generic code passes on and never
                 looks inside; nothing of the program is imported for
                 them;
      program    `spec_fields(config)`, the `FleetSpec` fields that belong
                 to the model, `program_fns()`, the `loss_fn` and `acc_fn`
                 of `api.Population`, and `population_fields(config,
                 inputs)`, the further `api.Population` keywords the model
                 needs, its `extra` arrays among them (none may be one
                 that `drive.population` sets): the only part that
                 imports the program;
      reference  `forward(p, x, precision, extra)` and `loss(p, x, y,
                 precision, extra)` in plain `jax.numpy`, `extra` being
                 the inputs' `extra` on the device, in the dtype the
                 module stored it in, `accuracy(logits, y)` as the
                 program reports it, `NODE_BLOCK` and `TEST_BLOCK`, and
                 `ALTERED`, the key path of the leaf the `altered` fault
                 doubles;
      counts     `n_params`, `update_flops` and `record_flops`;
      control    `control_kwargs(config)`: the `reference.run` keywords
                 that compute below the configuration's stated precision."""
    path = os.path.join(BENCH, "models", f"{config['model']}.py")
    if path not in _MODELS:
        if not os.path.isfile(path):
            raise FileNotFoundError(
                f"model {config['model']!r}: no module {path}")
        _MODELS[path] = _load(path, f"bench_model_{config['model']}")
    return _MODELS[path]
