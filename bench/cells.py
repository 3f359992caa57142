"""Finds a cell's files by the names in `BENCHMARK.json`.

A workload entry names a configuration and a traffic mix.  The
configuration's `file` holds its sizes; the traffic mix is
`bench/traffic/<traffic>.json`; the limits of the correctness check are
`bench/limits/<cell>.json`; a per-layer metric is read by
`bench/metrics/<metric>.py`.  Adding a cell, a configuration or a metric
adds files and entries and edits none."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import List, Optional

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


def metric_reader(name: str):
    """The `read(run)` function of `bench/metrics/<name>.py`."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
