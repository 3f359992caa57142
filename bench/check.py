"""The comparison that decides `correct`.

The program's first records (`drive.check_records`, driven through the
window's own `step()`) are held against the reference's rounds from the
same inputs.  Six numbers, each against the limit of its own that
`bench/limits/<cell>.json` holds (a number the file leaves out is
computed and printed, but not compared: see PERF.md for why):

  acc      largest gap of a record's test accuracy;
  rejected largest gap of a record's Alg. 2 rejections, as a share of
           the fleet;
  bytes    largest relative gap of a record's upload bytes (the DGC
           nonzero counts the wire codec prices);
  update   the first record's change of the global model (the update as
           the fold hands it on), and
  change   the change after the last record compared:
           for each, per leaf, the gap between the program's L2 norm and
           the reference's, over the larger of the reference's norm of
           that leaf and of the median leaf; the worst leaf counts;
  update_largest
           the first record's update gap on the largest leaf alone: the
           steady one, where the small leaves' norms swing with the few
           Alg. 2 verdicts that rounding flips and set the worst leaf.

Leaves whose first update in the reference is under a thousandth of the
median leaf's are left out of the norm gaps: they move by rounding
alone."""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Tuple

import numpy as np

NUMBERS = ("acc", "rejected", "bytes", "update", "change",
           "update_largest")
LIMITS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "limits")


@dataclasses.dataclass
class Readings:
    """A run's first records: params after each (host copies), and each
    record's test accuracy, rejections and upload bytes."""
    params: List[dict]
    accuracy: List[float]
    rejected: List[int]
    comm_bytes: List[float]


def leaf_paths(tree) -> List[Tuple[str, ...]]:
    """The key paths of a nested dict's leaves in sorted-key order: the
    order of the leaves in a node's flat upload."""
    if not isinstance(tree, dict):
        return [()]
    return [(k,) + p for k in sorted(tree) for p in leaf_paths(tree[k])]


def leaf(tree, path: Tuple[str, ...]):
    for k in path:
        tree = tree[k]
    return tree


def _leaves(tree) -> Dict[str, np.ndarray]:
    return {".".join(p): np.asarray(leaf(tree, p), np.float64)
            for p in leaf_paths(tree)}


def _norms(after, before) -> Dict[str, float]:
    b = _leaves(before)
    return {k: float(np.linalg.norm(v - b[k])) for k, v in
            _leaves(after).items()}


def norm_gap(prog: Dict[str, float], ref: Dict[str, float],
             kept: List[str]) -> float:
    med = float(np.median([ref[k] for k in kept]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in kept)


def leaf_gaps(prog: Readings, ref: Readings, initial: dict) -> dict:
    """Each leaf's gap, first update and last change: what `update` and
    `change` take the worst of."""
    first = _norms(ref.params[0], initial)
    last = _norms(ref.params[-1], initial)
    pf, pl = _norms(prog.params[0], initial), _norms(prog.params[-1], initial)
    mf, ml = np.median(list(first.values())), np.median(list(last.values()))
    return {k: [abs(pf[k] - first[k]) / max(first[k], mf),
                abs(pl[k] - last[k]) / max(last[k], ml)] for k in first}


def compare(initial: dict, prog: Readings, ref: Readings,
            n_nodes: int) -> Dict[str, float]:
    """The numbers of the module's docstring."""
    first = _norms(ref.params[0], initial)
    med = float(np.median(list(first.values())))
    kept = [k for k, v in first.items() if v >= 1e-3 * med]
    sizes = {k: v.size for k, v in _leaves(initial).items()}
    largest = max(sizes, key=sizes.get)
    return {
        "acc": max(abs(a - b) for a, b in zip(prog.accuracy, ref.accuracy)),
        "rejected": max(abs(a - b) for a, b in
                        zip(prog.rejected, ref.rejected)) / n_nodes,
        "bytes": max(abs(a - b) / b for a, b in
                     zip(prog.comm_bytes, ref.comm_bytes)),
        "update": norm_gap(_norms(prog.params[0], initial), first, kept),
        "change": norm_gap(_norms(prog.params[-1], initial),
                           _norms(ref.params[-1], initial), kept),
        "update_largest": norm_gap(_norms(prog.params[0], initial), first,
                                   [largest]),
    }


def limits(cell: str) -> Dict[str, float]:
    """The compared numbers of a cell and their limits."""
    with open(os.path.join(LIMITS_DIR, f"{cell}.json")) as f:
        lim = json.load(f)
    unknown = set(lim) - set(NUMBERS)
    if unknown or not lim:
        raise ValueError(f"limits of {cell}: {sorted(lim)} must be a "
                         f"non-empty subset of {NUMBERS}")
    return {k: float(v) for k, v in lim.items()}


def verdict(numbers: Dict[str, float], lim: Dict[str, float]) -> bool:
    return all(np.isfinite(numbers[k]) and numbers[k] <= v
               for k, v in lim.items())


def beside_limits(numbers: Dict[str, float],
                  lim: Dict[str, float]) -> Dict[str, dict]:
    """Each compared number beside its limit, as a run reports them."""
    return {k: {"value": numbers[k], "limit": v} for k, v in lim.items()}
