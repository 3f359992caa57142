"""The reduction from a profiler trace to numbers.

`load_xplane` reads the `.xplane.pb` that `jax.profiler` writes into a
`Trace`: per device, the intervals in which an operation ran, and the
host's spans (the benchmark's own `TraceAnnotation`s and the runtime's).
Everything after that is plain arithmetic on intervals, kept here so that
every run computes a number the same way and a test can check it on a
stored trace (`Trace.from_json`)."""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # [start_ns, end_ns)

# the benchmark's own host spans (bench/drive.py)
WINDOW_SPAN = "bench.window"
DEVICE_PLANE_PREFIX = "/device:TPU:"
# the line of a TPU device plane that holds one event per HLO operation
OPS_LINE = "XLA Ops"
# an op's name is its HLO text; the breakdown keeps its head
NAME_LEN = 200


@dataclasses.dataclass
class Op:
    name: str
    start: float        # ns on the trace's clock
    end: float


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]            # plane name -> its op events
    host: Dict[str, List[Op]]               # host line name -> its spans

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        dec = lambda rows: [Op(str(n), float(s), float(e)) for n, s, e in rows]
        return cls({k: dec(v) for k, v in d["devices"].items()},
                   {k: dec(v) for k, v in d["host"].items()})

    def window(self) -> Interval:
        """The benchmark's traced window: its `bench.window` host span."""
        spans = [o for ops in self.host.values() for o in ops
                 if o.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
        return spans[0].start, spans[0].end


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load_xplane(path: str) -> Trace:
    """Device op events and host spans of one profile."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        Op(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ops = [Op(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events if e.duration_ns > 0]
                if ops:
                    host[f"{plane.name}/{line.name}"] = ops
    return Trace(devices, host)


def describe_xplane(path: str, per_line: int = 8,
                    look_for: Sequence[str] = ("custom-call", "sort")
                    ) -> dict:
    """Plane and line names, event counts and the most frequent event
    names, and on the device's op line the events whose names hold one of
    `look_for` (with their stats): what to look at before trusting
    `load_xplane`'s choices and the metrics' name filters."""
    from collections import Counter
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            names = Counter(e.name[:200] for e in events)
            entry = {"events": len(events), "top": names.most_common(per_line)}
            if line.name == OPS_LINE:
                found = {}
                for e in events:
                    if any(w in e.name for w in look_for):
                        f = found.setdefault(e.name[:400], {
                            "count": 0, "seconds": 0.0,
                            "stats": {str(k): str(v)[:300]
                                      for k, v in e.stats}})
                        f["count"] += 1
                        f["seconds"] += e.duration_ns / 1e9
                entry["found"] = found
            lines[line.name] = entry
        out[plane.name] = lines
    return out


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered_ns(intervals: Sequence[Interval], window: Interval) -> float:
    return sum(e - s for s, e in clip(union(intervals), window))


def gaps(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of the window that no interval covers."""
    out, t = [], window[0]
    for s, e in clip(union(intervals), window):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < window[1]:
        out.append((t, window[1]))
    return out


# ---------------------------------------------------------------------------
# what the metrics read
# ---------------------------------------------------------------------------

def _spans(ops: Sequence[Op], keep: Callable[[str], bool] = lambda n: True
           ) -> List[Interval]:
    return [(o.start, o.end) for o in ops if keep(o.name)]


def busy_s(trace: Trace, window: Optional[Interval] = None) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    window = window or trace.window()
    if not trace.devices:
        return 0.0
    per = [covered_ns(_spans(ops), window) for ops in trace.devices.values()]
    return sum(per) / len(per) / 1e9


def window_s(trace: Trace) -> float:
    lo, hi = trace.window()
    return (hi - lo) / 1e9


def idle_share(trace: Trace) -> float:
    """1 - busy / window, as a share."""
    return 1.0 - busy_s(trace) / window_s(trace)


def matches(trace: Trace, pattern: str) -> List[Tuple["re.Match", float]]:
    """Every device op inside the window whose name matches `pattern`
    (a regular expression, searched), with its seconds, over all
    devices."""
    rx = re.compile(pattern)
    lo, hi = trace.window()
    out = []
    for ops in trace.devices.values():
        for o in ops:
            m = rx.search(o.name)
            if m and lo <= o.start < hi:
                out.append((m, (min(o.end, hi) - o.start) / 1e9))
    return out


def self_times(ops: Sequence[Op], window: Interval) -> Dict[str, float]:
    """Self time of each op name in ns: each moment of the window goes to
    the innermost op running then, the one that started last (a loop's
    body ops run inside the loop's own event)."""
    lo, hi = window
    evs = sorted((max(o.start, lo), min(o.end, hi), o.name) for o in ops
                 if o.end > lo and o.start < hi)
    points = sorted({p for s, e, _ in evs for p in (s, e)})
    total: Dict[str, float] = {}
    active: List[Tuple[float, float, str]] = []     # (-start, end, name)
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(evs) and evs[i][0] <= a:
            heapq.heappush(active, (-evs[i][0], evs[i][1], evs[i][2]))
            i += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)
        if active:
            name = active[0][2]
            total[name] = total.get(name, 0.0) + (b - a)
    return total


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The operations that took most device self time in the window, in
    seconds averaged over the devices."""
    window = trace.window()
    total: Dict[str, float] = {}
    for ops in trace.devices.values():
        for name, t in self_times(ops, window).items():
            total[name] = total.get(name, 0.0) + t
    k = max(len(trace.devices), 1)
    return [(name[:NAME_LEN], t / k / 1e9) for name, t in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def host_thread(trace: Trace) -> List[Op]:
    """The spans of the host thread that ran the benchmark's loop: the
    line that holds its `bench.window` span."""
    for ops in trace.host.values():
        if any(o.name == WINDOW_SPAN for o in ops):
            return ops
    raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")


def attribute_gaps(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The longest idle gaps of the first device, each named by what the
    benchmark's thread was doing at its middle: the innermost of its spans
    there (the benchmark's own and the runtime's), or "no host span"."""
    if not trace.devices:
        return []
    window = trace.window()
    ops = trace.devices[sorted(trace.devices)[0]]
    spans = [o for o in host_thread(trace) if o.name != WINDOW_SPAN]
    out = []
    for s, e in sorted(gaps(_spans(ops), window), key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        inner = [o for o in spans if o.start <= mid < o.end]
        name = (min(inner, key=lambda o: o.end - o.start).name if inner
                else "no host span")
        out.append((name, (e - s) / 1e9))
    return out
