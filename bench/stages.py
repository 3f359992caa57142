"""The fleet's stages in a trace: device time by the program's named
scopes, the program's own host spans, and the device idle time they
leave exposed.

The program wraps each stage of its round in a `jax.named_scope`
(``fleet.local_sgd``, ``fleet.upload``, ``fleet.cloud_score``,
``fleet.fold``, ``fleet.evaluate``), which lands in the `op_name`
metadata of every HLO instruction the stage lowers to.  A device op's
event in the trace is named by its instruction (``%fusion.272 = f32[...]
fusion(...)``).  The profile's event metadata holds the path too (its
``tf_op`` stat), but `load_xplane` keeps names only, so `live_scopes`
reads the instruction -> scope map from the HLO of the executables the
process holds, and `scope_ns` puts each op's device self time to the
first ``fleet.*`` scope of its path.

The program (`repro.obs`) also opens a profiler annotation for every host
stage of a record: ``round`` (one per record), ``stage.*`` (the device
program's dispatch, the read-backs, net draw and commit, the test pass,
the record's accounting, the privacy accountant) and ``py.gc`` (a Python
garbage collection).  They sit on the benchmark's own thread, inside its
``bench.step`` spans.  A trace of a program that has none of them (an
older commit) reduces to nothing here, and the metrics that read it
return None."""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

from . import trace as tr

SCOPE_PREFIX = "fleet."
# device time under no fleet scope
UNSCOPED = "unscoped"
# an instruction key that two live executables put to different scopes
AMBIGUOUS = "ambiguous"

ROUND_SPAN = "round"
GC_SPAN = "py.gc"
STAGE_PREFIX = "stage."
# the exposed idle that lies under none of the program's spans
UNATTRIBUTED = "unattributed"


def is_program_span(name: str) -> bool:
    return (name == ROUND_SPAN or name == GC_SPAN
            or name.startswith(STAGE_PREFIX))


def program_spans(trace: tr.Trace) -> List[tr.Op]:
    """The program's spans on the benchmark's thread."""
    return [o for o in tr.host_thread(trace) if is_program_span(o.name)]


def round_count(trace: tr.Trace) -> int:
    """The ``round`` spans that start inside the traced window."""
    lo, hi = trace.window()
    return sum(1 for o in program_spans(trace)
               if o.name == ROUND_SPAN and lo <= o.start < hi)


def exposed_ns(trace: tr.Trace) -> Dict[str, float]:
    """Idle ns of the first device in the window, by the innermost program
    span that holds each idle gap's middle (`UNATTRIBUTED` where none
    does)."""
    if not trace.devices:
        return {}
    window = trace.window()
    ops = trace.devices[sorted(trace.devices)[0]]
    spans = program_spans(trace)
    out: Dict[str, float] = {}
    for s, e in tr.gaps([(o.start, o.end) for o in ops], window):
        mid = (s + e) / 2
        inner = [o for o in spans if o.start <= mid < o.end]
        name = (min(inner, key=lambda o: o.end - o.start).name if inner
                else UNATTRIBUTED)
        out[name] = out.get(name, 0.0) + (e - s)
    return out


# ---------------------------------------------------------------------------
# device time by scope
# ---------------------------------------------------------------------------

Key = Tuple[str, str]       # (instruction name, its result shape)

_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"(?:ENTRY )?%([\w.\-]+) ")
_CALLS = re.compile(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)")


def instruction_key(text: str) -> Optional[Key]:
    """(name, result shape) of an HLO instruction's text, as an HLO module
    prints it and as a device op's event is named; None for other text."""
    m = _INSTR.match(text)
    if m is None:
        return None
    i, depth = m.end(), 0
    for j in range(i, len(text)):   # a tuple shape and a tiling nest parens
        c = text[j]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0 and text[i] == "(":
                return m.group(1), text[i:j + 1]
        elif c == " " and depth == 0:
            return m.group(1), text[i:j]
    return m.group(1), text[i:]


def scope_of(op_name: str) -> str:
    """The first ``fleet.*`` component of an `op_name` path."""
    for part in op_name.split("/"):
        if part.startswith(SCOPE_PREFIX):
            return part
    return UNSCOPED


def hlo_scopes(hlo_text: str) -> Dict[Key, str]:
    """Instruction key -> scope, over an HLO module's text.  An
    instruction whose own `op_name` names no fleet scope (a copy or
    fusion the compiler made) takes the scope of the instruction that
    calls its computation (a while loop's body takes the loop's), and so
    on outwards, where one of them has one."""
    own: Dict[str, str] = {}        # instruction -> its own scope
    comp_of: Dict[str, str] = {}    # instruction -> its computation
    caller: Dict[str, str] = {}     # computation -> calling instruction
    keys: List[Key] = []
    comp = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else None
            continue
        key = instruction_key(line)
        if key is None or comp is None:
            continue
        m = _OP_NAME.search(line)
        own[key[0]] = scope_of(m.group(1)) if m else UNSCOPED
        comp_of[key[0]] = comp
        keys.append(key)
        for callee in _CALLS.findall(line):
            caller.setdefault(callee, key[0])

    def scope(name: str, seen: frozenset = frozenset()) -> str:
        up = caller.get(comp_of[name])
        if own[name] != UNSCOPED or up is None or up in seen:
            return own[name]
        return scope(up, seen | {name})

    return {k: scope(k[0]) for k in keys}


def merged(maps: Iterable[Dict[Key, str]]) -> Dict[Key, str]:
    """One map over several executables': a key that two of them put to
    different scopes maps to `AMBIGUOUS`."""
    out: Dict[Key, str] = {}
    for m in maps:
        for key, scope in m.items():
            out[key] = scope if out.get(key, scope) == scope else AMBIGUOUS
    return out


def live_scopes() -> Dict[Key, str]:
    """Instruction key -> scope over every executable the process holds
    (after the window, the round program and the test pass among them)."""
    import jax
    return merged(hlo_scopes(module.to_string())
                  for exe in jax.devices()[0].client.live_executables()
                  for module in exe.hlo_modules())


def op_scope(event_name: str, scopes: Dict[Key, str]) -> str:
    """The scope of a device op's event, the map's for its (name, result
    shape).  An op whose key two live executables put to different
    scopes raises: the event alone cannot say which of them ran it."""
    scope = scopes.get(instruction_key(event_name), UNSCOPED)
    if scope == AMBIGUOUS:
        raise ValueError(f"device op {event_name[:160]!r} is an instruction "
                         "of two live executables, under different scopes")
    return scope


def scope_ns(trace: tr.Trace, scopes: Dict[Key, str]) -> Dict[str, float]:
    """Device self time (`trace.self_times`) in the window by scope, in ns
    averaged over the devices; ops the map does not name go to
    `UNSCOPED`."""
    window = trace.window()
    out: Dict[str, float] = {}
    for ops in trace.devices.values():
        for name, t in tr.self_times(ops, window).items():
            scope = op_scope(name, scopes)
            out[scope] = out.get(scope, 0.0) + t
    k = max(len(trace.devices), 1)
    return {s: t / k for s, t in out.items()}


def _scopes(run) -> Dict[Key, str]:
    scopes = getattr(run, "scopes", None)
    return live_scopes() if scopes is None else scopes


def scope_ms_per_record(run, scope: str) -> Optional[float]:
    """Device ms of one scope over the window's ``round`` spans, or None
    where the trace has no round span or the program no such scope.  A
    run view may carry its own instruction -> scope map (`run.scopes`);
    else the process's live executables give it."""
    rounds = round_count(run.trace)
    if not rounds:
        return None
    ns = scope_ns(run.trace, _scopes(run)).get(scope)
    return None if ns is None else ns / 1e6 / rounds


def op_seconds(run, op: str, scope: str) -> float:
    """Device seconds in the window of the ops named ``%<op>`` or
    ``%<op>.N`` under `scope`, each op's duration as `trace.matches`
    takes it."""
    scopes = _scopes(run)
    return sum(s for m, s in tr.matches(run.trace,
                                        rf"^%{re.escape(op)}(?:\.\d+)? = ")
               if op_scope(m.string, scopes) == scope)


def breakdown(run) -> dict:
    """Where a record's device time and exposed idle went: device ms per
    record by scope (and `UNSCOPED`), the share of busy time under some
    scope, exposed idle ms per record by program span (and
    `UNATTRIBUTED`), and the ``round`` spans beside the records the
    benchmark counted."""
    rounds = round_count(run.trace)
    per = max(rounds, 1)
    by_scope = scope_ns(run.trace, _scopes(run))
    busy = sum(by_scope.values())
    return {
        "device_ms_per_record": {s: t / 1e6 / per
                                 for s, t in sorted(by_scope.items())},
        "scoped_share_of_busy": (1 - by_scope.get(UNSCOPED, 0.0) / busy
                                 if busy else None),
        "exposed_ms_per_record": {s: t / 1e6 / per for s, t in
                                  sorted(exposed_ns(run.trace).items())},
        "round_spans": rounds, "records": run.records}
