"""Host stage spans on the profiler's clock, and the kernels profiling mode.

Every host stage of a record (the round or window, the device program's
dispatch, the read-backs, net draw/commit, evaluation, the accountant)
opens one `Stage`.  A stage is always a `jax.profiler.TraceAnnotation` of
its name, so it lands in any `jax.profiler` trace on the same clock as
the device's ops, with no `ObsSpec` at all; when no profile is being
taken the annotation does nothing.  Two modes add to it:

  * `host_span(tracer, name)` — also the `obs` span event ``name`` when
    the tracer is enabled (the ``round`` and ``window`` spans the
    analytics read);
  * `timed_stage(tracer, name)` — the ``stage.<name>`` span, which is an
    `obs` event and a fence only in the measurement mode
    (``tracer.stage_timings``): the caller fences the stage's outputs via
    ``st.fence(out)`` before the context exits, so the event's wall
    duration covers the device work.  JAX dispatch is asynchronous, and
    fencing serializes it, which is itself a perf change; that is why it
    is opt-in per run, never ambient.  Outside that mode `fence` returns
    its argument untouched.

`GcSpans` puts Python's garbage collections on the same clock, as
``py.gc`` spans.

`bench_kernel(name, fn, *args)` is the microbenchmark primitive
`benchmarks/kernels_micro.py` consumes: warmup + fenced timing loop,
µs/call, and a counter event + histogram sample into the tracer.

`fence` accepts any pytree (jax arrays, tuples, dicts) and tolerates
plain host values, so call sites don't special-case output shapes.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Optional

from .events import _NULL_SPAN, Tracer, get_tracer
from .metrics import SECONDS_EDGES


def fence(x: Any) -> Any:
    """Block until every jax array in ``x`` has materialized; host values
    pass through untouched."""
    import jax
    return jax.block_until_ready(x)


def _annotation(name: str, **args):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name, **args)


class Stage:
    """One open host stage: the profiler annotation, the `obs` span (or
    the null one) and whether `fence` blocks."""
    __slots__ = ("_annotation", "_span", "_fenced")

    def __init__(self, name: str, span, fenced: bool, tags):
        self._annotation = _annotation(name, **tags)
        self._span = span
        self._fenced = fenced

    def fence(self, x: Any) -> Any:
        return fence(x) if self._fenced else x

    def set(self, **tags) -> None:
        self._span.set(**tags)

    def set_virtual(self, virt_t=None, virt_end=None) -> None:
        self._span.set_virtual(virt_t, virt_end)

    def __enter__(self):
        self._annotation.__enter__()
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        self._annotation.__exit__(*exc)
        return False


def host_span(tracer: Optional[Tracer], name: str,
              virt_t: Optional[float] = None, **tags) -> Stage:
    """The span ``name`` of one record or window: a profiler annotation,
    and an `obs` span event when the tracer is enabled.  Never fences."""
    tracer = tracer if tracer is not None else get_tracer()
    return Stage(name, tracer.span(name, virt_t=virt_t, **tags), False,
                 tags)


def timed_stage(tracer: Optional[Tracer], name: str,
                virt_t: Optional[float] = None, **tags) -> Stage:
    """The span ``stage.<name>`` of one host pipeline stage: a profiler
    annotation, and under ``stage_timings`` a fenced `obs` span event.

        with timed_stage(self.obs, "window.device", window=w) as st:
            out = self._window_fn(...)
            st.fence(out)           # blocks only under stage_timings
    """
    tracer = tracer if tracer is not None else get_tracer()
    timed = tracer.enabled and tracer.stage_timings
    span = (tracer.span(f"stage.{name}", virt_t=virt_t, **tags) if timed
            else _NULL_SPAN)
    return Stage(f"stage.{name}", span, timed, tags)


class GcSpans:
    """While open, each Python garbage collection is a ``py.gc`` span on
    the profiler's clock, with its generation as an argument."""

    def __init__(self):
        self._open = None       # the annotation of a running collection

    def _callback(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._open = _annotation("py.gc", generation=info["generation"])
            self._open.__enter__()
        elif self._open is not None:
            ann, self._open = self._open, None
            ann.__exit__(None, None, None)

    def __enter__(self):
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._callback)
        return False


# ---------------------------------------------------------------------------
# kernels profiling mode
# ---------------------------------------------------------------------------

def bench_kernel(name: str, fn, *args, iters: int = 3, warmup: int = 1,
                 tracer: Optional[Tracer] = None) -> float:
    """Fenced kernel microbenchmark: µs per call over ``iters`` timed
    iterations after ``warmup`` untimed ones (compilation + first-touch).

    When the (global or injected) tracer is enabled, each measurement
    lands in the stream as a ``kernel.<name>`` counter event (value =
    µs/call, tags carry iters) and a shared ``kernel.us_per_call``
    histogram sample — the kernels profiling mode
    `benchmarks/kernels_micro.py --profile` turns on.
    """
    tracer = tracer if tracer is not None else get_tracer()
    for _ in range(max(1, warmup)):
        fence(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    fence(out)
    us = (time.perf_counter() - t0) / iters * 1e6
    if tracer.enabled:
        tracer.counter(f"kernel.{name}", us, iters=iters)
        tracer.metrics.histogram("kernel.us_per_call",
                                 [e * 1e6 for e in SECONDS_EDGES]).observe(us)
    return us
