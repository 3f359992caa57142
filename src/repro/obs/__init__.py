"""`repro.obs`: structured event tracing, metrics, and profiling hooks.

The observability layer under the whole fleet/net/kernel stack:

  * `events`  — `TraceEvent` (span/instant/counter, wall + virtual time,
    node/round/window tags), the nestable-`span()` `Tracer`, and the
    process-global injectable default (`get_tracer`/`set_tracer`/
    `use_tracer`) that is a no-op when disabled;
  * `metrics` — typed registry (counters, gauges, fixed-bucket
    histograms) with a deterministic `snapshot()`;
  * `sinks`   — crash-safe streaming JSONL (`JsonlWriter`/`JsonlSink` +
    `read_jsonl`), `MemorySink` for tests, and the Chrome-trace/Perfetto
    exporter (`chrome_trace`/`write_chrome_trace`);
  * `timers`  — host stage spans on the profiler's clock (`host_span`,
    `timed_stage`, fenced only under ``stage_timings``), ``py.gc`` spans
    (`GcSpans`) and the kernel profiling primitive (`bench_kernel`);
  * `analysis` — `FleetAnalytics`, the streaming trace-analytics sink
    folding arrival/window/upload/verdict events into derived fleet
    indicators (straggler scores, occupancy/skew, byte accounting,
    detection confusion);
  * `health`  — declarative `HealthSpec` SLO probes and the
    `HealthMonitor` that turns analytics state into `health.alert`
    instants and `health.incident` spans in the same trace stream;
  * `report`  — trace-only Markdown postmortems (`postmortem_md`) and
    run-vs-run diffs (`run_diff_md`), fronted by `tools/obs_report.py`.

Enabled per experiment through `api.ObsSpec`; with the spec at its
default (off) no event is constructed and the engines' jitted programs
are unchanged — tracing costs nothing until asked for.  The stage spans'
profiler annotations, and the engines' `jax.named_scope`s on the device
side, are there in every run: they land in any `jax.profiler` trace and
do nothing when no profile is being taken.  `repro.obs`
imports nothing from the rest of the repo (and jax only lazily, for
fencing), so every layer down to the kernels can depend on it.
"""
from .analysis import FleetAnalytics, NodeStats  # noqa: F401
from .events import (TraceEvent, Tracer, get_tracer,  # noqa: F401
                     set_tracer, use_tracer)
from .health import HealthMonitor, HealthSpec  # noqa: F401
from .metrics import (SECONDS_EDGES, STALENESS_EDGES,  # noqa: F401
                      WINDOW_SIZE_EDGES, Counter, Gauge, Histogram,
                      MetricsRegistry)
from .report import postmortem_md, run_diff_md  # noqa: F401
from .sinks import (OBS_SCHEMA_VERSION, JsonlSink, JsonlWriter,  # noqa: F401
                    MemorySink, Sink, chrome_trace, read_events,
                    read_jsonl, write_chrome_trace)
from .timers import (GcSpans, bench_kernel, fence,  # noqa: F401
                     host_span, timed_stage)
