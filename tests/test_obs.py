"""repro.obs: event tracing, metrics, sinks, and the instrumented stack.

Tiers:
  * unit        — TraceEvent round trip, disabled-tracer no-ops, span
    nesting/seq order, metrics registry semantics, JSONL torn-tail
    handling, Chrome-trace structure, `timed_stage` gating;
  * api         — `ObsSpec` validation in `compile_plan`, crash-safe
    `append_json_records`;
  * acceptance  — a traced async run over a lossy network produces a
    Perfetto-loadable Chrome trace plus a streaming records JSONL whose
    replay reconstructs the final `RunReport` exactly, and a detection
    audit log that reconstructs Fig. 6's rejection series;
  * net         — `NetTrace`/`NetSim.summary()` invariants;
  * mesh        — obs event ordering on a forced-8-device host
    (subprocess pattern from test_fleet_shard.py).
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import api
from repro import obs
from repro.net import LinkProfile, NetSim
from repro.obs import (MemorySink, MetricsRegistry, TraceEvent, Tracer,
                       chrome_trace, read_events, read_jsonl, timed_stage,
                       use_tracer)


# ---------------------------------------------------------------------------
# unit: events
# ---------------------------------------------------------------------------

def test_trace_event_round_trip():
    ev = TraceEvent(kind="span", name="window", wall_t=1.5, virt_t=10.0,
                    dur=0.25, virt_dur=3.0, tags={"window": 2}, seq=7)
    back = TraceEvent.from_dict(ev.to_dict())
    assert back == ev
    with pytest.raises(ValueError, match="kind"):
        TraceEvent.from_dict({"kind": "nope", "name": "x", "wall_t": 0.0})


def test_disabled_tracer_is_noop():
    sink = MemorySink()
    tr = Tracer([sink], enabled=False)
    tr.instant("a", node=1)
    tr.counter("b", 1.0)
    s1, s2 = tr.span("c"), tr.span("d")
    with s1:
        pass
    assert s1 is s2, "disabled span must be the shared null context"
    assert sink.events == []


def test_span_nesting_seq_order_and_tags():
    sink = MemorySink()
    tr = Tracer([sink])
    with tr.span("outer", window=0) as outer:
        tr.instant("inner.point", node=3)
        with tr.span("inner") as inner:
            inner.set(found=2)
        outer.set_virtual(virt_t=5.0, virt_end=9.0)
    names = [e.name for e in sink.events]
    # spans emit at *exit*: inner closes before outer
    assert names == ["inner.point", "inner", "outer"]
    seqs = [e.seq for e in sink.events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert sink.events[1].tags == {"found": 2}
    outer_ev = sink.events[2]
    assert outer_ev.virt_t == 5.0 and outer_ev.virt_dur == 4.0
    assert outer_ev.dur is not None and outer_ev.dur >= 0.0


# ---------------------------------------------------------------------------
# unit: metrics
# ---------------------------------------------------------------------------

def test_metrics_registry_semantics():
    mx = MetricsRegistry()
    mx.counter("up").inc(3)
    mx.counter("up").inc(2.5)
    mx.gauge("ver").set(7)
    h = mx.histogram("lat", [1.0, 2.0, 4.0])
    for v in (0.5, 1.5, 100.0):
        h.observe(v)
    snap = mx.snapshot()
    assert snap["up"] == {"type": "counter", "value": 5.5}
    assert snap["ver"]["value"] == 7.0
    assert snap["lat"]["counts"] == [1, 1, 0, 1]       # +inf overflow bucket
    assert snap["lat"]["count"] == 3
    assert snap["lat"]["min"] == 0.5 and snap["lat"]["max"] == 100.0
    assert list(snap) == sorted(snap)
    with pytest.raises(ValueError, match="edges"):
        mx.histogram("lat", [1.0, 999.0])              # edges are frozen
    with pytest.raises(TypeError, match="Counter"):
        mx.gauge("up")                                 # type-checked re-touch


# ---------------------------------------------------------------------------
# unit: JSONL sinks (satellite: crash-exposure)
# ---------------------------------------------------------------------------

def test_jsonl_torn_tail_rejected_cleanly(tmp_path):
    p = str(tmp_path / "ev.jsonl")
    sink = obs.JsonlSink(p, header={"stream": "t"})
    tr = Tracer([sink])
    for i in range(3):
        tr.instant("tick", i=i)
    tr.close()
    clean = read_jsonl(p)
    assert clean[0]["kind"] == "header" and clean[0]["obs_schema"] == 1
    assert len(clean) == 4
    # simulate a crash mid-append: torn final line
    with open(p, "a") as f:
        f.write('{"kind":"instant","name":"tor')
    with pytest.raises(ValueError, match="truncated final"):
        read_jsonl(p)
    dropped = read_jsonl(p, strict=False)
    assert dropped == clean, "strict=False must drop exactly the torn tail"
    assert len(read_events(p, strict=False)) == 3
    # a torn line *before* the end is corruption and always raises
    with open(p, "a") as f:
        f.write('\n{"kind":"instant","name":"fine","wall_t":0}\n')
    with pytest.raises(ValueError, match="corrupt"):
        read_jsonl(p, strict=False)


def test_chrome_trace_structure():
    sink = MemorySink()
    tr = Tracer([sink])
    with tr.span("window", window=0) as sp:
        tr.instant("arrival", virt_t=2.0, node=4)
        tr.counter("bytes", 128.0, virt_t=2.5)
        sp.set_virtual(virt_t=0.0, virt_end=3.0)
    doc = chrome_trace(sink.events)
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} <= {"M", "X", "i", "C"}
    tracks = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert tracks == {"cloud", "node 4"}
    arr = next(e for e in evs if e["ph"] == "i")
    assert arr["ts"] == pytest.approx(2.0 * 1e6)       # virtual clock wins
    span = next(e for e in evs if e["ph"] == "X")
    assert span["dur"] == pytest.approx(3.0 * 1e6)
    json.dumps(doc)                                    # serializable as-is


def test_chrome_trace_counter_tracks_perfetto_shape():
    """Counter events must export as Perfetto *counter tracks*: phase
    "C", value under args keyed by the counter name, and per-node
    counters on distinctly named tracks (Perfetto identifies counter
    tracks by (pid, name) — two nodes sharing one name would interleave
    into a single garbled series)."""
    sink = MemorySink()
    tr = Tracer([sink])
    tr.counter("bytes", 100.0, virt_t=1.0, node=0)
    tr.counter("bytes", 250.0, virt_t=2.0, node=1)
    tr.counter("ring.held", 3.0, virt_t=2.5)           # cloud-side counter
    doc = chrome_trace(sink.events)
    counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert len(counters) == 3
    for c in counters:
        assert set(c) >= {"ph", "name", "pid", "tid", "ts", "args"}
        assert len(c["args"]) == 1                     # one series per track
    by_name = {c["name"]: c for c in counters}
    # per-node counters: distinct track names, value keyed by counter name
    assert by_name["bytes (node 0)"]["args"] == {"bytes": 100.0}
    assert by_name["bytes (node 1)"]["args"] == {"bytes": 250.0}
    # cloud-track counters keep the bare name
    assert by_name["ring.held"]["args"] == {"ring.held": 3.0}
    assert by_name["ring.held"]["tid"] == 1            # the cloud track
    json.dumps(doc)


def test_histogram_quantile_hand_computed():
    mx = MetricsRegistry()
    h = mx.histogram("lat", [1.0, 2.0, 4.0])
    assert h.quantile(0.5) is None                     # empty histogram
    for v in (0.5, 1.5, 3.0, 100.0):
        h.observe(v)
    # counts [1, 1, 1, 1]: one per bucket incl. the +inf overflow; outer
    # bounds are the observed min/max (0.5 and 100.0)
    assert h.quantile(0.0) == pytest.approx(0.5)
    assert h.quantile(0.25) == pytest.approx(1.0)
    assert h.quantile(0.5) == pytest.approx(2.0)
    assert h.quantile(0.9) == pytest.approx(4.0 + (100.0 - 4.0) * 0.6)
    assert h.quantile(1.0) == pytest.approx(100.0)
    with pytest.raises(ValueError, match="quantile"):
        h.quantile(1.5)
    # single-value histogram: every quantile is that value
    h1 = MetricsRegistry().histogram("one", [10.0])
    h1.observe(3.0)
    assert h1.quantile(0.5) == pytest.approx(3.0)


def test_to_prom_text_hand_computed():
    mx = MetricsRegistry()
    mx.counter("net.uploads").inc(12)
    mx.gauge("ring.occupancy").set(0.75)
    h = mx.histogram("lat", [1.0, 2.0])
    for v in (0.5, 1.5, 9.0):
        h.observe(v)
    text = mx.to_prom_text()
    lines = text.splitlines()
    assert "# TYPE lat histogram" in lines
    assert 'lat_bucket{le="1"} 1' in lines              # cumulative
    assert 'lat_bucket{le="2"} 2' in lines
    assert 'lat_bucket{le="+Inf"} 3' in lines
    assert "lat_sum 11" in lines
    assert "lat_count 3" in lines
    # dots sanitized to the Prometheus charset
    assert "# TYPE net_uploads counter" in lines
    assert "net_uploads 12" in lines
    assert "ring_occupancy 0.75" in lines
    assert text.endswith("\n")
    assert MetricsRegistry().to_prom_text() == ""


# ---------------------------------------------------------------------------
# unit: read_jsonl edge cases (satellite: crash-exposure corners)
# ---------------------------------------------------------------------------

def test_read_jsonl_header_only_file(tmp_path):
    p = str(tmp_path / "empty.jsonl")
    w = obs.JsonlWriter(p, header={"stream": "events"})
    w.close()
    rows = read_jsonl(p)
    assert len(rows) == 1 and rows[0]["kind"] == "header"
    assert read_events(p) == []
    assert read_jsonl(p, strict=False) == rows


def test_read_jsonl_tail_valid_json_prefix_is_kept(tmp_path):
    """A crash between the JSON bytes and the trailing newline leaves a
    final line that is *complete valid JSON* — indistinguishable from a
    clean last line, so it is kept under both strictness modes (the
    documented limit of newline-framed crash detection)."""
    p = str(tmp_path / "ev.jsonl")
    sink = obs.JsonlSink(p, header={"stream": "t"})
    tr = Tracer([sink])
    tr.instant("tick", i=0)
    tr.instant("tick", i=1)
    tr.close()
    clean = read_jsonl(p)
    with open(p) as f:
        body = f.read()
    assert body.endswith("\n")
    with open(p, "w") as f:
        f.write(body[:-1])                  # crash ate only the newline
    assert read_jsonl(p) == clean
    assert read_jsonl(p, strict=False) == clean


def test_read_jsonl_strict_false_drops_exactly_one(tmp_path):
    p = str(tmp_path / "ev.jsonl")
    sink = obs.JsonlSink(p, header={"stream": "t"})
    tr = Tracer([sink])
    for i in range(5):
        tr.instant("tick", i=i)
    tr.close()
    clean = read_jsonl(p)
    with open(p, "a") as f:
        f.write('{"kind":"instant","name":"torn","wall_t":1.2,"ta')
    dropped = read_jsonl(p, strict=False)
    assert dropped == clean                 # exactly the torn tail is gone
    assert len(dropped) == 6                # header + 5 complete records


# ---------------------------------------------------------------------------
# unit: timers
# ---------------------------------------------------------------------------

def test_timed_stage_gating(monkeypatch):
    fenced = []
    monkeypatch.setattr(obs.timers, "fence",
                        lambda x: fenced.append(x) or x)
    # disabled, or enabled without stage timings: no event, no fence
    # (stage timing is a separate opt-in: fencing changes perf)
    quiet = MemorySink()
    for tracer in (Tracer(enabled=False),
                   Tracer([quiet], enabled=True, stage_timings=False)):
        with timed_stage(tracer, "x") as st:
            assert st.fence({"a": 1}) == {"a": 1}
    assert fenced == [] and quiet.events == []
    sink = MemorySink()
    on = Tracer([sink], enabled=True, stage_timings=True)
    with timed_stage(on, "round.device", round=3) as st:
        assert st.fence({"a": 1}) == {"a": 1}
    assert fenced == [{"a": 1}]
    (ev,) = sink.events
    assert ev.name == "stage.round.device" and ev.tags == {"round": 3}


# ---------------------------------------------------------------------------
# api: ObsSpec validation + crash-safe trajectory appends
# ---------------------------------------------------------------------------

def _spec(**kw):
    base = dict(
        fleet=api.FleetSpec(n_nodes=4, samples_per_node=20, n_test=32,
                            n_cloud_test=16,
                            attack=api.AttackMix(malicious_frac=0.25)),
        schedule=api.SchedulePolicy(kind="async"),
        defense=api.DefenseSpec(detect=True),
        train=api.TrainSpec(local_steps=2, batch_size=8, lr=0.1),
        rounds=2, seed=0)
    base.update(kw)
    return api.ExperimentSpec(**base)


@pytest.mark.parametrize("obs_kw, match", [
    (dict(events_jsonl="x.jsonl"), "enabled"),
    (dict(chrome_trace="t.json"), "enabled"),
    (dict(records_jsonl="r.jsonl"), "enabled"),
    (dict(stage_timings=True), "enabled"),
    (dict(enabled=True, events_jsonl=""), "empty"),
])
def test_compile_plan_rejects_bad_obs(obs_kw, match):
    with pytest.raises(api.SpecError, match=match):
        api.compile_plan(_spec(obs=api.ObsSpec(**obs_kw)))


def test_compile_plan_rejects_stage_timings_on_sequential():
    spec = _spec(obs=api.ObsSpec(enabled=True, stage_timings=True),
                 topology=api.Topology(kind="sequential"))
    with pytest.raises(api.SpecError, match="sequential"):
        api.compile_plan(spec)


def test_obs_stage_lowered_and_spec_round_trips():
    plan = api.compile_plan(_spec(obs=api.ObsSpec(enabled=True)))
    assert "obs_trace" in plan.stages
    plan_off = api.compile_plan(_spec())
    assert "obs_trace" not in plan_off.stages
    spec = _spec(obs=api.ObsSpec(enabled=True, events_jsonl="e.jsonl",
                                 stage_timings=True))
    back = api.ExperimentSpec.from_dict(spec.to_dict())
    assert back.obs == spec.obs


def test_append_json_records_crash_safe(tmp_path):
    p = str(tmp_path / "traj.json")
    api.append_json_records(p, [{"name": "a", "v": 1}])
    api.append_json_records(p, [{"name": "b", "v": 2}])
    traj = api.load_json_records(p)
    assert [t["name"] for t in traj] == ["a", "b"]
    assert all(t["schema_version"] == api.SCHEMA_VERSION for t in traj)
    # a stale half-written temp file from a crashed appender must not
    # poison the next append (write goes to tmp, then os.replace)
    with open(p + ".tmp", "w") as f:
        f.write('[{"torn": ')
    api.append_json_records(p, [{"name": "c"}])
    assert not os.path.exists(p + ".tmp")
    assert [t["name"] for t in api.load_json_records(p)] == ["a", "b", "c"]
    # non-list file: loud error, file untouched
    solo = str(tmp_path / "solo.json")
    with open(solo, "w") as f:
        json.dump({"not": "a list"}, f)
    with pytest.raises(ValueError, match="trajectory list"):
        api.append_json_records(solo, [{"name": "d"}])
    with pytest.raises(ValueError, match="trajectory list"):
        api.load_json_records(solo)


# ---------------------------------------------------------------------------
# acceptance: one traced async run over a lossy network
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    td = tmp_path_factory.mktemp("obs")
    paths = {"events": str(td / "events.jsonl"),
             "chrome": str(td / "trace.json"),
             "records": str(td / "records.jsonl")}
    spec = _spec(
        network=api.NetworkSpec(codec="sparse_coo", loss_prob=0.1,
                                jitter_s=0.5, bandwidth_sigma=1.0),
        compression=api.CompressionSpec(sparsify_ratio=0.5),
        obs=api.ObsSpec(enabled=True, events_jsonl=paths["events"],
                        chrome_trace=paths["chrome"],
                        records_jsonl=paths["records"],
                        stage_timings=True),
        rounds=3)
    rep = api.run(api.compile_plan(spec))
    return spec, rep, paths


def test_traced_run_event_stream(traced_run):
    _, rep, paths = traced_run
    rows = read_jsonl(paths["events"])
    assert rows[0]["kind"] == "header"
    names = {r["name"] for r in rows if r.get("kind") in
             ("span", "instant", "counter")}
    assert {"window", "arrival", "detect.verdict", "net.upload"} <= names
    assert any(n.startswith("stage.") for n in names)
    # the run-end metrics snapshot rides the same stream
    (mrow,) = [r for r in rows if r.get("kind") == "metrics"]
    mx = mrow["metrics"]
    # every processed arrival is one committed upload on the net path
    assert mx["window.arrivals"]["value"] == rep.net["n_uploads"]
    assert mx["net.uploads"]["value"] == rep.net["n_uploads"]
    assert mx["net.encoded_bytes"]["value"] == rep.net["encoded_bytes"]
    # per-upload link events reconcile with the NetTrace totals
    ups = [r for r in rows if r.get("name") == "net.upload"]
    assert len(ups) == rep.net["n_uploads"]
    assert sum(u["tags"]["encoded_bytes"] for u in ups) == \
        rep.net["encoded_bytes"]


def test_traced_run_chrome_trace_loadable(traced_run):
    _, _, paths = traced_run
    with open(paths["chrome"]) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert len(evs) > 10
    assert {e["ph"] for e in evs} <= {"M", "X", "i", "C"}
    assert all(set(e) >= {"ph", "pid"} for e in evs)
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert "cloud" in names and any(n.startswith("node ") for n in names)
    # simulation-side slices carry virtual-time stamps (µs, nonnegative)
    slices = [e for e in evs if e["ph"] == "X" and e["name"] == "window"]
    assert slices and all(e["ts"] >= 0.0 and e["dur"] >= 0.0
                          for e in slices)


def test_traced_run_replay_reconstructs_report(traced_run):
    _, rep, paths = traced_run
    rep2 = api.replay_records(paths["records"])
    assert rep2 == dataclasses.replace(rep, final_params=None)
    # crashed stream: drop the footer + tear the last record line — the
    # lenient replay returns the faithful prefix
    rows = open(paths["records"]).read().splitlines()
    torn = [r for r in rows if '"kind":"report"' not in r]
    crash = paths["records"] + ".crash"
    with open(crash, "w") as f:
        f.write("\n".join(torn[:-1]) + "\n" + torn[-1][:len(torn[-1]) // 2])
    with pytest.raises(ValueError, match="truncated final"):
        api.replay_records(crash)
    part = api.replay_records(crash, strict=False)
    assert part.records == rep.records[:-1]
    assert part.mode == rep.mode and part.engine == rep.engine


def test_detection_audit_reconstructs_fig6(traced_run):
    """Fig. 6's per-round rejection series must be derivable from the
    detect.verdict audit log alone (accuracy, threshold, ring occupancy,
    verdict per cloud evaluation)."""
    _, rep, paths = traced_run
    verdicts = [r for r in read_jsonl(paths["events"])
                if r.get("name") == "detect.verdict"]
    assert verdicts, "detection audit log missing"
    for v in verdicts:
        assert {"node", "accuracy", "threshold", "ring_held",
                "rejected"} <= set(v["tags"])
    assert sum(v["tags"]["rejected"] for v in verdicts) == \
        sum(r.n_rejected for r in rep.records)


def test_obs_disabled_is_bit_identical(traced_run):
    """The default-off contract: the identical experiment without obs
    produces the identical trajectory (tracing observes, never perturbs)."""
    spec, rep, _ = traced_run
    off = dataclasses.replace(spec, obs=api.ObsSpec())
    rep_off = api.run(api.compile_plan(off))
    assert rep_off.records == rep.records
    assert rep_off.kappa == rep.kappa
    assert rep_off.final_accuracy == rep.final_accuracy
    assert rep_off.detections == rep.detections


# ---------------------------------------------------------------------------
# net: NetTrace / NetSim summary invariants
# ---------------------------------------------------------------------------

def test_netsim_summary_invariants():
    rng = np.random.default_rng(0)
    sim = NetSim("sparse_coo",
                 LinkProfile(loss_prob=0.1, jitter_s=0.2, latency_s=0.01),
                 bandwidth_bps=np.full(6, 1e6), n_params=1_000,
                 sparsify_ratio=0.5, seed=7)
    sink = MemorySink()
    commits, uploads_after = [], []
    with use_tracer(Tracer([sink])):
        for _ in range(4):
            nodes = rng.choice(6, size=3, replace=False)
            draw = sim.draw(nodes)
            assert (draw.transfer_s > 0).all()
            enc = sim.commit(draw, rng.integers(100, 500, size=3))
            commits.append(float(enc.sum()))
            uploads_after.append(sim.trace.n_uploads)
    # totals are exactly the sum of commits; upload count is monotone
    assert sim.trace.total_encoded_bytes == sum(commits)
    assert uploads_after == [3, 6, 9, 12]
    s = sim.summary()
    assert s == sim.trace.summary()
    assert s["n_uploads"] == 12
    assert s["encoded_bytes"] == sum(commits)
    assert s["wire_bytes"] >= s["encoded_bytes"]
    assert s["transfer_s"] == pytest.approx(sum(sim.trace.transfer_s))
    assert s["retransmits"] == sum(sim.trace.retransmits) >= 0
    # the tracer saw one net.upload instant per committed upload
    ups = [e for e in sink.events if e.name == "net.upload"]
    assert len(ups) == 12
    assert sum(e.tags["encoded_bytes"] for e in ups) == s["encoded_bytes"]


# ---------------------------------------------------------------------------
# mesh: obs event ordering on a forced-8-device host
# ---------------------------------------------------------------------------

def test_mesh_obs_event_ordering_forced_8dev(tmp_path):
    """On a forced-8-device host the mesh async engine's event stream must
    keep the obs ordering contract: seq strictly increasing in file order,
    window spans closing in window order, and every detection verdict
    preceded by its node's arrival instant in the same window."""
    ev_path = str(tmp_path / "mesh_events.jsonl")
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax
        from repro import api

        spec = api.ExperimentSpec(
            fleet=api.FleetSpec(n_nodes=8, samples_per_node=20, n_test=32,
                                n_cloud_test=16,
                                attack=api.AttackMix(malicious_frac=0.25),
                                profile=api.NodeHeterogeneity(
                                    heterogeneity=0.8)),
            schedule=api.SchedulePolicy(kind="async"),
            defense=api.DefenseSpec(detect=True),
            topology=api.Topology(kind="mesh", devices=8),
            obs=api.ObsSpec(enabled=True, events_jsonl={ev_path!r}),
            train=api.TrainSpec(local_steps=2, batch_size=8, lr=0.1),
            rounds=2, seed=0)
        rep = api.run(api.compile_plan(spec))
        print(json.dumps({{"n_devices": len(jax.devices()),
                          "engine": rep.engine,
                          "n_rejected": sum(r.n_rejected
                                            for r in rep.records)}}))
    """)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["n_devices"] == 8 and out["engine"] == "fleet-mesh"

    rows = read_jsonl(ev_path)
    evs = [r for r in rows if r.get("kind") in ("span", "instant", "counter")]
    seqs = [e["seq"] for e in evs]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    windows = [e["tags"]["window"] for e in evs
               if e["kind"] == "span" and e["name"] == "window"]
    assert windows == sorted(windows) and len(windows) > 0
    verdicts = [e for e in evs if e["name"] == "detect.verdict"]
    arrivals = {(e["tags"]["node"], e["tags"]["window"]): e["seq"]
                for e in evs if e["name"] == "arrival"}
    assert verdicts, "mesh path must carry the detection audit log"
    for v in verdicts:
        key = (v["tags"]["node"], v["tags"]["window"])
        assert key in arrivals and arrivals[key] < v["seq"], \
            "verdict must follow its arrival in stream order"
    assert sum(v["tags"]["rejected"] for v in verdicts) == out["n_rejected"]
