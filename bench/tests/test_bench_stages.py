"""The readers of the fleet's stages on the CPU: device time by the
program's named scopes, exposed idle by the program's host spans, on
hand-made traces, on the HLO of a program compiled here, and on a trace
recorded on the chip."""
import json
import os
import types

import pytest

from bench import cells, peaks, stages, trace, work

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = ("local_sgd.ms_per_record", "cloud_score.ms_per_record",
           "host.exposed_ms_per_record")


def read(name, run):
    return cells.metric_reader(name)(run)


# the readers by shape that the readers by scope and name replaced: any
# 2-D f32 sort, and any custom call with the upload kernel's outputs
DGC_SORT = r"^%sort[.\d]* = \(f32\[\d+,\d+\]"
UPLOAD_CALL = (r"= \(f32\[(\d+),(\d+),1024\](\{[^}]*\}), f32\[\1,\2,1024\]"
               r"\S*, s32\[\1,1,128\]\S*\) custom-call\(")


def dgc_by_shape(run):
    seconds = sum(s for _, s in trace.matches(run.trace, DGC_SORT))
    return 1e3 * seconds / run.records if seconds > 0 else None


def upload_by_shape(run):
    calls = [(m, s) for m, s in trace.matches(run.trace, UPLOAD_CALL)
             if "S(1)" not in m.group(3)]
    seconds = sum(s for _, s in calls)
    if not calls or seconds <= 0:
        return None
    nbytes = sum(work.upload_fused_bytes(int(m.group(1)), run.n_params)
                 for m, _ in calls)
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / seconds


def readings(run) -> dict:
    """Each repointed reader's value beside its shape reader's."""
    return {name: {"by_scope_and_name": read(name, run),
                   "by_shape": old(run)}
            for name, old in (("dgc_threshold.ms_per_record", dgc_by_shape),
                              ("upload_fused_roofline", upload_by_shape))}


# ---------------------------------------------------------------------------
# exposed idle: each gap goes to the innermost program span at its middle
# ---------------------------------------------------------------------------

def host_trace():
    """Window [0, 100) ns, two records.  Device busy [5, 30), [40, 60),
    [70, 90): gaps [0, 5), [30, 40), [60, 70), [90, 100)."""
    dev = [trace.Op("%fusion.1 = f32[8]{0} fusion()", 5, 30),
           trace.Op("%sort.2 = f32[8]{0} sort()", 40, 60),
           trace.Op("%fusion.1 = f32[8]{0} fusion()", 70, 90)]
    host = [trace.Op(trace.WINDOW_SPAN, 0, 100),
            trace.Op("bench.step", 0, 50), trace.Op("bench.step", 50, 100),
            trace.Op("round", 2, 48), trace.Op("round", 52, 92),
            # [30, 40) is under the readback inside the first round
            trace.Op("stage.round.readback", 28, 42),
            trace.Op("np.asarray(jax.Array)", 33, 39),
            # [60, 70) is under a collection inside the evaluation
            trace.Op("stage.round.evaluate", 55, 75),
            trace.Op("py.gc", 62, 68)]
    return trace.Trace({"/device:TPU:0": dev}, {"/host:CPU/main": host})


def test_exposed_idle_goes_to_the_innermost_program_span():
    t = host_trace()
    assert stages.round_count(t) == 2
    assert stages.exposed_ns(t) == {
        "round": 5.0,                       # [0, 5)
        # the runtime's np.asarray is not a program span
        "stage.round.readback": 10.0,
        "py.gc": 10.0,
        # [90, 100): under bench.step alone
        stages.UNATTRIBUTED: 10.0}
    run = types.SimpleNamespace(trace=t, records=2, scopes={})
    assert read("host.exposed_ms_per_record", run) == pytest.approx(
        25.0 / 1e6 / 2)


def test_a_program_without_spans_or_scopes_reads_none():
    """An older commit's trace (no program spans, no scopes): every
    reader returns None, and none raises."""
    t = host_trace()
    t.host["/host:CPU/main"] = [o for o in t.host["/host:CPU/main"]
                                if not stages.is_program_span(o.name)]
    run = types.SimpleNamespace(trace=t, records=2, scopes={})
    assert [read(n, run) for n in READERS] == [None, None, None]
    # spans but no scope in the program's metadata
    run.trace = host_trace()
    assert read("local_sgd.ms_per_record", run) is None


# ---------------------------------------------------------------------------
# device time by scope
# ---------------------------------------------------------------------------

HLO = """
%body.1 (p: f32[8]) -> f32[8] {
  %fusion.3 = f32[8]{0:T(256)} fusion(%p), kind=kLoop, metadata={op_name="jit(round_fn)/fleet.local_sgd/while/body/mul"}
  ROOT %copy.4 = f32[8]{0} copy(%fusion.3)
}
%cond.1 (p: f32[8]) -> pred[] {
  ROOT %lt.1 = pred[] compare(%p, %p), direction=LT
}
ENTRY %main {
  %while.1 = f32[8]{0} while(%x), condition=%cond.1, body=%body.1, metadata={op_name="jit(round_fn)/fleet.local_sgd/while"}
  %sort.36 = (f32[1000,15680]{1,0:T(8,128)}, s32[1000,15680]{1,0:T(8,128)}) sort(%a, %b), dimensions={1}, metadata={op_name="jit(round_fn)/fleet.upload/sort"}
  %upload_fused.1 = (f32[4,24,1024]{2,1,0:T(8,128)}) custom-call(%c), custom_call_target="tpu_custom_call", metadata={op_name="jit(round_fn)/fleet.upload/upload_fused/pallas_call"}
  %fusion.7 = f32[4]{0} fusion(%d), kind=kLoop, metadata={op_name="jit(round_fn)/fleet.cloud_score/vmap(jit(cnn_accuracy))/dot_general"}
  %copy.2 = f32[4]{0} copy(%fusion.7)
  ROOT %fusion.9 = f32[8]{0} fusion(%e), metadata={op_name="jit(round_fn)/concatenate"}
}
"""


def test_instruction_keys_and_scopes_of_an_hlo_module():
    assert stages.instruction_key(
        "%sort.36 = (f32[1000,15680]{1,0:T(8,128)}, s32[1000,15680]"
        "{1,0:T(8,128)}) sort(f32[1000,15680]") == (
        "sort.36", "(f32[1000,15680]{1,0:T(8,128)}, s32[1000,15680]"
                   "{1,0:T(8,128)})")
    assert stages.instruction_key(
        "  ROOT %fusion.3 = f32[8]{0:T(256)} fusion(%p)") == (
        "fusion.3", "f32[8]{0:T(256)}")
    assert stages.instruction_key("np.asarray(jax.Array)") is None
    assert stages.hlo_scopes(HLO) == {
        ("fusion.3", "f32[8]{0:T(256)}"): "fleet.local_sgd",
        # no metadata of their own: the loop's scope, through its body
        # and its condition
        ("copy.4", "f32[8]{0}"): "fleet.local_sgd",
        ("lt.1", "pred[]"): "fleet.local_sgd",
        ("while.1", "f32[8]{0}"): "fleet.local_sgd",
        ("sort.36", "(f32[1000,15680]{1,0:T(8,128)}, s32[1000,15680]"
                    "{1,0:T(8,128)})"): "fleet.upload",
        ("upload_fused.1", "(f32[4,24,1024]{2,1,0:T(8,128)})"):
            "fleet.upload",
        ("fusion.7", "f32[4]{0}"): "fleet.cloud_score",
        ("copy.2", "f32[4]{0}"): stages.UNSCOPED,
        ("fusion.9", "f32[8]{0}"): stages.UNSCOPED}


def test_scope_time_is_self_time_by_scope():
    scopes = stages.hlo_scopes(HLO)
    loop = "%while.1 = f32[8]{0} while(%x)"
    dev = [trace.Op(loop, 0, 50),
           trace.Op("%fusion.3 = f32[8]{0:T(256)} fusion(%p)", 10, 30),
           trace.Op("%sort.36 = (f32[1000,15680]{1,0:T(8,128)}, s32[1000,"
                    "15680]{1,0:T(8,128)}) sort(f32", 50, 70),
           trace.Op("%fusion.7 = f32[4]{0} fusion(%d)", 70, 75),
           trace.Op("%fusion.9 = f32[8]{0} fusion(%e)", 75, 80),
           # a copy the compiler made outside any scoped computation
           trace.Op("%copy.2 = f32[4]{0} copy(%fusion.7)", 80, 90)]
    host = [trace.Op(trace.WINDOW_SPAN, 0, 100), trace.Op("round", 0, 100)]
    t = trace.Trace({"/device:TPU:0": dev}, {"/host:CPU/main": host})
    assert stages.scope_ns(t, scopes) == {
        stages.UNSCOPED: 15.0, "fleet.local_sgd": 50.0,
        "fleet.upload": 20.0, "fleet.cloud_score": 5.0}
    run = types.SimpleNamespace(trace=t, records=1, scopes=scopes)
    assert read("local_sgd.ms_per_record", run) == pytest.approx(50e-6)
    assert read("cloud_score.ms_per_record", run) == pytest.approx(5e-6)
    b = stages.breakdown(run)
    assert b["scoped_share_of_busy"] == pytest.approx(75 / 90)
    assert b["round_spans"] == b["records"] == 1


def test_a_key_two_executables_scope_differently_is_refused():
    """The map is merged over every live executable: a key they agree on
    keeps its scope, one they put to two scopes cannot be read from the
    event alone, so an op of it in the window raises."""
    fusion = ("fusion.3", "f32[8]{0:T(256)}")
    sort = ("sort.36", "f32[8]{0}")
    scopes = stages.merged([
        {fusion: "fleet.local_sgd", sort: "fleet.upload"},
        {fusion: "fleet.local_sgd", sort: stages.UNSCOPED},
        {sort: "fleet.upload"}])
    assert scopes == {fusion: "fleet.local_sgd", sort: stages.AMBIGUOUS}
    dev = [trace.Op("%fusion.3 = f32[8]{0:T(256)} fusion(%p)", 0, 10)]
    host = [trace.Op(trace.WINDOW_SPAN, 0, 100), trace.Op("round", 0, 100)]
    t = trace.Trace({"/device:TPU:0": dev}, {"/host:CPU/main": host})
    assert stages.scope_ns(t, scopes) == {"fleet.local_sgd": 10.0}
    dev.append(trace.Op("%sort.36 = f32[8]{0} sort(%a)", 20, 30))
    with pytest.raises(ValueError, match="two live executables"):
        stages.scope_ns(t, scopes)


def test_live_scopes_read_the_process_executables():
    """A program compiled here: its instructions' scopes come back from
    the process's live executables, keyed as its op events are named."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def round_fn(x):
        with jax.named_scope("fleet.local_sgd"):
            y = jnp.sin(x) @ x
        with jax.named_scope("fleet.cloud_score"):
            return jnp.sort(y, axis=1)

    x = jnp.ones((16, 16))
    round_fn(x).block_until_ready()
    hlo = round_fn.lower(x).compile().as_text()
    scopes = stages.live_scopes()
    texts = [line.strip() for line in hlo.splitlines()
             if "fleet." in line and " = " in line]
    assert texts
    found = {stages.op_scope(t.split(", metadata=")[0], scopes)
             for t in texts}
    assert found == {"fleet.local_sgd", "fleet.cloud_score"}


# ---------------------------------------------------------------------------
# a record recorded on the chip: one whole fleet1k record, round span to
# round span, with the scopes the live round program's HLO gave its ops
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "fixtures",
                           "trace_fleet1k_scoped.json")) as f:
        fix = json.load(f)
    run = types.SimpleNamespace(
        trace=trace.Trace.from_json(fix["trace"]), records=1,
        scopes={(n, s): v for n, s, v in fix["scopes"]})
    return run, fix["expect"]


def test_recorded_record_reduces_to_stored_numbers(recorded):
    run, expect = recorded
    for name in READERS:
        assert read(name, run) == pytest.approx(expect[name], rel=1e-9)
    assert trace.busy_s(run.trace) == pytest.approx(expect["busy_s"],
                                                    rel=1e-9)
    assert trace.window_s(run.trace) == pytest.approx(expect["window_s"],
                                                      rel=1e-9)
    b, want = stages.breakdown(run), expect["breakdown"]
    for part in ("device_ms_per_record", "exposed_ms_per_record"):
        assert b[part] == pytest.approx(want[part], rel=1e-9)
    assert b["scoped_share_of_busy"] == pytest.approx(
        want["scoped_share_of_busy"], rel=1e-9)
    assert b["round_spans"] == b["records"] == 1
    # every stage of the round is there, and nearly all of the busy time
    # lies under a scope
    assert set(b["device_ms_per_record"]) == {
        "fleet.local_sgd", "fleet.upload", "fleet.cloud_score",
        "fleet.fold", "fleet.evaluate", stages.UNSCOPED}
    assert b["scoped_share_of_busy"] >= 0.95


def test_repointed_readers_read_what_the_shape_readers_read(recorded):
    """On the recorded record, the readers by scope and name find the ops
    the readers by shape found, and read the same value to the last
    digit: the six DGC sorts under ``fleet.upload`` (not Alg. 2's sort of
    1,000 scores under ``fleet.cloud_score``) and the one
    ``%upload_fused.1`` call."""
    run, _ = recorded
    run = types.SimpleNamespace(**vars(run), n_params=20490,
                                peaks=peaks.peaks("TPU v5 lite"))
    got = readings(run)
    for pair in got.values():
        assert pair["by_scope_and_name"] == pair["by_shape"]
    dgc = got["dgc_threshold.ms_per_record"]["by_scope_and_name"]
    assert dgc == pytest.approx(18.65, rel=1e-3)
    roof = got["upload_fused_roofline"]["by_scope_and_name"]
    assert roof == pytest.approx(100 * 16 * 1000 * 20490 / 819e9
                                 / 1086992e-9, rel=1e-12)


def test_repointed_readers_take_no_op_of_another_stage_or_kernel():
    """A 2-D f32 sort under another scope, and another kernel with the
    upload kernel's output shapes: the readers by shape count both, the
    readers by scope and name neither."""
    upload = ("(f32[8,24,1024]{2,1,0:T(8,128)}, f32[8,24,1024]{2,1,0:T(8,"
              "128)}, s32[8,1,128]{2,1,0:T(1,128)}) custom-call(")
    sort = "(f32[8,4608]{1,0}, s32[8,4608]{1,0}) sort("
    dev = [trace.Op(f"%sort.1 = {sort}", 0, 10),
           trace.Op(f"%sort.2 = {sort}", 10, 30),
           trace.Op(f"%upload_fused.1 = {upload}", 30, 40),
           trace.Op(f"%lora_fused.3 = {upload}", 40, 60)]
    host = [trace.Op(trace.WINDOW_SPAN, 0, 100), trace.Op("round", 0, 100)]
    scopes = {("sort.1", sort[:-6]): "fleet.upload",
              ("sort.2", sort[:-6]): "fleet.local_sgd"}
    run = types.SimpleNamespace(
        trace=trace.Trace({"/device:TPU:0": dev}, {"/host:CPU/main": host}),
        records=1, scopes=scopes, n_params=20490,
        peaks=peaks.peaks("TPU v5 lite"))
    got = readings(run)
    dgc, roof = (got["dgc_threshold.ms_per_record"],
                 got["upload_fused_roofline"])
    assert dgc["by_scope_and_name"] == pytest.approx(10e-9 * 1e3)
    assert dgc["by_shape"] == pytest.approx(30e-9 * 1e3)
    assert roof["by_scope_and_name"] == pytest.approx(
        100 * 16 * 8 * 20490 / 819e9 / 10e-9)
    assert roof["by_shape"] == pytest.approx(
        100 * 2 * 16 * 8 * 20490 / 819e9 / 30e-9)


# ---------------------------------------------------------------------------
# a cell without a stage: the readers of the fleet's stages apply to every
# cell, and one whose trace lacks a reader's scope, op or spans reads None
# ---------------------------------------------------------------------------

def _without_scope(scope):
    def strip(run):
        run.scopes = {k: v for k, v in run.scopes.items() if v != scope}
    return strip


def _without_ops(prefix):
    def strip(run):
        run.trace.devices = {d: [o for o in ops
                                 if not o.name.startswith(prefix)]
                             for d, ops in run.trace.devices.items()}
    return strip


def _without_program_spans(run):
    run.trace.host = {t: [o for o in ops if not stages.is_program_span(o.name)]
                      for t, ops in run.trace.host.items()}


@pytest.mark.parametrize("name, strip", [
    ("local_sgd.ms_per_record", _without_scope("fleet.local_sgd")),
    ("cloud_score.ms_per_record", _without_scope("fleet.cloud_score")),
    ("dgc_threshold.ms_per_record", _without_ops("%sort")),
    ("upload_fused_roofline", _without_ops("%upload_fused")),
    ("host.exposed_ms_per_record", _without_program_spans),
])
def test_stage_reader_reads_none_where_its_stage_is_missing(recorded, name,
                                                            strip):
    """On the recorded record each reader reads a number; with its scope,
    op or program spans taken out, as in a cell that runs no such stage,
    it reads None and does not raise.  The five carry no `workloads`
    list in BENCHMARK.json: every cell reads them."""
    base, _ = recorded
    run = types.SimpleNamespace(
        trace=trace.Trace(dict(base.trace.devices), dict(base.trace.host)),
        records=base.records, scopes=dict(base.scopes), n_params=20490,
        peaks=peaks.peaks("TPU v5 lite"))
    assert read(name, run) > 0
    strip(run)
    assert read(name, run) is None
    entry = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}[name]
    assert "workloads" not in entry
