"""The benchmark's yardstick on the CPU: the trace reduction on a stored
trace, the work counts against hand counts, the peak table, and the
discovery of cells and metrics by name."""
import json
import os
import shutil
import types

import pytest

from bench import cells, peaks, trace, work

HERE = os.path.dirname(os.path.abspath(__file__))


# ---------------------------------------------------------------------------
# interval arithmetic and a hand-made trace
# ---------------------------------------------------------------------------

def test_union_gaps_and_cover():
    iv = [(0, 10), (5, 20), (30, 40), (40, 45), (50, 50)]
    assert trace.union(iv) == [(0, 20), (30, 45)]
    assert trace.gaps(iv, (-5, 60)) == [(-5, 0), (20, 30), (45, 60)]
    assert trace.covered_ns(iv, (10, 35)) == 15


def hand_trace():
    """Window [0, 100) ns; device ops busy [10, 40) and [60, 70); an
    all-reduce [35, 50) of which [40, 50) runs alone."""
    dev = [trace.Op("fusion.1", 10, 30), trace.Op("_fused_kernel", 30, 40),
           trace.Op("all-reduce.2", 35, 50), trace.Op("sort.3", 60, 70)]
    host = [trace.Op(trace.WINDOW_SPAN, 0, 100),
            trace.Op("bench.step", 0, 55), trace.Op("bench.step", 55, 100),
            trace.Op("PjitFunction(round_fn)", 80, 95)]
    return trace.Trace({"/device:TPU:0": dev}, {"/host:CPU/python": host})


def test_busy_idle_and_kernel_time():
    t = hand_trace()
    assert trace.window_s(t) == pytest.approx(100e-9)
    assert trace.busy_s(t) == pytest.approx(50e-9)
    assert trace.idle_share(t) == pytest.approx(0.5)
    [(m, s)] = trace.matches(t, r"^_fused_(kernel)")
    assert m.group(1) == "kernel" and s == pytest.approx(10e-9)
    assert len(trace.matches(t, "sort")) == 1


def test_self_time_gives_nested_time_to_the_inner_op():
    t = hand_trace()
    t.devices["/device:TPU:0"].append(trace.Op("while.9", 5, 45))
    self = trace.self_times(t.devices["/device:TPU:0"], t.window())
    # the loop owns only [5, 10) before its body's first op starts
    assert self["while.9"] == pytest.approx(5)
    assert self["all-reduce.2"] == pytest.approx(15)
    assert sum(self.values()) == pytest.approx(55)
    assert trace.top_ops(t, 1) == [("fusion.1", pytest.approx(20e-9))]


def test_gap_attribution_names_innermost_host_span():
    gaps = trace.attribute_gaps(hand_trace())
    # gaps: [70, 100) 30 ns, [50, 60) 10 ns, [0, 10) 10 ns
    assert gaps[0] == ("PjitFunction(round_fn)", pytest.approx(30e-9))
    assert {g[0] for g in gaps[1:]} == {"bench.step"}


# ---------------------------------------------------------------------------
# a trace recorded on the chip: 51 ms of a traced fleet1k window up to a
# record's end (the DGC sort, the upload kernel, the cloud's scoring, the
# test pass and the host's syncs)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "fixtures", "trace_fleet1k.json")) as f:
        fix = json.load(f)
    return trace.Trace.from_json(fix["trace"]), fix["expect"]


def test_recorded_trace_reduces_to_stored_numbers(recorded):
    t, expect = recorded
    assert trace.busy_s(t) == pytest.approx(expect["busy_s"], rel=1e-9)
    assert trace.window_s(t) == pytest.approx(expect["window_s"], rel=1e-9)
    assert 0.0 < trace.idle_share(t) < 1.0
    run = types.SimpleNamespace(trace=t, n_params=20490, records=1,
                                peaks=peaks.peaks("TPU v5 lite"))
    share = 100 * expect["upload_calls"] * 16 * 1000 * 20490 / 819e9 \
        / expect["upload_s"]
    assert expect["upload_calls"] > 0
    assert share == pytest.approx(expect["upload_fused_roofline"], rel=1e-9)
    assert 0 < share < 100
    # the kernel's op in this trace predates its name (`%round_fn.1`):
    # the reader by name finds no op of its own
    assert cells.metric_reader("upload_fused_roofline")(run) is None
    gaps = trace.attribute_gaps(t)
    assert gaps and all(s > 0 for _, s in gaps)
    assert [n for n, _ in gaps] == expect["gap_names"]


# ---------------------------------------------------------------------------
# work and peaks
# ---------------------------------------------------------------------------

def test_cnn_flops_match_the_hand_count():
    cfg = cells.cell("fleet1k.aldpfl_sync").config
    cnn = cells.model_module(cfg)
    # conv1 28,224 MAC, conv2 225,792 MAC, fc 15,680 MAC at 28x28x1
    assert cnn.forward_flops(cfg) == 2 * (28224 + 225792 + 15680) == 539392
    assert cnn.n_params(cfg) == work.n_params(cfg) == 20490


def test_update_and_record_flops_of_the_1k_config():
    cfg = cells.cell("fleet1k.aldpfl_sync").config
    per = 539392 * (3 * 10 * 128 + 500)
    assert work.update_flops(cfg) == per
    assert work.record_flops(cfg) == 539392 * 10000
    round_flops = 1000 * per + work.record_flops(cfg)
    assert round_flops == pytest.approx(2.35e12, rel=0.01)


def test_step_mfu_reads_the_mlp_counts():
    """`step.mfu` of a run whose configuration names the MLP counts the
    MLP's FLOPs, through its module: 784 x 32 + 32 x 10 multiply-adds a
    sample's forward pass at 28x28x1."""
    with open(os.path.join(HERE, "fixtures", "tiny_mlp.json")) as f:
        cfg = json.load(f)
    f = 2 * (784 * 32 + 32 * 10)
    assert work.n_params(cfg) == 785 * 32 + 33 * 10
    assert work.update_flops(cfg) == f * (3 * 3 * 128 + 64)
    assert work.record_flops(cfg) == f * 128
    run = types.SimpleNamespace(config=cfg, updates=12, records=1,
                                window_s=2.0, chips=1,
                                peaks=peaks.peaks("TPU v5 lite"))
    flops = 12 * f * (3 * 3 * 128 + 64) + f * 128
    assert cells.metric_reader("step.mfu")(run) == pytest.approx(
        100.0 * flops / (2.0 * 197e12), rel=1e-12)


def test_kernel_byte_count():
    assert work.upload_fused_bytes(1000, 20490) == 16 * 1000 * 20490


def test_peaks_known_and_unknown():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")


# ---------------------------------------------------------------------------
# discovery by name: new files and entries, no edit of an existing file
# ---------------------------------------------------------------------------

def test_new_cell_config_and_metric_are_found_by_name(tmp_path, monkeypatch):
    root = tmp_path / "checkout"
    shutil.copytree(cells.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), root)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    bench = json.loads((root / "BENCHMARK.json").read_text())

    # the new files
    conf = json.loads((root / "bench/configs/paper_cnn_mnist_1k.json")
                      .read_text())
    conf.update(name="paper_cnn_mnist_2k", n_nodes=2000, samples_per_node=30)
    (root / "bench/configs/paper_cnn_mnist_2k.json").write_text(
        json.dumps(conf))
    (root / "bench/traffic/aldpfl_sync_slow.json").write_text(json.dumps(
        dict(json.loads((root / "bench/traffic/aldpfl_sync.json")
                        .read_text()), check_records=2)))
    (root / "bench/metrics/host_share.py").write_text(
        "def read(run):\n    return 42.0\n")
    (root / "bench/models/toy.py").write_text(
        "def n_params(config):\n    return 7\n")
    (root / "bench/configs/toy.json").write_text(json.dumps(
        dict(conf, name="toy", model="toy")))
    # the new entries
    bench["configs"].append({"name": "paper_cnn_mnist_2k", "source": "x",
                             "file": "bench/configs/paper_cnn_mnist_2k.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "fleet2k.slow", "config":
                               "paper_cnn_mnist_2k", "traffic":
                               "aldpfl_sync_slow", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "host_share", "unit": "%",
                               "better": "lower", "source": "host_clock",
                               "layer": "host", "moves": "updates_per_s",
                               "workloads": ["fleet2k.slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    monkeypatch.setattr(cells, "BENCH", str(root / "bench"))
    c = cells.cell("fleet2k.slow", root=str(root))
    assert c.config["n_nodes"] == 2000 and c.traffic["check_records"] == 2
    assert "host_share" in [m["name"] for m in c.per_layer]
    assert cells.metric_reader("host_share")(None) == 42.0
    with open(root / "bench/configs/toy.json") as f:
        assert work.n_params(json.load(f)) == 7
    assert all(p.read_bytes() == b for p, b in before.items())


# ---------------------------------------------------------------------------
# configurations: every setting is read, and the spec carries it
# ---------------------------------------------------------------------------

# the settings every configuration has; its model module's `SETTINGS`
# add the model's own
SETTINGS = {"model", "n_nodes", "samples_per_node", "n_test",
            "n_cloud_test", "local_steps", "batch_size", "lr", "alpha",
            "sigma", "clip_s", "sparsify_ratio", "detect_s", "detect_warmup",
            "codec", "backend"}
DOCUMENTATION = {"name", "source", "deployment", "reduced", "assumed",
                 "stated_precision"}


@pytest.mark.parametrize("name", [c["name"] for c in
                                  cells.load_benchmark()["configs"]])
def test_config_holds_settings_and_documentation_only(name):
    entry = {c["name"]: c for c in cells.load_benchmark()["configs"]}[name]
    with open(os.path.join(cells.ROOT, entry["file"])) as f:
        config = json.load(f)
    settings = SETTINGS | set(cells.model_module(config).SETTINGS)
    assert settings <= set(config)
    assert set(config) <= settings | DOCUMENTATION
    assert config["reduced"] == entry["reduced"]


def test_spec_carries_the_cells_settings_on_one_chip():
    from bench import drive
    c = cells.cell("fleet1k.aldpfl_sync")
    spec = drive.build_spec(c.config, c.traffic, seed=2 ** 31 + 11)
    assert spec.topology.kind == "single"
    assert spec.topology.backend == c.config["backend"]
    assert spec.fleet.n_nodes == c.config["n_nodes"]
    assert spec.fleet.samples_per_node == c.config["samples_per_node"]
    assert spec.train.local_steps == c.config["local_steps"]
    assert spec.schedule.kind == c.traffic["schedule"] == "sync"
    assert spec.privacy.sigma == c.config["sigma"]
    assert spec.defense.detect_s == c.config["detect_s"]
    assert spec.seed == 2 ** 31 + 11
