"""A run of the benchmark, on the CPU at a test size, with the timed path
broken underneath: the check has to read `correct` false for each fault
a training cell can have, and true for the sound program.

The run is the one `bench/run.py` makes after its look for a chip
(`run.run_cell`), under the real cell's name, so that it is held to the
cell's own limits, with a test-size configuration: the paper's CNN in
`fixtures/tiny.json`, and the fleet's MLP in `fixtures/tiny_mlp.json`,
which no cell runs and which reaches the harness through its model
module alone.  The control and the reference's planted faults are judged
as `bench/control.py` judges them on the chip."""
import json
import os

import jax
import pytest

from bench import cells, check, control, reference, run

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "fleet1k.aldpfl_sync"
TINY = ("tiny", "tiny_mlp")


def tiny_cell(name=CELL, fixture="tiny"):
    real = cells.cell(name)
    with open(os.path.join(HERE, "fixtures", f"{fixture}.json")) as f:
        config = json.load(f)
    return cells.Cell(name, config, real.traffic, 1, real.end_to_end, [])


def unchanged(global_tree, new_tree, alpha):
    """A step that returns its state unchanged."""
    return global_tree


def half_mean(orig):
    """Half of the batch of kept uploads left out, the mean taken over
    the rest."""
    def masked_mean(trees, mask):
        keep = jax.numpy.cumsum(mask) <= mask.sum() // 2
        return orig(trees, mask & keep)
    return masked_mean


def altered(orig, path):
    """The fold's answer altered where it is produced: the leaf at `path`
    (the model module's `ALTERED`) doubled."""
    def mix(global_tree, new_tree, alpha):
        out = orig(global_tree, new_tree, alpha)
        *up, last = path
        node = check.leaf(out, tuple(up))
        node[last] = node[last] * 2 + 1e-3
        return out
    return mix


def faults(config):
    from repro.core import async_update, detection
    path = cells.model_module(config).ALTERED
    return {
        "unchanged": (async_update, "mix", unchanged),
        "half": (detection, "masked_mean", half_mean(detection.masked_mean)),
        "altered": (async_update, "mix", altered(async_update.mix, path)),
    }


@pytest.mark.parametrize("fixture", TINY)
@pytest.mark.parametrize("fault", ["sound", "unchanged", "half", "altered"])
def test_check_catches_fault(fault, fixture, monkeypatch):
    cell = tiny_cell(fixture=fixture)
    if fault != "sound":
        monkeypatch.setattr(*faults(cell.config)[fault])
    line = run.run_cell(cell, seed=2 ** 31 + 5, seconds=0.2, trace=False)
    assert line["correct"] is (fault == "sound"), line["check"]
    assert list(line)[-1] == "check"
    assert line["metrics"]["updates_per_s"]["value"] > 0


@pytest.mark.parametrize("fixture", TINY)
def test_control_in_bfloat16_fails_the_check(fixture):
    """The control: the reference computed in bfloat16, the precision
    below the configuration's float32 (the model module's
    `control_kwargs`), put in the program's place and held to the cell's
    own limits."""
    [line] = control.readings(tiny_cell(fixture=fixture), 2 ** 31 + 6,
                              program=False, control=True, faults=False)
    assert line["run"] == "control"
    assert line["correct"] is False, line["check"]


@pytest.mark.parametrize("fixture", TINY)
def test_control_readings_of_program_and_planted_faults(fixture):
    """What `bench/control.py` prints for a seed: the sound program is
    correct by the cell's limits, each fault planted in the reference is
    not, and each number stands beside its limit."""
    lines = control.readings(tiny_cell(fixture=fixture), 2 ** 31 + 7,
                             program=True, control=False, faults=True)
    got = {line["run"]: line["correct"] for line in lines}
    assert got == {"program": True, **{f"fault.{f}": False
                                        for f in reference.FAULTS}}, lines
    for line in lines:
        assert set(line["check"]) == set(check.limits(CELL))
        assert all(set(v) == {"value", "limit"}
                   for v in line["check"].values())


def test_reference_writes_the_sync_schedule_only():
    cell = tiny_cell()
    with pytest.raises(ValueError, match="no reference for schedule"):
        reference.run(cell.config, dict(cell.traffic, schedule="async"),
                      None, 0, 1)


def test_unknown_model_names_the_missing_file():
    with pytest.raises(FileNotFoundError,
                       match=r"model 'no_such_net': no module .*"
                             r"bench/models/no_such_net\.py"):
        cells.model_module({"model": "no_such_net"})
