"""The model module's own arrays (`Inputs.extra`) on both sides of the
check, on the CPU.

The reference gets them as the last argument of the module's `loss` and
`forward`, placed on the device once per run; the program gets them
through the module's `population_fields`.  The model here is a tiny
frozen-feature model: a frozen random layer, stored in float16, under a
trainable dense head."""
import inspect
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells, drive, population, reference
from bench.models import _images

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "fleet1k.aldpfl_sync"
# nodes, rows a node, input width, frozen features, classes
N, M, D, F, K = 6, 20, 12, 8, 4
HEAD = F * K + K                        # the trainable tree's parameters
CONFIG = {"model": "frozen_features", "local_steps": 2, "batch_size": 8,
          "lr": 0.1, "sparsify_ratio": 0.25, "clip_s": 1.0, "sigma": 0.05,
          "alpha": 0.5, "detect_s": 50.0}


def frozen_features(closed_over=None):
    """The model module: logits = tanh(x W) H + b with W frozen.  W comes
    from `extra["frozen"]`, or, given `closed_over`, from that array as a
    constant of the module, `extra` left unread."""
    mod = types.ModuleType("frozen_features")

    def forward(p, x, precision, extra):
        w = extra["frozen"] if closed_over is None else closed_over
        h = jnp.tanh(jnp.dot(x, w.astype(x.dtype), precision=precision))
        return jnp.dot(h, p["head"]["w"], precision=precision) \
            + p["head"]["b"]

    def loss(p, x, y, precision, extra):
        logp = jax.nn.log_softmax(forward(p, x, precision, extra))
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    mod.forward, mod.loss, mod.accuracy = forward, loss, _images.accuracy
    mod.NODE_BLOCK, mod.TEST_BLOCK, mod.ALTERED = 4, 16, ("head", "b")
    return mod


def inputs(seed=2 ** 31 + 11) -> population.Inputs:
    rng = np.random.default_rng(seed)
    frozen = rng.standard_normal((D, F)).astype(np.float16)

    def rows(n):
        x = rng.standard_normal((n, D)).astype(np.float32)
        return x, (x[:, :K].argmax(-1)).astype(np.int32)

    x, y = rows(N * M)
    return population.Inputs(
        params={"head": {"w": (rng.standard_normal((F, K)) / np.sqrt(F))
                         .astype(np.float32),
                         "b": np.zeros((K,), np.float32)}},
        x=x.reshape(N, M, D), y=y.reshape(N, M), test=rows(40),
        cloud=rows(24), malicious=[], compute_s=np.ones(N),
        bandwidth_bps=np.ones(N), extra={"frozen": frozen})


def run(monkeypatch, module, inp, records=2, **kw):
    monkeypatch.setattr(reference, "model_module", lambda config: module)
    return reference.run_sync(CONFIG, inp, 2 ** 31 + 3, records, **kw)


def test_reference_takes_extra_as_the_closed_over_model_does(monkeypatch):
    """Passed as an argument, the frozen layer gives the readings of the
    same model with the layer closed over, bit for bit, over 2 records."""
    inp = inputs()
    passed = run(monkeypatch, frozen_features(), inp)
    closed = run(monkeypatch,
                 frozen_features(jnp.asarray(inp.extra["frozen"])),
                 population.Inputs(**dict(vars(inp), extra={})))
    assert len(passed.params) == 2
    for a, b in zip(passed.params, closed.params):
        np.testing.assert_array_equal(a["head"]["w"], b["head"]["w"])
        np.testing.assert_array_equal(a["head"]["b"], b["head"]["b"])
    assert passed.accuracy == closed.accuracy
    assert passed.rejected == closed.rejected
    assert passed.comm_bytes == closed.comm_bytes
    # the round moved the head: the comparison is not of two idle runs
    assert not np.array_equal(passed.params[-1]["head"]["w"],
                              inp.params["head"]["w"])


def test_extra_reaches_the_jitted_functions_placed_once(monkeypatch):
    """`_block` and `_logits` get `extra` as device arrays, the same ones
    in every call of a run, in the dtype the module stored them in (also
    when the round computes in bfloat16), and the trainable tree alone
    is flattened, uploaded and kept as residuals."""
    seen = []

    def spy(fn):
        def wrapped(*args, **kw):
            bound = inspect.signature(fn).bind(*args, **kw).arguments
            seen.append((fn.__name__, bound["extra"], bound.get("res")))
            return fn(*args, **kw)
        return wrapped

    monkeypatch.setattr(reference, "_block", spy(reference._block))
    monkeypatch.setattr(reference, "_logits", spy(reference._logits))
    inp = inputs()
    for dtype in (jnp.float32, jnp.bfloat16):
        seen.clear()
        out = run(monkeypatch, frozen_features(), inp, dtype=dtype)
        names = [name for name, _, _ in seen]
        # 2 blocks of nodes and 3 blocks of the test set, a record each
        assert names.count("_block") == 4 and names.count("_logits") == 6
        first = seen[0][1]["frozen"]
        assert isinstance(first, jax.Array)
        assert not isinstance(first, np.ndarray)
        assert first.dtype == np.float16
        np.testing.assert_array_equal(np.asarray(first), inp.extra["frozen"])
        assert all(extra["frozen"] is first for _, extra, _ in seen)
        # the residuals, one flat row a node, hold the head alone
        assert {res.shape[1] for name, _, res in seen
                if name == "_block"} == {HEAD}
        assert [list(p) for p in out.params] == [["head"], ["head"]]
    flat = reference.flatten(jax.tree.map(jnp.asarray, inp.params))
    assert flat.shape == (HEAD,)


# ---------------------------------------------------------------------------
# the program's side: `drive.population` forwards `population_fields`
# ---------------------------------------------------------------------------

@pytest.fixture
def tiny():
    with open(os.path.join(HERE, "fixtures", "tiny.json")) as f:
        config = json.load(f)
    return config, population.make_inputs(config, cells.cell(CELL).traffic,
                                          2 ** 31 + 13)


def _module_with_fields(config, fields):
    """The configuration's model module with `population_fields` giving
    `fields`."""
    real = cells.model_module(config)
    mod = types.ModuleType("with_fields")
    mod.program_fns = real.program_fns
    mod.population_fields = lambda config, inputs: fields
    return mod


def test_population_forwards_the_module_fields(tiny, monkeypatch):
    from repro import api
    config, inp = tiny
    frozen = object()
    monkeypatch.setattr(drive, "model_module", lambda config:
                        _module_with_fields(config, {"frozen": frozen}))
    made = []
    monkeypatch.setattr(api, "Population",
                        lambda **kw: made.append(kw) or kw)
    drive.population(config, inp)
    [kw] = made
    assert kw["frozen"] is frozen
    assert kw["params"] is inp.params
    assert len(kw["node_data"]) == config["n_nodes"]


@pytest.mark.parametrize("key", ["params", "loss_fn", "cloud_test"])
def test_population_refuses_a_field_it_sets_itself(tiny, monkeypatch, key):
    config, inp = tiny
    monkeypatch.setattr(drive, "model_module", lambda config:
                        _module_with_fields(config, {key: None}))
    with pytest.raises(ValueError, match=rf"population_fields sets "
                                         rf"\['{key}'\]"):
        drive.population(config, inp)

