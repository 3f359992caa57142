"""Local SGD's minibatch selection: a one-hot product that gives the
gather's rows bit for bit.

`fleet.stages.select_rows` takes ``x[idx]`` as ``one_hot(idx, M) @ x`` at
``Precision.HIGHEST``.  These tests hold it to the gather bit for bit, on
its own and through `make_local_train`, check that every shard shape and
dtype lowers to that one product, and read the `local_sgd.onehot_selects`
counter of an obs-enabled sync run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.fleet import stages
from repro.models.cnn import cnn_loss, init_cnn
from repro.models.mlp import init_mlp, mlp_loss
from repro.obs import read_jsonl

BATCH = 128


def _draw(key, size):
    """The minibatch indices exactly as `make_local_train`'s body draws
    them: bounded by the true shard length, not the padded one."""
    return jax.random.randint(key, (BATCH,), 0, size)


@pytest.mark.parametrize("m,size,dtype", [
    (1, 1, jnp.float32),
    (60, 60, jnp.float32),
    (60, 37, jnp.float32),         # a padded shard: rows past `size` unused
    (512, 512, jnp.float32),
    (512, 300, jnp.float32),
    (60, 60, jnp.bfloat16),
    (6000, 4000, jnp.float32),     # the paper's own shard length
    (60, 37, jnp.int32),
])
def test_onehot_rows_equal_the_gather(m, size, dtype):
    kx, ki = jax.random.split(jax.random.PRNGKey(m + size))
    # a wide spread of exponents and signs, so a lost bf16 part would show
    x = (jax.random.normal(kx, (m, 6, 5, 1)) *
         jnp.exp2(jax.random.randint(kx, (m, 6, 5, 1), -20, 20)))
    x = x.astype(dtype) if jnp.issubdtype(dtype, jnp.floating) else \
        jax.random.randint(kx, (m, 6, 5, 1), -2**31, 2**31 - 1, dtype)
    # rows past `size` are padding: the product multiplies them by 0, the
    # gather never reads them; either way none may reach the minibatch
    idx = _draw(ki, size)
    got = jax.jit(stages.select_rows)(x, idx)
    assert got.shape == (BATCH, 6, 5, 1) and got.dtype == x.dtype
    bits = f"u{x.dtype.itemsize}"
    np.testing.assert_array_equal(np.asarray(got).view(bits),
                                  np.asarray(x[idx]).view(bits))


def _cohort(model, m, c=3):
    """Params, stacked shards (c, m, ...), labels, sizes and keys."""
    kp, kx, ky, kk = jax.random.split(jax.random.PRNGKey(7), 4)
    if model == "cnn":
        params = init_cnn(kp, in_hw=(8, 8))
        x = jax.random.uniform(kx, (c, m, 8, 8, 1))
        loss = cnn_loss
    else:
        params = init_mlp(kp, 64)
        x = jax.random.normal(kx, (c, m, 64))
        loss = mlp_loss
    y = jax.random.randint(ky, (c, m), 0, 10)
    sizes = jnp.array([m, m - 7, 1][:c], jnp.int32)
    return loss, params, x, y, sizes, jax.random.split(kk, c)


@pytest.mark.parametrize("model", ["mlp", "cnn"])
def test_local_train_params_equal_through_either_path(model, monkeypatch):
    """The product against the gather it replaced, as `select_rows`."""
    loss, params, x, y, sizes, keys = _cohort(model, 60)

    def train():
        lt = stages.make_local_train(loss, local_steps=4, lr=0.1,
                                     batch_size=16)
        return jax.jit(jax.vmap(lt, in_axes=(None, 0, 0, 0, 0)))(
            params, x, y, sizes, keys)

    onehot = train()
    monkeypatch.setattr(stages, "select_rows", lambda xs, idx: xs[idx])
    gathered = train()
    for a, b in zip(jax.tree.leaves(onehot), jax.tree.leaves(gathered)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _lowered_local_train(m, dtype):
    lt = stages.make_local_train(mlp_loss, local_steps=2, lr=0.1,
                                 batch_size=BATCH)
    params = jax.eval_shape(lambda k: init_mlp(k, 784), jax.random.PRNGKey(0))
    shapes = (jax.ShapeDtypeStruct((3, m, 28, 28), dtype),
              jax.ShapeDtypeStruct((3, m), jnp.int32),
              jax.ShapeDtypeStruct((3,), jnp.int32),
              jax.eval_shape(lambda k: jax.random.split(k, 3),
                             jax.random.PRNGKey(0)))
    f = jax.jit(jax.vmap(lt, in_axes=(None, 0, 0, 0, 0)))
    return f.lower(params, *shapes).as_text()


@pytest.mark.parametrize("m,dtype", [
    (1, jnp.float32),
    (60, jnp.float32),
    (512, jnp.float32),
    (6000, jnp.float32),           # the paper's own 10 x 6,000 shards
    (60, jnp.int32),
])
def test_shard_shape_and_dtype_pick_the_selection(m, dtype):
    """Every shard shape and dtype picks the one product: the MLP's own
    dots lower at the default precision, so a ``HIGHEST`` dot is the
    selection and nothing else, and no gather reads the shard's rows."""
    text = _lowered_local_train(m, dtype)
    highest = [l for l in text.splitlines()
               if "dot_general" in l and "HIGHEST" in l]
    row_gathers = [l for l in text.splitlines()
                   if "stablehlo.gather" in l and f"x{m}x28x28x" in l]
    assert len(highest) == 1
    assert "precision = [HIGHEST, HIGHEST]" in highest[0]
    assert not row_gathers


def _sync_spec(events, local_steps):
    return api.ExperimentSpec(
        fleet=api.FleetSpec(n_nodes=4, samples_per_node=20, n_test=32,
                            n_cloud_test=16),
        schedule=api.SchedulePolicy(kind="sync"),
        train=api.TrainSpec(local_steps=local_steps, batch_size=8, lr=0.1),
        obs=api.ObsSpec(enabled=True, events_jsonl=events),
        rounds=2, seed=0)


@pytest.mark.parametrize("local_steps", [1, 3])
def test_onehot_selects_counter(local_steps, tmp_path):
    """Each record counts cohort x local steps one-hot selections."""
    events = str(tmp_path / "events.jsonl")
    rep = api.run(api.compile_plan(_sync_spec(events, local_steps)))
    (row,) = [r for r in read_jsonl(events) if r.get("kind") == "metrics"]
    mx = row["metrics"]
    assert len(rep.records) == 2
    assert mx["round.participants"]["value"] == 2 * 4
    assert mx["local_sgd.onehot_selects"]["value"] == 2 * 4 * local_steps
