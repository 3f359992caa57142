"""Per-architecture smoke tests: reduced variant of each assigned family runs
one forward/train step on CPU with correct shapes and no NaNs, and the
prefill+decode path agrees with teacher forcing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke_config
from repro.models import (decode_step, forward, init_cache, init_params,
                          loss_fn, prefill)

B, S = 2, 16


def make_batch(cfg, key, seq=S):
    ks = jax.random.split(key, 3)
    toks = jax.random.randint(ks[0], (B, seq), 0, cfg.vocab)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, 1)}
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(ks[1], (B, cfg.n_patches, cfg.d_model))
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(ks[2], (B, cfg.n_audio_frames, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_shapes_no_nan(arch):
    cfg = get_smoke_config(arch).replace(attn_chunk=8)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    batch = make_batch(cfg, key)
    logits, aux = forward(params, cfg, batch)
    assert logits.shape == (B, S, cfg.vocab)
    assert not jnp.isnan(logits).any()
    assert jnp.isfinite(aux)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = get_smoke_config(arch).replace(attn_chunk=8)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    batch = make_batch(cfg, key)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: loss_fn(p, cfg, batch), has_aux=True)(params)
    assert jnp.isfinite(loss)
    gn = sum(float(jnp.abs(g).sum()) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0
    new = jax.tree.map(lambda p, g: p - 1e-2 * g, params, grads)
    loss2, _ = loss_fn(new, cfg, batch)
    assert jnp.isfinite(loss2)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_prefill_decode_consistency(arch):
    cfg = get_smoke_config(arch).replace(attn_chunk=8, remat=False)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    batch = make_batch(cfg, key)
    logits, _ = forward(params, cfg, batch)
    cache = init_cache(cfg, B, 32, dtype=jnp.float32)
    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :-1]
    pre.pop("targets")
    lgp, cache = prefill(params, cfg, pre, cache)
    lgs, cache = decode_step(params, cfg, batch["tokens"][:, -1:], cache)
    np.testing.assert_allclose(np.asarray(lgp[:, 0]), np.asarray(logits[:, -2]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(lgs[:, 0]), np.asarray(logits[:, -1]),
                               rtol=2e-4, atol=2e-4)


def test_sliding_window_ring_cache():
    cfg = get_smoke_config("qwen1.5-0.5b").replace(
        sliding_window=8, attn_chunk=4, n_layers=2)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    toks = jax.random.randint(key, (1, 24), 0, cfg.vocab)
    logits, _ = forward(params, cfg, {"tokens": toks, "targets": toks})
    cache = init_cache(cfg, 1, 8, dtype=jnp.float32)
    lgp, cache = prefill(params, cfg, {"tokens": toks[:, :-1]}, cache)
    lgs, _ = decode_step(params, cfg, toks[:, -1:], cache)
    np.testing.assert_allclose(np.asarray(lgs[:, 0]), np.asarray(logits[:, -1]),
                               rtol=2e-4, atol=2e-4)


def test_param_count_analytic_close():
    for arch in ("smollm-360m", "olmo-1b"):
        cfg = get_smoke_config(arch)
        params = init_params(cfg, jax.random.PRNGKey(0))
        actual = sum(x.size for x in jax.tree.leaves(params))
        est = cfg.n_params()
        assert abs(actual - est) / actual < 0.2, (arch, actual, est)


# ---------------------------------------------------------------------------
# edge models: the argmax-free prediction that scores uploads (Alg. 2)
# ---------------------------------------------------------------------------

def test_predicted_class_is_first_argmax():
    from repro.models.cnn import predicted_class
    key = jax.random.PRNGKey(3)
    # values on a coarse grid so that rows tie, including at the maximum
    logits = jnp.round(jax.random.normal(key, (7, 250, 10)) * 2) / 2
    got = predicted_class(logits)
    want = logits.argmax(-1)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ties = (logits == logits.max(-1, keepdims=True)).sum(-1) > 1
    assert bool(ties.any())


@pytest.mark.parametrize("model", ["cnn", "mlp"])
@pytest.mark.parametrize("cohort", [3, 10, 250])
def test_edge_accuracy_bit_equal_to_argmax_form(model, cohort):
    """The vmapped per-node accuracies (the engines' detection scores) are
    the argmax form's, bit for bit, at cohort sizes on and off a multiple
    of 8."""
    from repro.models.cnn import cnn_accuracy, cnn_forward, init_cnn
    from repro.models.mlp import init_mlp, mlp_accuracy, mlp_forward
    kp, kd, kx, ky = jax.random.split(jax.random.PRNGKey(5), 4)
    if model == "cnn":
        params, fwd, acc = init_cnn(kp, in_hw=(8, 8)), cnn_forward, cnn_accuracy
        x = jax.random.normal(kx, (64, 8, 8, 1))
    else:
        params, fwd, acc = init_mlp(kp, 64), mlp_forward, mlp_accuracy
        x = jax.random.normal(kx, (64, 64))
    y = jax.random.randint(ky, (64,), 0, 10)
    keys = jax.random.split(kd, len(jax.tree.leaves(params)))
    cohort_params = jax.tree.map(
        lambda p, k: p[None] + 0.3 * jax.random.normal(k, (cohort,) + p.shape),
        params, jax.tree.unflatten(jax.tree.structure(params), list(keys)))
    got = jax.jit(jax.vmap(lambda p: acc(p, x, y)))(cohort_params)
    want = jax.jit(jax.vmap(
        lambda p: jnp.mean((fwd(p, x).argmax(-1) == y).astype(jnp.float32))
    ))(cohort_params)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert len(set(np.asarray(got).tolist())) > 1


# ---------------------------------------------------------------------------
# edge models: the CNN's ReLU keeps a bf16 mask source for its backward pass
# ---------------------------------------------------------------------------

def _bits(tree):
    return [np.asarray(a).view(np.uint8) for a in jax.tree.leaves(tree)]


def _assert_bit_equal(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for u, v in zip(_bits(a), _bits(b)):
        np.testing.assert_array_equal(u, v)


def _cnn_case(dtype, n=24, hw=(12, 12), seed=7):
    """CNN params with some bias entries exactly 0 and a batch whose first
    images are all zeros, so that both convolutions see exact-zero,
    negative and positive pre-activations."""
    from repro.models.cnn import init_cnn
    kp, kb, kx, ky = jax.random.split(jax.random.PRNGKey(seed), 4)
    params = init_cnn(kp, in_hw=hw)
    for name, k in zip(("conv1", "conv2", "fc"), jax.random.split(kb, 3)):
        b = params[name]["b"]
        b = 0.1 * jax.random.normal(k, b.shape)
        params[name]["b"] = b.at[::2].set(0.0)
    x = jax.random.normal(kx, (n,) + hw + (1,)).at[:3].set(0.0)
    y = jax.random.randint(ky, (n,), 0, 10)
    return params, x.astype(dtype), y


def test_relu_forward_and_gradient_bit_equal_to_jax_nn_relu():
    from repro.models.cnn import relu
    x = jnp.array([1e-40, -1e-40, 0.0, -0.0, 1.0, -2.0, 3e38, jnp.inf,
                   -jnp.inf, jnp.nan, 1.2e-38, 1.1754944e-38], jnp.float32)
    x = jnp.concatenate([x, jax.random.normal(jax.random.PRNGKey(0), (500,))])
    w = jnp.arange(1.0, x.size + 1)
    for dtype in (jnp.float32, jnp.bfloat16):
        xd = x.astype(dtype)
        got, want = jax.jit(relu)(xd), jax.jit(jax.nn.relu)(xd)
        assert got.dtype == want.dtype == dtype
        _assert_bit_equal(got, want)
        grad = lambda f: jax.jit(jax.grad(
            lambda v: (f(v).astype(jnp.float32) * w).sum()))(xd)
        _assert_bit_equal(grad(relu), grad(jax.nn.relu))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cnn_loss_gradient_bit_equal_to_jax_nn_relu(dtype, monkeypatch):
    """`jax.grad(cnn_loss)` through the bf16-mask ReLU equals the one
    through `jax.nn.relu` on every leaf, bit for bit, over a batch with
    exact-zero and negative pre-activations in both convolutions."""
    from repro.models import cnn
    params, x, y = _cnn_case(dtype)
    pre1 = jax.lax.conv_general_dilated(
        x, params["conv1"]["w"].astype(dtype), (2, 2), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + \
        params["conv1"]["b"].astype(dtype)
    assert bool((pre1 == 0).any()) and bool((pre1 < 0).any())
    assert bool((pre1 > 0).any())

    def grads():
        return jax.jit(jax.grad(
            lambda p: cnn.cnn_loss(p, {"x": x, "y": y})[0]))(params)

    got = grads()
    monkeypatch.setattr(cnn, "relu", jax.nn.relu)
    want = grads()
    _assert_bit_equal(got, want)
    assert all(bool(jnp.any(g != 0)) for g in jax.tree.leaves(got))


def test_cnn_local_train_params_bit_equal_to_jax_nn_relu(monkeypatch):
    """Local SGD of the fleet (`make_local_train`, vmapped over 3 nodes,
    2 steps) trains the same params, bit for bit, as with `jax.nn.relu`."""
    from repro.fleet import stages
    from repro.models import cnn
    params, x, y = _cnn_case(jnp.float32, n=3 * 20)
    xs, ys = x.reshape((3, 20) + x.shape[1:]), y.reshape(3, 20)
    sizes = jnp.array([20, 17, 9], jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(11), 3)

    def trained():
        lt = stages.make_local_train(cnn.cnn_loss, 2, 0.1, 8)
        return jax.jit(jax.vmap(lt, in_axes=(None, 0, 0, 0, 0)))(
            params, xs, ys, sizes, keys)

    got = trained()
    monkeypatch.setattr(cnn, "relu", jax.nn.relu)
    want = trained()
    _assert_bit_equal(got, want)
    assert not all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
                   zip(jax.tree.leaves(got), jax.tree.leaves(params)))
