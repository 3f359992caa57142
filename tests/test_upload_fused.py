"""Parity tests for the fused upload-pipeline megakernel and the
arrival-order window-fold kernel.

Contracts under test (see kernels/upload_fused.py, kernels/window_fold.py):

  * the megakernel's noise on a zero delta ≡ the jnp mirror's
    `block_noise` **bitwise** (same blocks, same per-block hash streams);
  * fused megakernel ≡ the jnp mirror `upload_fused_reference` — bitwise
    on sparsify/residual/nnz, ~1-ulp on the noised upload (XLA contracts
    the scale-multiply + noise-add into an FMA inside the kernel);
  * pallas-backend `upload_pipeline` ≡ reference backend at sigma=0
    (noise streams differ between backends by design, the sparse
    coordinate set and nnz must not);
  * `window_fold_fleet` ≡ the lax.scan reference bitwise, and ≡ a
    sequence of gated `mix_stale` applications via its (a, b)
    coefficients.

Property tests (hypothesis, optional dev dep) randomize cohort sizes,
leaf layouts, ratios, sigmas and gate patterns around those contracts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _optional import given, settings, st  # hypothesis, optional

from repro.core import mix_stale_sequence, staleness_alpha
from repro.fleet import stages
from repro.kernels.upload_fused import (block_noise, upload_fused_fleet,
                                        upload_fused_reference)
from repro.kernels.window_fold import window_fold_fleet, window_fold_reference


def _cohort(k, sizes, seed=0, scale=1.0):
    """(flat deltas (k, n), flat residuals, leaf boundaries) with awkward
    (non-LANE-aligned) total length."""
    n = sum(sizes)
    kd, kr = jax.random.split(jax.random.PRNGKey(seed))
    flat = jax.random.normal(kd, (k, n), jnp.float32) * scale
    res = jax.random.normal(kr, (k, n), jnp.float32) * scale
    offs = tuple(int(b) for b in np.cumsum((0,) + tuple(sizes))[:-1])
    return flat, res, offs


def _thresholds(flat, res, offs, ratio):
    from repro.core import accumulator as accum
    comb = flat + res
    ends = list(offs[1:]) + [flat.shape[1]]
    return jnp.stack(
        [jax.vmap(lambda v: accum.leaf_threshold(v, ratio))(
            comb[:, o:e]) for o, e in zip(offs, ends)], axis=1)


# ---------------------------------------------------------------------------
# fused megakernel vs jnp mirror
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ratio,sigma", [
    (0.3, 0.0),     # sparsify only
    (1.0, 0.5),     # noise only
    (0.3, 0.5),     # full pipeline
])
def test_fused_kernel_matches_jnp_mirror(ratio, sigma):
    k, sizes = 4, (700, 1301, 96)
    flat, res, offs = _cohort(k, sizes, seed=1)
    do_sp = ratio < 1.0
    thr = _thresholds(flat, res, offs, ratio) if do_sp else None
    seeds = jnp.arange(11, 11 + k, dtype=jnp.int32)
    comb = flat + res if do_sp else flat
    if do_sp:
        from repro.kernels.upload_fused import spread_thresholds
        sp = jnp.where(jnp.abs(comb) >= spread_thresholds(
            thr, offs, flat.shape[1]), comb, 0.0)
    else:
        sp = flat
    scales = 1.0 / jnp.maximum(1.0, jnp.sqrt(
        jnp.sum(jnp.square(sp), 1))) if sigma > 0 else None
    args = (flat, res if do_sp else None, thr, seeds, scales, sigma, 1.0)
    up_k, nr_k, nnz_k = upload_fused_fleet(*args, boundaries=offs,
                                           need_nnz=True)
    up_r, nr_r, nnz_r = upload_fused_reference(*args, boundaries=offs,
                                               need_nnz=True)
    np.testing.assert_array_equal(np.asarray(nnz_k), np.asarray(nnz_r))
    if do_sp:
        np.testing.assert_array_equal(np.asarray(nr_k), np.asarray(nr_r))
    # noised upload: FMA contraction inside the kernel => ~1 ulp
    np.testing.assert_allclose(np.asarray(up_k), np.asarray(up_r),
                               atol=1e-6)


def test_fused_noise_matches_unfused_ldp_kernel_bitwise():
    """Same seeds, same block decomposition: on a zero delta the
    megakernel's in-kernel noise is the jnp mirror's unfused replay of
    the hash streams (`block_noise`), bit for bit — with no delta there is
    no multiply-add for XLA to contract, so nothing may differ."""
    k, n = 3, 4000
    seeds = jnp.arange(5, 5 + k, dtype=jnp.int32)
    up_f, _, _ = upload_fused_fleet(jnp.zeros((k, n)), None, None, seeds,
                                    jnp.ones(k), 0.4, 1.0)
    np.testing.assert_array_equal(np.asarray(up_f),
                                  np.asarray(block_noise(k, n, seeds, 0.4)))


def test_block_noise_is_seed_deterministic_and_node_distinct():
    seeds = jnp.array([7, 7, 8], jnp.int32)
    a = block_noise(3, 2000, seeds, 0.5)
    b = block_noise(3, 2000, seeds, 0.5)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(a[1]))
    assert float(np.max(np.abs(np.asarray(a[0] - a[2])))) > 0.0


def test_pallas_pipeline_matches_reference_backend_at_sigma0():
    """Stage-level: the pallas fused upload pipeline returns the reference
    backend's sparse coordinate set, residuals and nnz when no noise is
    drawn (noise streams differ between backends by design)."""
    import dataclasses as dc
    from repro.fleet.engine import FleetConfig
    k = 5
    tree = {"w": jax.random.normal(jax.random.PRNGKey(3), (k, 37, 29)),
            "b": jax.random.normal(jax.random.PRNGKey(4), (k, 53))}
    res = jax.tree.map(jnp.zeros_like, tree)
    res = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(5), x.shape) * 0.1,
        res)
    k2s = jax.random.split(jax.random.PRNGKey(6), k)
    cfg = FleetConfig(sigma=0.0, sparsify_ratio=0.25, backend="reference")
    up_r, nr_r, nnz_r = stages.upload_pipeline(cfg, tree, res, k2s,
                                               need_nnz=True)
    cfg_p = dc.replace(cfg, backend="pallas")
    up_p, nr_p, nnz_p = stages.upload_pipeline(cfg_p, tree, res, k2s,
                                               need_nnz=True)
    np.testing.assert_array_equal(np.asarray(nnz_p), np.asarray(nnz_r))
    for a, b in zip(jax.tree.leaves(up_p), jax.tree.leaves(up_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    for a, b in zip(jax.tree.leaves(nr_p), jax.tree.leaves(nr_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_fused_pipeline_ratio_one_skips_sparsify_keeps_residuals():
    import dataclasses as dc
    from repro.fleet.engine import FleetConfig
    k = 3
    tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (k, 64))}
    res = {"w": jnp.full((k, 64), 0.25)}
    k2s = jax.random.split(jax.random.PRNGKey(1), k)
    cfg = FleetConfig(sigma=0.2, sparsify_ratio=1.0, backend="pallas")
    up, nr, nnz = stages.upload_pipeline(cfg, tree, res, k2s, need_nnz=True)
    # residuals untouched, nnz counts the dense (pre-noise) delta
    np.testing.assert_array_equal(np.asarray(nr["w"]), np.asarray(res["w"]))
    np.testing.assert_array_equal(np.asarray(nnz), np.full(k, 64))
    # and the noiseless-noiseless edge is a true no-op fast path
    cfg0 = dc.replace(cfg, sigma=0.0)
    up0, nr0, _ = stages.upload_pipeline(cfg0, tree, res, k2s)
    np.testing.assert_array_equal(np.asarray(up0["w"]),
                                  np.asarray(tree["w"]))
    assert nr0 is res


@settings(deadline=None, max_examples=20)
@given(st.integers(1, 5), st.integers(1, 3),
       st.floats(0.05, 1.0), st.floats(0.0, 1.0),
       st.integers(0, 2**16))
def test_fused_property_matches_mirror(k, n_leaves, ratio, sigma, seed):
    """Property: for random cohort shapes, leaf layouts, DGC ratios and
    noise levels, kernel and jnp mirror agree — bitwise on the sparse
    coordinate set (residuals, nnz), 1e-6 on values."""
    rng = np.random.default_rng(seed)
    sizes = tuple(int(s) for s in rng.integers(1, 1500, n_leaves))
    flat, res, offs = _cohort(k, sizes, seed=seed)
    do_sp = ratio < 1.0
    thr = _thresholds(flat, res, offs, ratio) if do_sp else None
    seeds = jnp.asarray(rng.integers(0, 2**31 - 1, k), jnp.int32)
    scales = (jnp.asarray(rng.uniform(0.1, 1.0, k), jnp.float32)
              if sigma > 0 else None)
    args = (flat, res if do_sp else None, thr, seeds, scales, sigma, 1.0)
    up_k, nr_k, nnz_k = upload_fused_fleet(*args, boundaries=offs,
                                           need_nnz=True)
    up_r, nr_r, nnz_r = upload_fused_reference(*args, boundaries=offs,
                                               need_nnz=True)
    np.testing.assert_array_equal(np.asarray(nnz_k), np.asarray(nnz_r))
    if do_sp:
        np.testing.assert_array_equal(np.asarray(nr_k), np.asarray(nr_r))
    np.testing.assert_allclose(np.asarray(up_k), np.asarray(up_r),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# window-fold kernel
# ---------------------------------------------------------------------------

def test_window_fold_matches_scan_reference_bitwise():
    c, n = 7, 3001
    key = jax.random.PRNGKey(0)
    p = jax.random.normal(key, (n,))
    om = jax.random.normal(jax.random.PRNGKey(1), (c, n))
    gates = jnp.array([1, 0, 1, 1, 0, 1, 1])
    a = jax.random.uniform(jax.random.PRNGKey(2), (c,))
    b = 1.0 - a
    f_k, s_k = window_fold_fleet(p, om, gates, a, b)
    f_r, s_r = window_fold_reference(p, om, gates, a, b)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_r))
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_r))


def test_window_fold_all_gates_off_passes_params_through():
    p = jax.random.normal(jax.random.PRNGKey(0), (500,))
    om = jnp.ones((3, 500))
    final, seq = window_fold_fleet(p, om, jnp.zeros(3, jnp.int32),
                                   jnp.full(3, 0.5), jnp.full(3, 0.5))
    np.testing.assert_array_equal(np.asarray(final), np.asarray(p))
    for i in range(3):
        np.testing.assert_array_equal(np.asarray(seq[i]), np.asarray(p))


def test_window_fold_matches_mix_stale_sequence():
    """The kernel under FedAsync coefficients a=1−w(τ), b=w(τ) reproduces
    the public `mix_stale_sequence` building block (gated arrivals and
    all)."""
    c, alpha = 6, 0.5
    tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (40, 7)),
            "b": jnp.ones((13,))}
    stack = {"w": jax.random.normal(jax.random.PRNGKey(1), (c, 40, 7)),
             "b": jax.random.normal(jax.random.PRNGKey(2), (c, 13))}
    taus = jnp.array([0, 3, 1, 7, 2, 0])
    gates = jnp.array([1, 1, 0, 1, 1, 1])
    w = staleness_alpha(alpha, taus)
    layout = stages.cohort_layout(stack)
    final, _ = window_fold_fleet(layout.flatten_one(tree),
                                 layout.flatten(stack), gates,
                                 1.0 - w, w)
    ref, _ = mix_stale_sequence(tree, stack, taus, alpha,
                                gate=gates.astype(bool))
    for got, want in zip(jax.tree.leaves(layout.unflatten_one(final)),
                         jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# pallas backend end-to-end (api level): the engines running the fused
# megakernel + window-fold kernel against the reference backend / mesh
# ---------------------------------------------------------------------------

def _scheme_run(kind, sigma, backend, obs=None):
    from repro import api
    from repro.data import make_federated_image_data
    from repro.fleet import NodeProfile
    from repro.models.mlp import init_mlp, mlp_accuracy, mlp_loss
    n = 6
    node_data, test, cloud, _ = make_federated_image_data(
        0, n_nodes=n, n_malicious=2, n_train=240, n_test=128,
        n_cloud_test=64, hw=(8, 8))
    spec = api.ExperimentSpec(
        fleet=api.FleetSpec(n_nodes=n),
        schedule=api.SchedulePolicy(kind=kind),
        privacy=api.PrivacySpec(sigma=sigma),
        compression=api.CompressionSpec(sparsify_ratio=0.5),
        defense=api.DefenseSpec(detect=True),
        topology=api.Topology(kind="single", backend=backend),
        train=api.TrainSpec(local_steps=3, batch_size=16, lr=0.1),
        rounds=3, seed=0, obs=obs if obs is not None else api.ObsSpec())
    pop = api.Population(
        params=init_mlp(jax.random.PRNGKey(0), 64), loss_fn=mlp_loss,
        acc_fn=mlp_accuracy, node_data=node_data, test_data=test,
        cloud_test=cloud,
        profile=NodeProfile.lognormal(n, 1.0, 0.5, 12.5e6, seed=0))
    return api.run(api.compile_plan(spec), pop)


@pytest.mark.parametrize("kind", ["sync", "async"])
def test_pallas_backend_api_matches_reference_sigma0(kind):
    """σ=0 removes the only backend-divergent piece (the noise stream):
    the fused-megakernel engines must reproduce the reference backend's
    trajectory through the full api path (sync round fold and the async
    window-fold kernel both exercised)."""
    ref = _scheme_run(kind, 0.0, "reference")
    pal = _scheme_run(kind, 0.0, "pallas")
    assert len(ref.records) == len(pal.records)
    np.testing.assert_allclose([r.accuracy for r in pal.records],
                               [r.accuracy for r in ref.records], atol=2e-3)
    assert [r.n_rejected for r in pal.records] == \
        [r.n_rejected for r in ref.records]
    for a, b in zip(jax.tree.leaves(pal.final_params),
                    jax.tree.leaves(ref.final_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("kind", ["sync", "async"])
def test_pallas_backend_with_noise_trains_and_charges_budget(kind):
    rep = _scheme_run(kind, 0.05, "pallas")
    assert rep.epsilon_spent > 0
    assert 0.0 <= rep.final_accuracy <= 1.0
    assert len(rep.records) == 3


@pytest.mark.parametrize("kind", ["sync", "async"])
def test_pallas_backend_obs_tracing_does_not_change_results(kind, tmp_path):
    """Enabling the obs layer must not perturb the fused-kernel engines:
    record streams agree field-for-field with the obs-off run."""
    from repro import api
    off = _scheme_run(kind, 0.05, "pallas")
    on = _scheme_run(kind, 0.05, "pallas", obs=api.ObsSpec(
        enabled=True, events_jsonl=str(tmp_path / f"{kind}.jsonl")))
    assert len(on.records) == len(off.records)
    for a, b in zip(on.records, off.records):
        assert (a.t, a.version, a.accuracy, a.comm_bytes, a.n_rejected) == \
            (b.t, b.version, b.accuracy, b.comm_bytes, b.n_rejected)
    assert (tmp_path / f"{kind}.jsonl").exists()


def test_pallas_mesh_matches_single_device_on_8_devices():
    """Shard-obliviousness acceptance: the fused megakernel + window-fold
    kernel inside `shard_map` on a forced-8-device host mesh reproduce the
    single-device pallas trajectories for all four schemes."""
    import json
    import os
    import subprocess
    import sys
    import textwrap
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, json
        import jax, numpy as np
        from repro import api
        from repro.data import make_federated_image_data
        from repro.fleet import NodeProfile
        from repro.models.mlp import init_mlp, mlp_accuracy, mlp_loss

        n = 8
        node_data, test, cloud, _ = make_federated_image_data(
            0, n_nodes=n, n_malicious=2, n_train=320, n_test=128,
            n_cloud_test=64, hw=(8, 8))
        out = {"n_devices": len(jax.devices())}
        schemes = {"sfl": ("sync", 0.0), "afl": ("async", 0.0),
                   "sldpfl": ("sync", 0.05), "aldpfl": ("async", 0.05)}
        for mode, (kind, sigma) in schemes.items():
            spec = api.ExperimentSpec(
                fleet=api.FleetSpec(n_nodes=n),
                schedule=api.SchedulePolicy(kind=kind),
                privacy=api.PrivacySpec(sigma=sigma),
                compression=api.CompressionSpec(sparsify_ratio=0.5),
                defense=api.DefenseSpec(detect=True),
                topology=api.Topology(kind="single", backend="pallas"),
                train=api.TrainSpec(local_steps=3, batch_size=16, lr=0.1),
                rounds=2, seed=0)

            def pop():
                return api.Population(
                    params=init_mlp(jax.random.PRNGKey(0), 64),
                    loss_fn=mlp_loss, acc_fn=mlp_accuracy,
                    node_data=node_data, test_data=test, cloud_test=cloud,
                    profile=NodeProfile.lognormal(n, 1.0, 0.5, 12.5e6,
                                                  seed=0))

            ref = api.run(api.compile_plan(spec), population=pop())
            mesh_spec = dataclasses.replace(
                spec, topology=api.Topology(kind="mesh", devices=8,
                                            backend="pallas"))
            rep = api.run(api.compile_plan(mesh_spec), population=pop())
            assert rep.engine == "fleet-mesh", rep.engine
            hist = ref.records
            out[f"{mode}_len"] = len(hist) - len(rep.records)
            out[f"{mode}_acc"] = max(abs(a.accuracy - b.accuracy)
                                     for a, b in zip(hist, rep.records))
            out[f"{mode}_rej"] = int(sum(a.n_rejected != b.n_rejected
                                         for a, b in zip(hist,
                                                         rep.records)))
        print(json.dumps(out))
    """)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src)
    env.pop("XLA_FLAGS", None)          # the child forces its own devices
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["n_devices"] == 8
    for mode in ("sfl", "afl", "sldpfl", "aldpfl"):
        assert out[f"{mode}_len"] == 0, (mode, out)
        assert out[f"{mode}_acc"] < 2e-3, (mode, out)
        assert out[f"{mode}_rej"] == 0, (mode, out)


@settings(deadline=None, max_examples=20)
@given(st.integers(1, 8), st.integers(1, 4000), st.integers(0, 2**16))
def test_window_fold_property_matches_reference(c, n, seed):
    """Property: random window sizes, param lengths (incl. < one lane),
    gate patterns and coefficients — kernel ≡ scan reference bitwise."""
    rng = np.random.default_rng(seed)
    p = jnp.asarray(rng.normal(size=n), jnp.float32)
    om = jnp.asarray(rng.normal(size=(c, n)), jnp.float32)
    gates = jnp.asarray(rng.integers(0, 2, c), jnp.int32)
    a = jnp.asarray(rng.uniform(0.0, 1.0, c), jnp.float32)
    b = jnp.asarray(rng.uniform(0.0, 1.0, c), jnp.float32)
    f_k, s_k = window_fold_fleet(p, om, gates, a, b)
    f_r, s_r = window_fold_reference(p, om, gates, a, b)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_r))
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_r))
