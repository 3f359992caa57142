"""Fleet engine tests: stacked state, samplers, batched round equivalence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.data import make_federated_image_data
from repro.fleet import (AvailabilityTrace, FleetData, FullParticipation,
                         SCENARIOS, UniformSampler, build_engine,
                         chain_node_keys, detect_masked, gather_nodes,
                         get_scenario, scatter_nodes, stack_trees,
                         unstack_tree)
from repro.models.mlp import init_mlp, mlp_accuracy, mlp_loss


# ---------------------------------------------------------------------------
# stacked-state helpers
# ---------------------------------------------------------------------------

def test_stack_gather_scatter_roundtrip():
    trees = [{"w": jnp.full((3,), float(i)), "b": {"c": jnp.ones((2, 2)) * i}}
             for i in range(5)]
    stacked = stack_trees(trees)
    assert stacked["w"].shape == (5, 3)
    got = unstack_tree(stacked, 5)
    for a, b in zip(got, trees):
        np.testing.assert_array_equal(np.asarray(a["w"]), np.asarray(b["w"]))

    idx = jnp.array([3, 1])
    cohort = gather_nodes(stacked, idx)
    np.testing.assert_array_equal(np.asarray(cohort["w"][0]), 3.0)
    back = scatter_nodes(stacked, idx, jax.tree.map(lambda x: x * 10, cohort))
    np.testing.assert_array_equal(np.asarray(back["w"][3]), 30.0)
    np.testing.assert_array_equal(np.asarray(back["w"][0]), 0.0)  # untouched


def test_scatter_nodes_debug_rejects_conflicting_duplicates():
    """Duplicate scatter indices must carry identical values (the padded-
    cohort contract); the debug check catches silent last-write-wins."""
    tree = {"w": jnp.zeros((4, 2))}
    idx = jnp.array([1, 1, 3])
    same = {"w": jnp.ones((3, 2)).at[2].set(5.0)}
    out = scatter_nodes(tree, idx, same, debug=True)     # identical dups: ok
    np.testing.assert_array_equal(np.asarray(out["w"][1]), [1.0, 1.0])

    differing = {"w": jnp.asarray([[1.0, 1.0], [2.0, 2.0], [5.0, 5.0]])}
    with pytest.raises(ValueError, match="duplicated index 1"):
        scatter_nodes(tree, idx, differing, debug=True)
    # debug off: documented last-write-wins, no check
    out = scatter_nodes(tree, idx, differing, debug=False)
    np.testing.assert_array_equal(np.asarray(out["w"][1]), [2.0, 2.0])


def test_fleet_data_rejects_empty_shards():
    """`from_node_data` must fail loudly — not with `sizes.max()` blowing up
    or a padded size-0 shard poisoning randint — on empty input."""
    with pytest.raises(ValueError, match="empty node list"):
        FleetData.from_node_data([])
    good = (np.ones((3, 2), np.float32), np.ones(3, np.int32))
    empty = (np.zeros((0, 2), np.float32), np.zeros(0, np.int32))
    with pytest.raises(ValueError, match=r"node\(s\) \[1\]"):
        FleetData.from_node_data([good, empty])


def test_fleet_data_pads_unequal_shards():
    node_data = [(np.ones((4, 2), np.float32), np.ones(4, np.int32)),
                 (np.ones((7, 2), np.float32), np.ones(7, np.int32))]
    fd = FleetData.from_node_data(node_data)
    assert fd.x.shape == (2, 7, 2) and fd.y.shape == (2, 7)
    np.testing.assert_array_equal(np.asarray(fd.sizes), [4, 7])
    assert float(fd.x[0, 4:].sum()) == 0.0  # right-padding is zeros


def test_chain_node_keys_matches_sequential_split():
    key = jax.random.PRNGKey(42)
    seq = []
    k = key
    for _ in range(6):
        k, k1, k2 = jax.random.split(k, 3)
        seq.append((k1, k2))
    kend, k1s, k2s = chain_node_keys(key, 6)
    np.testing.assert_array_equal(np.asarray(kend), np.asarray(k))
    for i, (k1, k2) in enumerate(seq):
        np.testing.assert_array_equal(np.asarray(k1s[i]), np.asarray(k1))
        np.testing.assert_array_equal(np.asarray(k2s[i]), np.asarray(k2))


# ---------------------------------------------------------------------------
# samplers
# ---------------------------------------------------------------------------

def test_uniform_sampler_static_cohort():
    s = UniformSampler(4, seed=0)
    seen = set()
    for r in range(20):
        idx, valid = s.cohort(r, 10)
        assert idx.shape == (4,) and valid.all()
        assert len(set(idx)) == 4          # without replacement
        seen.update(idx.tolist())
    assert len(seen) > 4                   # cohorts actually rotate


def test_availability_trace_never_starves():
    s = AvailabilityTrace(probs=np.zeros(8), seed=0)
    for r in range(5):
        idx, valid = s.cohort(r, 8)
        assert idx.shape == (8,) and valid.sum() == 1

    trace = np.zeros((3, 8), bool)
    trace[1, 2] = True
    st = AvailabilityTrace(trace=trace, seed=0)
    _, v1 = st.cohort(1, 8)
    assert v1[2] and v1.sum() == 1


def test_availability_requires_exactly_one_source():
    with pytest.raises(ValueError):
        AvailabilityTrace()
    with pytest.raises(ValueError):
        AvailabilityTrace(probs=np.ones(4), trace=np.ones((2, 4), bool))


def test_availability_rejects_too_narrow_coverage():
    with pytest.raises(ValueError, match="covers 4 nodes"):
        AvailabilityTrace(trace=np.ones((2, 4), bool)).cohort(0, 8)
    with pytest.raises(ValueError, match="covers 4 nodes"):
        AvailabilityTrace(probs=np.ones(4)).cohort(0, 8)


# ---------------------------------------------------------------------------
# masked detection
# ---------------------------------------------------------------------------

def test_detect_masked_reduces_to_detect_when_all_valid():
    from repro.core.detection import detect
    accs = jnp.array([0.9, 0.92, 0.91, 0.88, 0.3, 0.25, 0.93, 0.89])
    m1, t1 = detect(accs, 30.0)
    m2, t2 = detect_masked(accs, jnp.ones(8, bool), 30.0)
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
    assert float(t1) == pytest.approx(float(t2))


def test_detect_masked_excludes_invalid_slots():
    accs = jnp.array([0.9, 0.91, 0.92, 0.0, 0.0])   # last two are padding
    valid = jnp.array([True, True, True, False, False])
    mask, thr = detect_masked(accs, valid, 50.0)
    assert not bool(mask[3]) and not bool(mask[4])
    # threshold from the valid three only: median 0.91, not dragged to 0
    assert float(thr) == pytest.approx(0.91, abs=1e-6)


# ---------------------------------------------------------------------------
# engine ≡ sequential reference loop (the acceptance bar: K=8, 5 rounds)
# ---------------------------------------------------------------------------

def _paired_sync_reports(sigma, sparsify):
    """(fleet report, sequential-reference report) for one sync scheme —
    the seed per-node loop (`Topology('sequential')`) is the parity
    oracle the batched engine is held to."""
    node_data, test, cloud, _ = make_federated_image_data(
        0, n_nodes=8, n_malicious=2, n_train=640, n_test=256,
        n_cloud_test=128, hw=(8, 8))

    def run(topology):
        from repro.fleet import NodeProfile
        spec = api.ExperimentSpec(
            fleet=api.FleetSpec(n_nodes=8),
            schedule=api.SchedulePolicy(kind="sync"),
            privacy=api.PrivacySpec(sigma=sigma),
            compression=api.CompressionSpec(sparsify_ratio=sparsify),
            defense=api.DefenseSpec(detect=True),
            topology=api.Topology(kind=topology),
            train=api.TrainSpec(local_steps=8, batch_size=16, lr=0.1),
            rounds=5, seed=0)
        pop = api.Population(
            params=init_mlp(jax.random.PRNGKey(0), 64), loss_fn=mlp_loss,
            acc_fn=mlp_accuracy, node_data=node_data, test_data=test,
            cloud_test=cloud,
            profile=NodeProfile.lognormal(8, 1.0, 0.5, 12.5e6, seed=0))
        return api.run(api.compile_plan(spec), population=pop)

    return run("single"), run("sequential")


@pytest.mark.parametrize("sigma,sparsify", [
    (0.0, 1.0),           # plain sync FedAvg + detection (sfl)
    (0.05, 1.0),          # + LDP noise, shared PRNG chain (sldpfl)
    (0.05, 0.25),         # + DGC sparsified uploads
])
def test_fleet_sync_matches_sequential(sigma, sparsify):
    fleet_rep, seq_rep = _paired_sync_reports(sigma, sparsify)
    hf, hs = fleet_rep.records, seq_rep.records
    accs_f = np.array([r.accuracy for r in hf])
    accs_s = np.array([r.accuracy for r in hs])
    np.testing.assert_allclose(accs_f, accs_s, atol=2e-3)
    for a, b in zip(jax.tree.leaves(fleet_rep.final_params),
                    jax.tree.leaves(seq_rep.final_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    # simulated clock, wire bytes and rejections agree too
    np.testing.assert_allclose([r.t for r in hf], [r.t for r in hs],
                               rtol=1e-9)
    assert [r.n_rejected for r in hf] == [r.n_rejected for r in hs]
    assert [r.comm_bytes for r in hf] == [r.comm_bytes for r in hs]
    assert fleet_rep.epsilon_spent == pytest.approx(seq_rep.epsilon_spent)


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------

def test_all_scenarios_build_and_run_one_round():
    for name in SCENARIOS:
        sc = get_scenario(name).with_nodes(min(SCENARIOS[name].n_nodes, 8))
        eng = build_engine(sc, seed=0)
        rec = eng.run(1)[-1]
        assert 0.0 <= rec.accuracy <= 1.0
        assert rec.n_participating >= 1


def test_unknown_scenario_raises():
    with pytest.raises(KeyError):
        get_scenario("nope")


def test_honest_fleet_learns():
    sc = dataclasses.replace(get_scenario("honest"), local_steps=10, lr=0.2)
    eng = build_engine(sc, seed=0)
    hist = eng.run(12)
    assert hist[-1].accuracy > hist[0].accuracy + 0.15, \
        [r.accuracy for r in hist]


def test_straggler_scenario_slows_rounds():
    base = build_engine(get_scenario("honest"), seed=0)
    slow = build_engine(get_scenario("stragglers").with_nodes(10), seed=0)
    base.run(1)
    slow.run(1)
    assert slow.history[0].comp_time > base.history[0].comp_time


def test_churn_scenario_partial_participation():
    eng = build_engine(get_scenario("churn"), seed=0)
    recs = eng.run(4)
    parts = [r.n_participating for r in recs]
    assert min(parts) >= 1 and max(parts) <= eng.n_nodes
    assert any(p < eng.n_nodes for p in parts)


def test_cohort_sampling_updates_only_sampled_residuals():
    """DGC residuals of nodes outside the cohort must stay untouched."""
    class LoggingSampler(UniformSampler):
        def __init__(self):
            super().__init__(3, seed=7)
            self.seen = set()

        def cohort(self, round_idx, n_nodes):
            idx, valid = super().cohort(round_idx, n_nodes)
            self.seen.update(idx.tolist())
            return idx, valid

    sc = dataclasses.replace(get_scenario("honest"), sparsify_ratio=0.25,
                             local_steps=3)
    sampler = LoggingSampler()
    eng = build_engine(sc, seed=0, sampler=sampler)
    eng.run(3)
    res_norm = np.asarray(jnp.stack([
        jnp.sqrt(sum(jnp.sum(jnp.square(leaf[i]))
                     for leaf in jax.tree.leaves(eng.state.residuals)))
        for i in range(eng.n_nodes)]))
    for node in range(eng.n_nodes):
        if node in sampler.seen:
            assert res_norm[node] > 0.0, node
        else:
            assert res_norm[node] == 0.0, node


# ---------------------------------------------------------------------------
# pallas backend (the node-batched fused upload kernel)
# ---------------------------------------------------------------------------

def test_ldp_fleet_kernel_matches_flat():
    """Noise only: node row i of a 3-node fused upload call is a 1-node
    call with node i's seed and clip scale."""
    from repro.kernels.upload_fused import upload_fused_fleet
    rng = np.random.default_rng(0)
    flat = jnp.asarray(rng.normal(size=(3, 2000)).astype(np.float32))
    seeds = jnp.array([11, 22, 33], jnp.int32)
    scales = jnp.array([0.5, 1.0, 0.25], jnp.float32)
    batched, _, _ = upload_fused_fleet(flat, None, None, seeds, scales,
                                       0.3, 1.5)
    for i in range(3):
        single, _, _ = upload_fused_fleet(flat[i:i + 1], None, None,
                                          seeds[i:i + 1], scales[i:i + 1],
                                          0.3, 1.5)
        # the two programs may contract scale·x + noise into an FMA
        # differently: one ulp
        np.testing.assert_allclose(np.asarray(batched[i]),
                                   np.asarray(single[0]), atol=1e-6)


def test_dgc_fleet_kernel_matches_flat():
    """Sparsify only: node row i of a 3-node fused upload call is a
    1-node call with node i's threshold."""
    from repro.kernels.upload_fused import upload_fused_fleet
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.normal(size=(3, 1500)).astype(np.float32))
    r = jnp.asarray(rng.normal(size=(3, 1500)).astype(np.float32))
    thr = jnp.array([[0.5], [1.0], [2.0]], jnp.float32)
    up_b, nr_b, _ = upload_fused_fleet(g, r, thr, None, None, 0.0, 1.0)
    for i in range(3):
        up, nr, _ = upload_fused_fleet(g[i:i + 1], r[i:i + 1],
                                       thr[i:i + 1], None, None, 0.0, 1.0)
        np.testing.assert_array_equal(np.asarray(up_b[i]),
                                      np.asarray(up[0]))
        np.testing.assert_array_equal(np.asarray(nr_b[i]),
                                      np.asarray(nr[0]))


def test_pallas_backend_matches_reference_without_noise():
    """σ=0 removes the only backend-divergent piece (noise source); the
    sparsify threshold rule is shared, so trajectories must agree."""
    sc = dataclasses.replace(get_scenario("honest"), sparsify_ratio=0.25,
                             local_steps=4)
    ref = build_engine(sc, seed=0, backend="reference")
    pal = build_engine(sc, seed=0, backend="pallas")
    hr = ref.run(3)
    hp = pal.run(3)
    np.testing.assert_allclose([r.accuracy for r in hp],
                               [r.accuracy for r in hr], atol=2e-3)
    for a, b in zip(jax.tree.leaves(pal.params), jax.tree.leaves(ref.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_pallas_backend_noise_magnitude():
    """With σ>0 the pallas noise source differs from jax.random but its
    statistics must match N(0, (σS)²) on the uploaded deltas."""
    from repro.fleet.engine import FleetConfig
    from repro.fleet.stages import upload_pipeline
    zeros = {"w": jnp.zeros((4, 4096))}
    k2s = jax.random.split(jax.random.PRNGKey(0), 4)
    sigma, clip_s = 0.5, 2.0
    cfg = FleetConfig(sigma=sigma, clip_s=clip_s, sparsify_ratio=1.0,
                      backend="pallas")
    out = upload_pipeline(cfg, zeros, zeros, k2s)[0]["w"]
    stds = np.asarray(out).std(axis=1)
    np.testing.assert_allclose(stds, sigma * clip_s, rtol=0.1)
    # node-distinct seeds => node-distinct noise
    assert not np.allclose(np.asarray(out[0]), np.asarray(out[1]))
