"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import LANE, block_layout, interpret_mode
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ldp_noise import _finalize_uniform, ldp_perturb_flat
from repro.kernels.ops import (aldp_perturb_pallas, attention_pallas,
                               sparsify_pallas)
from repro.kernels.ref import (flash_attention_ref, ldp_perturb_flat_ref,
                               selective_scan_ref, sparsify_flat_ref,
                               ssd_scan_ref)
from repro.kernels.selective_scan import selective_scan
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.sparsify import sparsify_flat
from repro.core.aldp import aldp_perturb, clip_by_global_norm
from repro.core.accumulator import accumulate_and_sparsify, init_residual

TOLS = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("B,H,KV,Sq,Sk,D", [
    (1, 4, 2, 64, 64, 32),
    (2, 2, 2, 33, 47, 16),      # ragged, needs padding
    (1, 4, 1, 128, 128, 64),    # MQA
    (1, 8, 8, 96, 96, 32),      # MHA
    (2, 6, 2, 40, 72, 8),       # small head dim
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_shapes(B, H, KV, Sq, Sk, D, causal):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, Sq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, KV, Sk, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, KV, Sk, D), jnp.float32)
    o = flash_attention(q, k, v, causal=causal, bq=32, bk=32)
    o_ref = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [8, 24, 64])
def test_flash_attention_sliding_window(window):
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 2, 96, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 96, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 96, 32), jnp.float32)
    o = flash_attention(q, k, v, causal=True, window=window, bq=32, bk=32)
    o_ref = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 4, 64, 32)).astype(dtype)
    k = jax.random.normal(ks[1], (1, 2, 64, 32)).astype(dtype)
    v = jax.random.normal(ks[2], (1, 2, 64, 32)).astype(dtype)
    o = flash_attention(q, k, v, bq=32, bk=32)
    o_ref = flash_attention_ref(q, k, v)
    assert o.dtype == dtype
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_attention_pallas_model_layout():
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, 48, 4, 16))
    k = jax.random.normal(ks[1], (2, 48, 2, 16))
    v = jax.random.normal(ks[2], (2, 48, 2, 16))
    o = attention_pallas(q, k, v, causal=True)
    from repro.models.attention import attention as jnp_attention
    o_ref = jnp_attention(q, k, v, causal=True, chunk=16)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# ldp_noise kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [100, 1024, 4097, 300000])
def test_ldp_kernel_deterministic_path(n):
    g = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32)
    out = ldp_perturb_flat(g, jnp.int32(3), jnp.float32(0.25), 0.0, 1.0)
    ref = ldp_perturb_flat_ref(g, jnp.float32(0.25), None, 0.0, 1.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_ldp_kernel_noise_statistics():
    out = ldp_perturb_flat(jnp.zeros(500000), jnp.int32(11), jnp.float32(1.0),
                           0.3, 2.0)
    x = np.asarray(out)
    assert abs(x.mean()) < 5e-3
    assert abs(x.std() - 0.6) < 5e-3
    kurt = ((x - x.mean()) ** 4).mean() / x.std() ** 4
    assert abs(kurt - 3.0) < 0.1          # gaussianity
    out2 = ldp_perturb_flat(jnp.zeros(500000), jnp.int32(12), jnp.float32(1.0),
                            0.3, 2.0)
    assert abs(float(np.corrcoef(x, np.asarray(out2))[0, 1])) < 0.01


def test_ldp_noise_stream_independent_of_block_clamp():
    """`block_layout` clamps block_rows to the rows a vector needs; a vector
    that fits one block keeps its in-block indices, so its noise is the
    prefix of a full 256-row block's noise."""
    full = ldp_perturb_flat(jnp.zeros(256 * LANE), jnp.int32(9),
                            jnp.float32(1.0), 0.3, 1.0)
    for n in (100, 20490, 70000):
        out = ldp_perturb_flat(jnp.zeros(n), jnp.int32(9), jnp.float32(1.0),
                               0.3, 1.0)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(full)[:n])


def test_uniform_cast_through_int32_is_exact():
    """The TPU has no u32 -> f32 convert; the 24-bit value goes through
    int32 instead, which must give the direct cast's values bit for bit."""
    x = jnp.concatenate([jnp.arange(1 << 16, dtype=jnp.uint32),
                         jax.random.bits(jax.random.PRNGKey(0), (1 << 16,),
                                         jnp.uint32),
                         jnp.array([0xFFFFFFFF], jnp.uint32)])
    got = _finalize_uniform(x)
    y = x ^ (x >> 16)
    y = y * jnp.uint32(0x7FEB352D)
    y = y ^ (y >> 15)
    y = y * jnp.uint32(0x846CA68B)
    y = y ^ (y >> 16)
    want = (y >> 8).astype(jnp.float32) / jnp.float32(1 << 24)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert float(got.max()) < 1.0


@pytest.mark.parametrize("n,block_rows,want", [
    (20490, 256, (21, 24, 1)),      # paper CNN: one 24-row block
    (100, 256, (1, 8, 1)),
    (300 * LANE, 256, (300, 256, 2)),
    (4097, 2, (5, 2, 3)),           # below the clamp: unchanged
])
def test_block_layout_clamps_to_the_vector(n, block_rows, want):
    assert block_layout(n, block_rows) == want


def test_ldp_ops_matches_core_clipping():
    key = jax.random.PRNGKey(4)
    tree = {"a": jax.random.normal(key, (64, 32)) * 5,
            "b": jax.random.normal(key, (100,))}
    pk, nrm_k = aldp_perturb_pallas(tree, jnp.int32(0), sigma=0.0, clip_s=0.7)
    pc, nrm_c = clip_by_global_norm(tree, 0.7)
    assert float(abs(nrm_k - nrm_c)) < 1e-3
    for a, b in zip(jax.tree.leaves(pk), jax.tree.leaves(pc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


# ---------------------------------------------------------------------------
# sparsify kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [64, 1025, 50000])
@pytest.mark.parametrize("thr", [0.0, 0.5, 2.0])
def test_sparsify_kernel_exact(n, thr):
    key = jax.random.PRNGKey(5)
    g = jax.random.normal(key, (n,), jnp.float32)
    r = jax.random.normal(jax.random.PRNGKey(6), (n,), jnp.float32)
    up, nr = sparsify_flat(g, r, jnp.float32(thr))
    upr, nrr = sparsify_flat_ref(g, r, jnp.float32(thr))
    np.testing.assert_allclose(np.asarray(up), np.asarray(upr), atol=1e-7)
    np.testing.assert_allclose(np.asarray(nr), np.asarray(nrr), atol=1e-7)


# ---------------------------------------------------------------------------
# selective_scan kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,L,D,N,bl,bd", [
    (2, 32, 16, 4, 8, 8),
    (1, 50, 24, 8, 16, 16),     # ragged L/D, needs padding
    (2, 64, 64, 16, 32, 32),
    (1, 33, 8, 16, 64, 64),     # blocks larger than dims
])
def test_selective_scan_vs_ref(B, L, D, N, bl, bd):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (B, L, D), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, D))) * 0.1
    Bm = jax.random.normal(ks[2], (B, L, N))
    Cm = jax.random.normal(ks[3], (B, L, N))
    A = -jnp.exp(jax.random.normal(key, (D, N)) * 0.2)
    y, h = selective_scan(x, dt, Bm, Cm, A, block_l=bl, block_d=bd)
    yr, hr = selective_scan_ref(x, dt, Bm, Cm, A)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=1e-4, atol=1e-4)


def test_selective_scan_matches_model_ssm():
    """Kernel math == the model's chunked mamba1 recurrence (pre-gating)."""
    from repro.models.ssm import _m1_scan_chunk
    key = jax.random.PRNGKey(1)
    B, L, D, N = 1, 16, 8, 4
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (B, L, D), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, D))) * 0.1
    Bm = jax.random.normal(ks[2], (B, L, N))
    Cm = jax.random.normal(ks[3], (B, L, N))
    A = -jnp.exp(jax.random.normal(key, (D, N)) * 0.2)
    y, h = selective_scan(x, dt, Bm, Cm, A, block_l=8, block_d=8)
    la = dt[..., None] * A
    bx = (dt * x)[..., None] * Bm[:, :, None, :]
    h_all, h_last = _m1_scan_chunk(jnp.zeros((B, D, N)), la, bx)
    y_model = jnp.einsum("bldn,bln->bld", h_all, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_model),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_last),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# ssd_scan kernel (Mamba2)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,L,H,P,N,c,bh", [
    (1, 32, 4, 8, 16, 8, 2),
    (2, 48, 8, 16, 8, 16, 4),
    (1, 50, 6, 8, 32, 64, 8),    # ragged L/H, blocks > dims
])
def test_ssd_scan_vs_ref(B, L, H, P, N, c, bh):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (B, L, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H))) * 0.2
    Bm = jax.random.normal(ks[2], (B, L, N))
    Cm = jax.random.normal(ks[3], (B, L, N))
    A = -jnp.exp(jax.random.normal(key, (H,)) * 0.3)
    y, h = ssd_scan(x, dt, Bm, Cm, A, chunk=c, block_h=bh)
    yr, hr = ssd_scan_ref(x, dt, Bm, Cm, A)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=5e-4, atol=5e-4)


def test_ssd_scan_matches_model_mamba2():
    """Kernel == the model's one-token mamba2 recurrence iterated."""
    from repro.models.ssm import mamba2_fwd
    # compare against the model's chunked path by building equivalent inputs
    key = jax.random.PRNGKey(2)
    B, L, H, P, N = 1, 16, 4, 8, 8
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (B, L, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H))) * 0.2
    Bm = jax.random.normal(ks[2], (B, L, N))
    Cm = jax.random.normal(ks[3], (B, L, N))
    A = -jnp.exp(jax.random.normal(key, (H,)) * 0.3)
    y_k, h_k = ssd_scan(x, dt, Bm, Cm, A, chunk=4, block_h=4)
    y_r, h_r = ssd_scan_ref(x, dt, Bm, Cm, A)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=5e-4, atol=5e-4)


def test_sparsify_ops_matches_accumulator():
    key = jax.random.PRNGKey(7)
    g = {"w": jax.random.normal(key, (400,)), "b": jax.random.normal(key, (30,))}
    r = init_residual(g)
    up_k, r_k = sparsify_pallas(g, r, ratio=0.2)
    up_j, r_j, frac = accumulate_and_sparsify(r, g, 0.2)
    # same keep-fraction and conservation; thresholds computed identically
    kept_k = sum(float((jnp.asarray(u) != 0).sum()) for u in jax.tree.leaves(up_k))
    kept_j = sum(float((jnp.asarray(u) != 0).sum()) for u in jax.tree.leaves(up_j))
    assert abs(kept_k - kept_j) <= 2
    tot_k = jax.tree.map(lambda a, b: a + b, up_k, r_k)
    tot_in = jax.tree.map(lambda a, b: a + b, g, r)
    for x, y in zip(jax.tree.leaves(tot_k), jax.tree.leaves(tot_in)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)


# ---------------------------------------------------------------------------
# interpret mode follows the platform; the chip smoke test needs a chip
# ---------------------------------------------------------------------------

def test_interpret_mode_follows_platform(monkeypatch):
    assert jax.default_backend() == "cpu"
    assert interpret_mode() is True
    assert interpret_mode(False) is False and interpret_mode(True) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        interpret_mode()


def test_chip_smoke_refuses_to_run_without_a_tpu():
    import os
    import subprocess
    import sys
    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(repo, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_chip_smoke_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; without it the cache goes
    to the fixed <repo>/.jax_cache."""
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(repo)
    import chip_smoke as smoke
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert smoke.place_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(repo, ".jax_cache")
        assert smoke.place_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def _audit_drive(verdicts, params=0.0):
    import chip_smoke as smoke
    from repro.api.report import RoundRecord
    recs = [RoundRecord(t=float(k), version=k, accuracy=0.5, comm_bytes=0.0,
                        comp_time=0.0, comm_time=0.0,
                        n_rejected=sum(t["rejected"] for t in v))
            for k, v in enumerate(verdicts)]
    snaps = [{"w": np.full(3, params, np.float32)} for _ in verdicts]
    return smoke.Drive(0.0, [], recs, snaps[-1], snaps, None, verdicts)


def _verdict(window, node, acc, thr):
    return {"window": window, "node": node, "accuracy": acc,
            "threshold": thr, "rejected": not acc > thr}


@pytest.mark.parametrize("case", ["agree", "tie_flip", "wide_flip",
                                  "score_gap", "param_gap"])
def test_chip_smoke_mesh_hold(monkeypatch, case):
    """The --mesh comparison holds scores, thresholds and verdicts up to
    the first flipped verdict (which must be a tie; later windows of that
    record are not compared) and params of every record before it."""
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(repo)
    import chip_smoke as smoke
    rec1 = [_verdict(0, 0, 0.50, 0.40), _verdict(0, 1, 0.30, 0.40)]
    rec2 = [_verdict(1, 0, 0.5504, 0.55), _verdict(2, 1, 0.70, 0.55)]
    base = _audit_drive([rec1, rec2])
    other = [[dict(t) for t in rec1], [dict(t) for t in rec2]]
    # the window after a flip folds different uploads: never compared
    other[1][1]["accuracy"] = 0.1
    other[1][1]["rejected"] = True
    if case == "agree":
        other[1][1] = dict(rec2[1])
    elif case == "tie_flip":
        other[1][0].update(accuracy=0.5499, rejected=True)
    elif case == "wide_flip":
        other[1][0].update(accuracy=0.45, rejected=True)
    elif case == "score_gap":
        other[0][0]["accuracy"] = 0.47
    res = smoke.hold(base, _audit_drive(
        other, params=2e-5 if case == "param_gap" else 0.0))
    want = {"agree": (True, 2), "tie_flip": (True, 1),
            "wide_flip": (False, 1), "score_gap": (False, 1),
            "param_gap": (False, 1)}[case]
    assert (res["ok"], res["held_records"]) == want, res["verdicts"]
