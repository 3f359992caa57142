"""Pallas kernel micro-benchmarks.  Run on the CPU, the kernels run in the
Pallas interpreter, so every time here is an interpreter time, not a
device time; the derived column reports the HBM bytes the fused kernel
would save on a TPU, computed from the shapes.

Timing goes through `repro.obs.bench_kernel` (warmup + `block_until_ready`
fenced loop).  With ``--profile [events.jsonl]`` the module installs an
enabled tracer first, so every measurement also lands in the shared obs
stream as a ``kernel.<name>`` counter + ``kernel.us_per_call`` histogram
sample — the measurement harness the upload-pipeline megakernel work will
argue from.
"""
from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp

from .common import append_trajectory, emit

from repro.obs import JsonlSink, MemorySink, Tracer, bench_kernel, use_tracer
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ldp_noise import ldp_perturb_flat
from repro.kernels.sparsify import sparsify_flat

FUSED_RESULTS_PATH = os.path.join(os.path.dirname(__file__), "..", "results",
                                  "kernels_fused.json")


def bench_upload_pipeline():
    """The upload-pipeline megakernel vs the unfused pallas kernel chain
    (`sparsify_fleet` -> `nnz_fleet` -> `ldp_perturb_fleet`) at identical
    cohort shapes, seeds and thresholds — bit-identical outputs, so the
    delta is pure launch/HBM-traffic overhead.  Returns the records
    appended to ``results/kernels_fused.json``."""
    from repro.core import accumulator as accum
    from repro.kernels.ldp_noise import ldp_perturb_fleet
    from repro.kernels.sparsify import sparsify_fleet
    from repro.kernels.upload_fused import (spread_thresholds,
                                            upload_fused_fleet)
    from repro.kernels.wire_bytes import nnz_fleet
    from repro.kernels.window_fold import (window_fold_fleet,
                                           window_fold_reference)

    key = jax.random.PRNGKey(0)
    C, N = 8, 1 << 16
    sigma, clip_s, ratio = 0.1, 1.0, 0.25
    flat = jax.random.normal(key, (C, N), jnp.float32)
    res = jax.random.normal(jax.random.PRNGKey(1), (C, N), jnp.float32)
    comb = flat + res
    thr = jax.vmap(lambda v: accum.leaf_threshold(v, ratio))(comb)[:, None]
    seeds = jnp.arange(C, dtype=jnp.int32)
    sp = jnp.where(jnp.abs(comb) >= spread_thresholds(thr, (0,), N),
                   comb, 0.0)
    scales = 1.0 / jnp.maximum(1.0, jnp.sqrt(jnp.sum(jnp.square(sp), 1))
                               / clip_s)

    def fused():
        return upload_fused_fleet(flat, res, thr, seeds, scales, sigma,
                                  clip_s, need_nnz=True)

    def unfused():
        up, newr = sparsify_fleet(flat, res, thr[:, 0])
        nnz = nnz_fleet(up)
        up = ldp_perturb_fleet(up, seeds, scales, sigma, clip_s)
        return up, newr, nnz

    us_fused = bench_kernel("upload_fused_512K", fused)
    us_chain = bench_kernel("upload_unfused_chain_512K", unfused)
    # HBM accounting (f32): fused reads {delta, residual} and writes
    # {upload, residual'} once — 16·C·N; the chain re-reads/re-writes the
    # intermediate upload through nnz (4·C·N) and ldp (8·C·N) — 28·C·N.
    hbm_fused, hbm_chain = 16 * C * N, 28 * C * N
    emit("kernel_upload_fused_512K", us_fused,
         f"unfused_chain_us={us_chain:.1f};"
         f"speedup={us_chain / us_fused:.2f}x;"
         f"hbm_bytes_fused={hbm_fused};hbm_bytes_chain={hbm_chain};"
         f"hbm_bytes_saved={hbm_chain - hbm_fused}")

    W = 16
    p = jax.random.normal(key, (N,), jnp.float32)
    om = jax.random.normal(jax.random.PRNGKey(2), (W, N), jnp.float32)
    gates = jnp.ones((W,), jnp.int32)
    a = jnp.full((W,), 0.5, jnp.float32)
    b = 1.0 - a
    us_fold = bench_kernel("window_fold_16x64K",
                           lambda: window_fold_fleet(p, om, gates, a, b))
    us_scan = bench_kernel("window_fold_scan_16x64K",
                           lambda: window_fold_reference(p, om, gates, a, b))
    # the lax.scan carry round-trips HBM every arrival (read+write carry +
    # read om + write snapshot = 4·W·N); the kernel keeps the accumulator
    # block VMEM-resident (read om + write snapshot + params in/out =
    # (2W+2)·N).
    hbm_fold, hbm_scan = 4 * (2 * W + 2) * N, 4 * 4 * W * N
    emit("kernel_window_fold_16x64K", us_fold,
         f"scan_us={us_scan:.1f};hbm_bytes_fused={hbm_fold};"
         f"hbm_bytes_scan={hbm_scan};hbm_bytes_saved={hbm_scan - hbm_fold}")

    stamp = time.strftime("%Y-%m-%dT%H:%M:%S")
    records = [
        {"ts": stamp, "bench": "upload_fused", "cohort": C, "n": N,
         "fused_us": us_fused, "unfused_chain_us": us_chain,
         "speedup": us_chain / us_fused, "hbm_bytes_fused": hbm_fused,
         "hbm_bytes_chain": hbm_chain,
         "hbm_bytes_saved": hbm_chain - hbm_fused},
        {"ts": stamp, "bench": "window_fold", "window": W, "n": N,
         "fused_us": us_fold, "scan_us": us_scan,
         "hbm_bytes_fused": hbm_fold, "hbm_bytes_scan": hbm_scan,
         "hbm_bytes_saved": hbm_scan - hbm_fold},
    ]
    append_trajectory(FUSED_RESULTS_PATH, records)
    return records


def run() -> None:
    key = jax.random.PRNGKey(0)
    B, H, KV, S, D = 1, 4, 2, 256, 64
    q = jax.random.normal(key, (B, H, S, D), jnp.float32)
    k = jax.random.normal(key, (B, KV, S, D), jnp.float32)
    v = jax.random.normal(key, (B, KV, S, D), jnp.float32)
    us = bench_kernel("flash_attention_256",
                      lambda a, b, c: flash_attention(a, b, c, bq=128,
                                                      bk=128), q, k, v)
    flops = 4 * B * H * S * S * D * 0.5
    emit("kernel_flash_attention_256", us, f"flops={flops:.0f};"
         f"vmem_tile=128x128x{D}")

    n = 1 << 20
    g = jax.random.normal(key, (n,), jnp.float32)
    us = bench_kernel("ldp_noise_1M",
                      lambda x: ldp_perturb_flat(x, jnp.int32(1),
                                                 jnp.float32(0.5), 0.1, 1.0),
                      g)
    emit("kernel_ldp_noise_1M", us,
         f"hbm_bytes_fused={2*4*n};hbm_bytes_naive={6*4*n}")

    r = jax.random.normal(key, (n,), jnp.float32)
    us = bench_kernel("sparsify_1M",
                      lambda a, b: sparsify_flat(a, b, jnp.float32(0.5)),
                      g, r)
    emit("kernel_sparsify_1M", us,
         f"hbm_bytes_fused={4*4*n};hbm_bytes_naive={8*4*n}")

    from repro.kernels.selective_scan import selective_scan
    B_, L_, D_, N_ = 1, 128, 64, 16
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (B_, L_, D_), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B_, L_, D_))) * 0.1
    Bm = jax.random.normal(ks[2], (B_, L_, N_))
    Cm = jax.random.normal(ks[3], (B_, L_, N_))
    A = -jnp.exp(jax.random.normal(key, (D_, N_)) * 0.2)
    us = bench_kernel("selective_scan",
                      lambda *a: selective_scan(*a, block_l=64,
                                                block_d=64)[0],
                      x, dt, Bm, Cm, A)
    hbm_fused = 4 * (2 * B_ * L_ * D_ + 2 * B_ * L_ * N_ + B_ * L_ * D_)
    hbm_xla = hbm_fused + 4 * B_ * L_ * D_ * N_ * 7   # h_all × assoc-scan passes
    emit("kernel_selective_scan", us,
         f"hbm_bytes_fused={hbm_fused};hbm_bytes_xla_scan={hbm_xla}")

    from repro.kernels.ssd_scan import ssd_scan
    H_, P_ = 8, 16
    xh = jax.random.normal(ks[0], (1, 128, H_, P_), jnp.float32)
    dth = jax.nn.softplus(jax.random.normal(ks[1], (1, 128, H_))) * 0.2
    Ah = -jnp.exp(jax.random.normal(key, (H_,)) * 0.3)
    Bh = jax.random.normal(ks[2], (1, 128, N_))
    Ch = jax.random.normal(ks[3], (1, 128, N_))
    us = bench_kernel("ssd_scan",
                      lambda *a: ssd_scan(*a, chunk=64, block_h=8)[0],
                      xh, dth, Bh, Ch, Ah)
    emit("kernel_ssd_scan", us,
         f"hbm_bytes_fused={4*(2*128*H_*P_+2*128*N_+128*H_)};"
         f"vmem_state={H_*P_*N_*4}")

    bench_upload_pipeline()


def main(argv) -> None:
    if "--profile" in argv:
        i = argv.index("--profile")
        path = argv[i + 1] if len(argv) > i + 1 else None
        sinks = [JsonlSink(path)] if path else [MemorySink()]
        tracer = Tracer(sinks, enabled=True)
        with use_tracer(tracer):
            run()
        snap = tracer.metrics.snapshot()
        h = snap.get("kernel.us_per_call")
        if h:
            emit("kernel_profile_summary", h["sum"] / max(h["count"], 1),
                 f"n={h['count']};min_us={h['min']:.1f};max_us={h['max']:.1f}")
        tracer.close()
    else:
        run()


if __name__ == "__main__":
    main(sys.argv[1:])
