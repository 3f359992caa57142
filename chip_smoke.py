#!/usr/bin/env python3
"""Bring-up smoke test: the fleet's main path on a TPU, kernels compiled.

    python chip_smoke.py            # one chip: paper_aldpfl, score_witness,
                                    #   sfl_parity, fleet_1000
    python chip_smoke.py --mesh 4   # four chips: FleetMesh vs one device

Every phase declares an `api.ExperimentSpec`, lowers it with
`api.compile_plan` and drains the record stepper that `api.run` drains,
timing the first record (compilation included) apart from the steady
ones.  Each phase prints one JSON line; the last line of stdout is
``{"ok": ..., "device": {"platform", "kind", "count"}}``.

The script runs in one process and starts none.  It exits non-zero,
printing no result line, when JAX finds no TPU: it never falls back to the
CPU.  The kernels' interpret mode follows the platform
(`repro.kernels.interpret_mode`), so on the TPU every Pallas call of the
``backend="pallas"`` path compiles; each phase line shows the resolved
flag and whether the phase's kernels lower to a ``tpu_custom_call``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
import warnings

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

# Every spec, population and random input is made from this seed; the
# accuracy floor below was read from CPU runs at this seed.
SEED = 0
# Accuracy floor of `paper_aldpfl` after its 16 records.  On XLA:CPU the
# same spec ends at 0.9972 with backend="reference" and at 0.9909 with the
# interpreted kernels; both hold 0.98 or more from the 5th record on.
PAPER_ACC_FLOOR = 0.98
# tests/test_upload_fused.py: pallas vs reference backend at sigma=0
PARITY_PARAM_ATOL = 1e-5
PARITY_ACC_ATOL = 2e-3
# fused upload vs its jnp mirror with noise on: tests/test_upload_fused.py
# holds them to 1e-6 on the CPU; the chip's kernel and XLA evaluate log/cos
# their own way, and a wrong noise stream would be off by O(sigma*S) = 5e-2
NOISE_ATOL = 1e-5
# Cohort sizes at which the engines score uploads: 3 and 250 nodes per chip
# on a 4-chip mesh (the paper fleet padded to 12, and 1,000 nodes), the
# paper fleet's async bucket (10) and the bucket floor (16), a 1,000-node
# sync round.
WITNESS_COHORTS = (3, 10, 16, 250, 1000)
# The --mesh comparison checks that the node-sharded programs compute the
# single-device function, so both sides run their matmuls in f32, as the
# CPU tests do.
MESH_PRECISION = "highest"
# Per-node detection scores, mesh vs one chip.  Sound readings on v5e,
# 1,000 nodes: FleetMesh over one device vs the single-device engine, max
# 0.004 and mean 1.2e-05; four chips of 250 nodes with the argmax-free
# accuracy, max 0.004 and mean 1.6e-05.  The faulty 4-chip run (argmax at
# 250 nodes per chip): max 0.162, mean 0.044.
SCORE_MAX_ATOL = 0.01
SCORE_MEAN_ATOL = 0.001


def place_compile_cache() -> str:
    """Where JAX keeps its persistent compile cache for this run.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing is changed.  Otherwise the cache goes to ``<repo>/.jax_cache``:
    a fixed path, because the path is part of the cache key."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------

def paper_spec(**fleet):
    """The paper's ALDPFL setting (§6.1, `configs/paper_cnn.py`): the CNN
    on 28x28x1 images, 10 edge nodes of which 3 flip label 1 -> 7, MNIST's
    60k training / 10k test sizes, B=128, async Eq. (6) mixing with
    alpha=0.5, ALDP sigma=0.05, DGC keep-ratio 0.1, Alg. 2 detection with
    s=80, and the bit-packed sparse wire codec (so the nnz kernel runs).
    ``fleet`` overrides `FleetSpec` fields."""
    from repro import api
    from repro.configs.paper_cnn import config
    pc = config()
    fleet_kw = dict(
        n_nodes=pc.n_nodes, model="cnn", hw=pc.hw,
        samples_per_node=60000 // pc.n_nodes, n_test=10000,
        n_cloud_test=500,
        attack=api.AttackMix(malicious_frac=pc.n_malicious / pc.n_nodes,
                             flip_src=pc.flip_src, flip_dst=pc.flip_dst))
    fleet_kw.update(fleet)
    return api.ExperimentSpec(
        fleet=api.FleetSpec(**fleet_kw),
        schedule=api.SchedulePolicy(kind="async", alpha=pc.alpha),
        privacy=api.PrivacySpec(sigma=0.05),
        compression=api.CompressionSpec(sparsify_ratio=0.1),
        defense=api.DefenseSpec(detect=True, detect_s=pc.detect_s),
        network=api.NetworkSpec(codec="sparse_bitpack"),
        topology=api.Topology(kind="single", backend="pallas"),
        train=api.TrainSpec(local_steps=10, batch_size=pc.batch_size,
                            lr=0.1),
        rounds=16, seed=SEED)


def fleet_1000_spec():
    """One sync ALDPFL round of the paper CNN over 1,000 nodes of 60
    samples each (the cohort kernels at C=1000)."""
    from repro import api
    spec = paper_spec(n_nodes=1000, samples_per_node=60)
    return dataclasses.replace(
        spec, schedule=api.SchedulePolicy(kind="sync", alpha=0.5), rounds=2)


def with_topology(spec, **kw):
    from repro import api
    return dataclasses.replace(
        spec, topology=dataclasses.replace(spec.topology, **kw))


# ---------------------------------------------------------------------------
# driving one spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Drive:
    first_s: float
    steady_s: list
    records: list
    params: object
    snapshots: list         # host copy of the params after each record
    engine: object
    verdicts: list          # per record: its detect.verdict audit tags


def drive(spec, population=None, audit: bool = False) -> Drive:
    """`api.run`'s path (compile_plan -> materialize -> init_state ->
    make_stepper -> step ...), with a host clock around each record that
    ends on the device (`block_until_ready` on the params).  The host copy
    of each record's params is taken outside the clock.  ``audit`` keeps
    the engine's ``detect.verdict`` events (per-node score, threshold,
    verdict) in memory, grouped by record."""
    import jax
    from repro import api, obs
    sink = obs.MemorySink()
    scope = (obs.use_tracer(obs.Tracer(sinks=[sink])) if audit
             else contextlib.nullcontext())
    with scope:
        plan = api.compile_plan(spec)
        pop = population if population is not None else api.materialize(spec)
        state = api.init_state(plan, pop)
        stepper = api.make_stepper(plan, pop, state)
        times, snapshots, verdicts = [], [], []
        while not stepper.done:
            seen = len(sink.events)
            t0 = time.perf_counter()
            stepper.step()
            jax.block_until_ready(state.params)
            times.append(time.perf_counter() - t0)
            snapshots.append(jax.device_get(state.params))
            verdicts.append([e.tags for e in sink.events[seen:]
                             if e.name == "detect.verdict"])
        stepper.finalize()
    return Drive(times[0], times[1:], list(state.history), state.params,
                 snapshots, stepper.eng, verdicts)


def params_finite(params) -> bool:
    import jax
    import numpy as np
    return all(bool(np.isfinite(np.asarray(x)).all())
               for x in jax.tree.leaves(params))


def max_param_diff(a, b) -> float:
    import jax
    import numpy as np
    return max(float(np.abs(np.asarray(x, np.float64)
                            - np.asarray(y, np.float64)).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def kernel_checks(spec) -> dict:
    """The phase's cohort kernels at its shapes (C nodes x the CNN's P).

    ``tpu_custom_call``: the fused upload and the window fold lower to a
    Mosaic kernel, not to the interpreter's loop (the engines compile the
    same calls inside their round/window programs).  ``noise_max_diff``:
    the fused upload with ALDP noise on, against its jnp mirror
    `upload_fused_reference` run through XLA on the same device; the
    sparsify outputs and counts must match exactly, and the noised upload
    to within the mirror's float tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.upload_fused import (upload_fused_fleet,
                                            upload_fused_reference)
    from repro.kernels.window_fold import window_fold_fleet
    from repro.models.cnn import cnn_flat_layout
    p, bounds = cnn_flat_layout(spec.fleet.hw)
    c = spec.fleet.n_nodes
    sigma, clip_s = 0.05, spec.privacy.clip_s
    up_fn = jax.jit(lambda f, r, t, s, sc: upload_fused_fleet(
        f, r, t, s, sc, sigma, clip_s, boundaries=bounds, need_nnz=True))
    kf, kr, kt, ks = jax.random.split(jax.random.PRNGKey(SEED + 1), 4)
    args = (jax.random.normal(kf, (c, p)) * 0.01,
            jax.random.normal(kr, (c, p)) * 0.01,
            jax.random.uniform(kt, (c, len(bounds)), minval=0.005,
                               maxval=0.03),
            jnp.arange(c, dtype=jnp.int32) * 7 + 1,
            jax.random.uniform(ks, (c,), minval=0.2, maxval=1.0))
    fold = jax.jit(window_fold_fleet).lower(
        args[0][0], args[0], jnp.ones((c,), jnp.int32), args[4], args[4])
    lowered = all("tpu_custom_call" in x.as_text()
                  for x in (up_fn.lower(*args), fold))
    got = up_fn(*args)
    want = jax.jit(lambda *a: upload_fused_reference(
        *a, sigma, clip_s, boundaries=bounds, need_nnz=True))(*args)
    exact = all(np.array_equal(np.asarray(g), np.asarray(w))
                for g, w in zip(got[1:], want[1:]))
    diff = float(np.abs(np.asarray(got[0]) - np.asarray(want[0])).max())
    return {"tpu_custom_call": lowered, "sparsify_exact": exact,
            "noise_max_diff": diff,
            "ok": lowered and exact and diff <= NOISE_ATOL}


def phase_line(name: str, ok: bool, **fields) -> dict:
    from repro.kernels import interpret_mode
    line = {"phase": name, "ok": bool(ok), "interpret": interpret_mode(),
            **fields}
    print(json.dumps(line), flush=True)
    return line


def timing(d: Drive) -> dict:
    return {"first_s": d.first_s, "steady_s": d.steady_s,
            "records": len(d.records),
            "acc": [r.accuracy for r in d.records],
            "rejected": [r.n_rejected for r in d.records]}


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def phase_paper_aldpfl() -> dict:
    spec = paper_spec()
    d = drive(spec)
    finite = params_finite(d.params)
    acc = d.records[-1].accuracy
    kern = kernel_checks(spec)
    return phase_line("paper_aldpfl",
                      finite and acc >= PAPER_ACC_FLOOR and kern["ok"],
                      **timing(d), acc_floor=PAPER_ACC_FLOOR,
                      params_finite=finite, kernels=kern)


def phase_score_witness() -> dict:
    """Alg. 2's detection scores as the engines compute them
    (`stages.rebuild_and_evaluate`: the model's accuracy vmapped over the
    cohort) against an independent witness: each node's logits from a
    per-node `lax.map`, classified by numpy's argmax on the host.  Cohorts
    of perturbed paper CNNs on the paper fleet's 500 cloud-test images, at
    each of WITNESS_COHORTS; every node within one image of its witness
    (vmapped and per-node logits may round apart on a near-tie)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import api
    from repro.fleet.stages import rebuild_and_evaluate
    from repro.models.cnn import cnn_forward
    pop = api.materialize(paper_spec())
    cx, cy = (jnp.asarray(a) for a in pop.cloud_test)
    score = jax.jit(lambda start, d: rebuild_and_evaluate(
        pop.acc_fn, start, d, cx, cy)[1])
    logits = jax.jit(lambda start, d: jax.lax.map(
        lambda dd: cnn_forward(jax.tree.map(jnp.add, start, dd), cx), d))
    leaves, treedef = jax.tree.flatten(pop.params)
    n = len(cy)
    cohorts, ok = {}, True
    for c in WITNESS_COHORTS:
        keys = jax.random.split(jax.random.PRNGKey(SEED + c), len(leaves))
        deltas = jax.tree.unflatten(treedef, [
            0.1 * jax.random.normal(k, (c,) + l.shape)
            for k, l in zip(keys, leaves)])
        got = np.rint(np.asarray(score(pop.params, deltas), np.float64) * n)
        hits = (np.asarray(logits(pop.params, deltas)).argmax(-1)
                == np.asarray(cy)).sum(-1)
        diff = np.abs(got - hits)        # in images
        ok &= bool(diff.max() <= 1)
        cohorts[c] = {"images_off_max": int(diff.max()),
                      "images_off_mean": float(diff.mean()),
                      "nodes_exact_share": float((diff == 0).mean()),
                      "score_mean": float(hits.mean() / n)}
    return phase_line("score_witness", ok, cloud_test=n, cohorts=cohorts)


def compare(base: Drive, other: Drive) -> dict:
    """Record-by-record closeness of two runs of one spec, held to the
    tolerances of tests/test_upload_fused.py and tests/test_fleet_shard.py:
    params within PARITY_PARAM_ATOL, accuracy within PARITY_ACC_ATOL, the
    same rejection counts."""
    dp = [max_param_diff(a, b)
          for a, b in zip(base.snapshots, other.snapshots)]
    dacc = [abs(a.accuracy - b.accuracy)
            for a, b in zip(base.records, other.records)]
    rej = [[r.n_rejected for r in d.records] for d in (base, other)]
    ok = (len(base.records) == len(other.records)
          and max(dp) <= PARITY_PARAM_ATOL and max(dacc) <= PARITY_ACC_ATOL
          and rej[0] == rej[1] and params_finite(other.params))
    return {"ok": ok, "param_diff": dp, "acc_diff": dacc,
            "same_rejections": rej[0] == rej[1],
            "base_acc": [r.accuracy for r in base.records],
            "base_rejected": rej[0]}


def phase_sfl_parity() -> dict:
    """Same population, sync, sigma=0, DGC 0.1, two rounds: the compiled
    kernels against the pure-jnp reference backend."""
    from repro import api
    spec = dataclasses.replace(
        paper_spec(), schedule=api.SchedulePolicy(kind="sync", alpha=0.5),
        privacy=api.PrivacySpec(sigma=0.0), rounds=2)
    pop = api.materialize(spec)
    ref = drive(with_topology(spec, backend="reference"), pop)
    pal = drive(with_topology(spec, backend="pallas"), pop)
    cmp = compare(ref, pal)
    return phase_line("sfl_parity", cmp.pop("ok"), **timing(pal),
                      reference_first_s=ref.first_s, **cmp)


def phase_fleet_1000() -> dict:
    spec = fleet_1000_spec()
    d = drive(spec)
    finite = params_finite(d.params)
    kern = kernel_checks(spec)
    return phase_line("fleet_1000",
                      finite and len(d.records) == spec.rounds and kern["ok"],
                      **timing(d), n_nodes=spec.fleet.n_nodes,
                      params_finite=finite, kernels=kern)


# ---------------------------------------------------------------------------
# the four-chip comparison
# ---------------------------------------------------------------------------

def shard_placement(engine, n_devices: int) -> dict:
    """Does each device hold its own block of the node-sharded state?
    Reads the `addressable_shards` of every residual leaf."""
    import jax
    leaves = jax.tree.leaves(engine.state.residuals)
    devices, rows, starts = set(), set(), []
    for leaf in leaves:
        shards = leaf.addressable_shards
        devices |= {s.device.id for s in shards}
        rows |= {s.data.shape[0] for s in shards}
        starts.append(sorted(s.index[0].start or 0 for s in shards))
    n_pad = leaves[0].shape[0]
    ok = (len(devices) == n_devices and rows == {n_pad // n_devices}
          and all(len(set(s)) == n_devices for s in starts))
    return {"ok": ok, "devices": sorted(devices), "rows_per_device":
            sorted(rows), "n_pad": n_pad}


def mesh_pair(spec, n_devices: int):
    """`spec` on one chip and on a FleetMesh over `n_devices` chips, same
    population, both with f32 matmuls (MESH_PRECISION) and the detection
    audit kept.  Returns (single, mesh, placement)."""
    import jax
    from repro import api
    pop = api.materialize(spec)
    with jax.default_matmul_precision(MESH_PRECISION):
        single = drive(with_topology(spec, kind="single"), pop, audit=True)
        mesh = drive(with_topology(spec, kind="mesh", devices=n_devices),
                     pop, audit=True)
    return single, mesh, shard_placement(mesh.engine, n_devices)


def verdict_check(base: Drive, other: Drive) -> dict:
    """The two runs' detection audits, record by record: the same nodes
    scored in the same order, per-node scores within SCORE_MAX_ATOL (and
    SCORE_MEAN_ATOL on average), thresholds within SCORE_MAX_ATOL, the
    same Alg. 2 verdicts.  At the first verdict that differs the runs
    start folding different uploads, so the audit is checked up to that
    evaluation and no further.  That flip must be a tie: the node's score
    within SCORE_MAX_ATOL of the threshold in both runs."""
    import numpy as np
    records, first_flip = [], None
    for k, (va, vb) in enumerate(zip(base.verdicts, other.verdicts)):
        if not va or [t["node"] for t in va] != [t["node"] for t in vb]:
            records.append({"record": k + 1, "same_nodes": False})
            break
        flip = next((i for i, (a, b) in enumerate(zip(va, vb))
                     if a["rejected"] != b["rejected"]), None)
        if flip is not None:        # async: this window, not later ones
            when = "window" if "window" in va[flip] else "round"
            last = max(i for i, t in enumerate(va)
                       if t[when] == va[flip][when])
            va, vb = va[:last + 1], vb[:last + 1]
        score = np.abs([a["accuracy"] - b["accuracy"]
                        for a, b in zip(va, vb)])
        thr = np.abs([a["threshold"] - b["threshold"]
                      for a, b in zip(va, vb)])
        records.append({"record": k + 1, "same_nodes": True,
                        "score_diff_max": float(score.max()),
                        "score_diff_mean": float(score.mean()),
                        "threshold_diff_max": float(thr.max())})
        if flip is not None:
            a, b = base.verdicts[k][flip], other.verdicts[k][flip]
            first_flip = {
                "record": k + 1, "node": a["node"],
                "score": [a["accuracy"], b["accuracy"]],
                "threshold": [a["threshold"], b["threshold"]],
                "tie": all(abs(t["accuracy"] - t["threshold"])
                           <= SCORE_MAX_ATOL for t in (a, b))}
            break
    ok = (bool(records) and all(
        r["same_nodes"] and r["score_diff_max"] <= SCORE_MAX_ATOL
        and r["score_diff_mean"] <= SCORE_MEAN_ATOL
        and r["threshold_diff_max"] <= SCORE_MAX_ATOL for r in records)
        and (first_flip is None or first_flip["tie"]))
    same = len(records) - (first_flip is not None)
    return {"ok": ok, "same_verdict_records": same, "records": records,
            "first_flip": first_flip}


def hold(base: Drive, other: Drive) -> dict:
    """`compare` plus the audit (`verdict_check`): every record before
    the first differing Alg. 2 verdict is held to the fleet_shard
    tolerances; later records are reported."""
    cmp = compare(base, other)
    whole = cmp.pop("ok")
    ver = verdict_check(base, other)
    n = ver["same_verdict_records"]
    held = (ver.pop("ok") and params_finite(other.params)
            and all(x <= PARITY_PARAM_ATOL for x in cmp["param_diff"][:n])
            and all(x <= PARITY_ACC_ATOL for x in cmp["acc_diff"][:n]))
    return {"ok": held, "held_records": n, "all_records_close": whole,
            **cmp, "verdicts": ver}


def phase_mesh_paper(n_devices: int) -> dict:
    """The paper spec, 16 records, held record by record while the two
    runs make the same Alg. 2 decisions (`hold`), and both past the
    floor."""
    single, mesh, place = mesh_pair(paper_spec(), n_devices)
    res = hold(single, mesh)
    finals = [single.records[-1].accuracy, mesh.records[-1].accuracy]
    ok = res.pop("ok") and place["ok"] and min(finals) >= PAPER_ACC_FLOOR
    return phase_line("mesh_paper_aldpfl", ok, **timing(mesh),
                      single_first_s=single.first_s,
                      single_steady_s=single.steady_s, **res,
                      acc_floor=PAPER_ACC_FLOOR, placement=place)


def phase_mesh_fleet(n_devices: int) -> dict:
    """The fleet_1000 spec's two sync rounds, held as the paper spec is.
    The first round with detection and DGC off (`continuous`) is held to
    PARITY_PARAM_ATOL whatever the verdicts: local SGD, clip + noise and
    the sharded aggregation alone."""
    from repro import api
    spec = fleet_1000_spec()
    single, mesh, place = mesh_pair(spec, n_devices)
    res = hold(single, mesh)
    smooth = dataclasses.replace(
        spec, defense=dataclasses.replace(spec.defense, detect=False),
        compression=api.CompressionSpec(sparsify_ratio=1.0), rounds=1)
    single_c, mesh_c, place_c = mesh_pair(smooth, n_devices)
    cont = {"param_diff": max_param_diff(single_c.params, mesh_c.params),
            "acc_diff": abs(single_c.records[0].accuracy
                            - mesh_c.records[0].accuracy)}
    cont_ok = (cont["param_diff"] <= PARITY_PARAM_ATOL
               and cont["acc_diff"] <= PARITY_ACC_ATOL)
    ok = res.pop("ok") and place["ok"] and place_c["ok"] and cont_ok
    return phase_line(
        "mesh_fleet_1000", ok, **timing(mesh), n_nodes=spec.fleet.n_nodes,
        single_first_s=single.first_s, single_steady_s=single.steady_s,
        **res,
        continuous={"ok": cont_ok, "first_s": mesh_c.first_s,
                    "single_first_s": single_c.first_s, **cont},
        placement=place)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="run only the FleetMesh-vs-one-device comparison "
                         "on N chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is {platform!r}); "
              f"this smoke test runs only on a TPU", file=sys.stderr)
        return 1
    need = args.mesh or 1
    if len(devices) < need:
        print(f"chip_smoke: --mesh {args.mesh} needs {need} TPU chips, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 1
    cache = place_compile_cache()     # before anything is compiled
    sys.path.insert(0, SRC)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always", DeprecationWarning)
        try:
            import repro.api  # noqa: F401  (imports warn inside the catch)
        except ImportError as e:
            print(f"chip_smoke: cannot import the repro package from "
                  f"{SRC}: {e}", file=sys.stderr)
            return 1
        if args.mesh:
            lines = [phase_mesh_paper(args.mesh),
                     phase_mesh_fleet(args.mesh)]
        else:
            lines = [phase_paper_aldpfl(), phase_score_witness(),
                     phase_sfl_parity(), phase_fleet_1000()]
    deprecations = [f"{w.filename}:{w.lineno}: {w.message}" for w in caught
                    if issubclass(w.category, DeprecationWarning)]
    cache_files = (sum(len(f) for _, _, f in os.walk(cache))
                   if os.path.isdir(cache) else 0)
    print(json.dumps({"phase": "run", "ok": not deprecations,
                      "deprecation_warnings": deprecations,
                      "compile_cache": cache,
                      "compile_cache_files": cache_files}), flush=True)
    ok = all(l["ok"] for l in lines) and not deprecations
    print(json.dumps({"ok": ok, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
