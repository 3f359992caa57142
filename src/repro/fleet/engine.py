"""FleetEngine: cohort-batched federated rounds — one dispatch per round.

The sequential reference loop dispatches each node separately per round, so
wall-clock at fleet scale is dominated by Python dispatch, not math. The
engine stacks the whole cohort along a leading node axis and runs

  local SGD -> delta -> [DGC sparsify] -> [ALDP clip+noise]
            -> cloud detection (Alg. 2) -> masked aggregate -> Eq. (6) mix

as a single jitted program per round: `jax.vmap` over nodes of a
`lax.scan`-ed local-SGD body, with cohort gather/scatter of the stacked
residual state folded into the same program.

Pluggable pieces:
  * client sampling — `FullParticipation`, `UniformSampler` (paper's
    "m of K nodes"), `AvailabilityTrace` (availability/churn traces);
  * per-node compute/bandwidth via `NodeProfile` (replaces the seed
    implementation's scalar `node_time` array);
  * upload-pipeline backend — "reference" (pure-jnp `accumulator`/`aldp`,
    bit-compatible with the sequential reference loop) or "pallas" (the
    node-batched fused upload kernel, `kernels.upload_fused`).

With `key_mode="sequential"` the engine reproduces the sequential reference
loop's per-node PRNG chain exactly (see `state.chain_node_keys`), which is
how the api's single-device sync path stays numerically faithful to the seed
implementation.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import async_update, detection
from ..obs import (WINDOW_SIZE_EDGES, get_tracer, host_span,
                   timed_stage)
from . import mesh as mesh_lib
from . import stages
from .mesh import FleetMesh, MeshStateIO
from .stages import detect_masked  # noqa: F401  (public re-export)
from .state import (FleetState, chain_node_keys, gather_nodes,
                    init_fleet_state, pad_keys, parallel_node_keys)


# ---------------------------------------------------------------------------
# client sampling
# ---------------------------------------------------------------------------

class ClientSampler:
    """Selects each round's cohort.

    `cohort(round_idx, n_nodes)` returns (idx (C,), valid (C,)) with a
    *static* C so every round reuses one compiled program; padded slots are
    marked invalid and contribute nothing (their residual writes are
    dropped, their accuracies are excluded from detection).
    """

    def cohort(self, round_idx: int, n_nodes: int
               ) -> Tuple[np.ndarray, np.ndarray]:
        raise NotImplementedError


class FullParticipation(ClientSampler):
    """Every node, every round (the paper's synchronous barrier)."""

    def cohort(self, round_idx, n_nodes):
        return np.arange(n_nodes), np.ones(n_nodes, bool)


class UniformSampler(ClientSampler):
    """Uniform-C sampling without replacement (FedAvg's 'm of K' cohorts)."""

    def __init__(self, cohort_size: int, seed: int = 0):
        self.cohort_size = int(cohort_size)
        self.rng = np.random.default_rng(seed)

    def cohort(self, round_idx, n_nodes):
        c = min(self.cohort_size, n_nodes)
        idx = self.rng.choice(n_nodes, size=c, replace=False)
        return idx, np.ones(c, bool)


class AvailabilityTrace(ClientSampler):
    """Availability/churn model: node k answers round r with prob p_k (or
    per an explicit (rounds, N) boolean trace). Unavailable slots are padded
    so the compiled cohort size stays N."""

    def __init__(self, probs: Optional[np.ndarray] = None,
                 trace: Optional[np.ndarray] = None, seed: int = 0):
        if (probs is None) == (trace is None):
            raise ValueError("give exactly one of probs= or trace=")
        self.probs = None if probs is None else np.asarray(probs, np.float64)
        self.trace = None if trace is None else np.asarray(trace, bool)
        self.rng = np.random.default_rng(seed)

    def cohort(self, round_idx, n_nodes):
        src = self.trace if self.trace is not None else self.probs
        width = src.shape[-1]
        if width < n_nodes:
            raise ValueError(
                f"availability {'trace' if self.trace is not None else 'probs'}"
                f" covers {width} nodes but the fleet has {n_nodes}")
        if self.trace is not None:
            up = self.trace[round_idx % len(self.trace)][:n_nodes]
        else:
            up = self.rng.random(n_nodes) < self.probs[:n_nodes]
        if not up.any():              # never let a round starve entirely
            up = up.copy()
            up[self.rng.integers(n_nodes)] = True
        return np.arange(n_nodes), up


# ---------------------------------------------------------------------------
# per-node system model
# ---------------------------------------------------------------------------

@dataclass
class NodeProfile:
    """Per-node compute time and uplink bandwidth (replaces the trainer's
    scalar `node_time` array with an explicit, extensible system model)."""
    compute_s: np.ndarray          # (N,) seconds per local round
    bandwidth_bps: np.ndarray      # (N,) uplink bytes/s

    @classmethod
    def lognormal(cls, n_nodes: int, base_compute_s: float,
                  heterogeneity: float, bandwidth_bps: float,
                  seed: int = 0, straggler_frac: float = 0.0,
                  straggler_slowdown: float = 10.0) -> "NodeProfile":
        """The trainer's lognormal speed model + optional straggler tail."""
        rng = np.random.default_rng(seed)
        comp = base_compute_s * np.exp(rng.normal(0.0, heterogeneity, n_nodes))
        n_strag = int(round(straggler_frac * n_nodes))
        if n_strag:
            comp[rng.choice(n_nodes, n_strag, replace=False)] *= \
                straggler_slowdown
        bw = np.full(n_nodes, float(bandwidth_bps))
        return cls(compute_s=comp, bandwidth_bps=bw)

    def round_times(self, idx: np.ndarray, valid: np.ndarray,
                    bytes_per_node: float) -> Tuple[float, float]:
        """(comp, comm) for a synchronous cohort round: the barrier waits on
        the slowest participant; uplinks run in parallel."""
        sel = idx[valid]
        if sel.size == 0:
            return 0.0, 0.0
        comp = float(self.compute_s[sel].max())
        comm = float((bytes_per_node / self.bandwidth_bps[sel]).max())
        return comp, comm


# ---------------------------------------------------------------------------
# config + records
# ---------------------------------------------------------------------------

@dataclass
class FleetConfig:
    local_steps: int = 10
    batch_size: int = 64
    lr: float = 0.05
    alpha: float = 0.5              # Eq. (6)
    clip_s: float = 1.0
    sigma: float = 0.0              # noise multiplier (0 disables ALDP)
    detect: bool = True
    detect_s: float = 80.0
    sparsify_ratio: float = 1.0
    key_mode: str = "parallel"      # parallel | sequential (seed-loop parity)
    backend: str = "reference"      # reference (jnp) | pallas (fused kernels)
    seed: int = 0
    # trust-scored defense (api.DefenseSpec.kind="trust_weighted"): verdict-
    # EWMA trust per node, trust/uncertainty-weighted aggregation
    defense_kind: str = "percentile"   # percentile | trust_weighted
    trust_eta: float = 0.25
    trust_floor: float = 0.05
    uncertainty_scale: float = 4.0

    @property
    def trust_on(self) -> bool:
        return self.detect and self.defense_kind == "trust_weighted"


@dataclass
class FleetRoundRecord:
    t: float                        # simulated wall clock
    round: int
    accuracy: float                 # global model on the test set
    comm_bytes: float               # total cohort upload bytes
    comp_time: float
    comm_time: float
    n_participating: int
    n_rejected: int                 # participants rejected by detection


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

class FleetEngine(MeshStateIO):
    """Cohort-batched synchronous FEL over a stacked node fleet.

    Args:
      init_params: global model pytree ω_0.
      loss_fn: (params, batch{x,y}) -> (loss, aux).
      acc_fn: (params, x, y) -> scalar accuracy.
      node_data: per-node (x, y) shards (list) or a prebuilt `FleetData`.
      test_data: (x, y) for global accuracy; cloud_test: detection set (§5.4).
      cfg: `FleetConfig`.
      profile: `NodeProfile` (defaults to a homogeneous 1 s / 100 Mbit fleet).
      sampler: `ClientSampler` (defaults to `FullParticipation`).
      mesh: optional `FleetMesh` — shard the node axis across its devices
        and run the round under `shard_map` (node-parallel local SGD /
        upload pipeline per shard, detection + aggregation over collectives).
        The node axis is padded to a shard multiple; padding rows never
        participate. Sequential-chain PRNG parity with the single-device
        engine holds for arange-style cohorts (`FullParticipation`,
        `AvailabilityTrace`): the sharded round consumes one chain split per
        node in node order, exactly like an arange cohort. `UniformSampler`
        cohorts still run correctly but consume the chain in node order
        instead of cohort order.
    """

    def __init__(self, init_params, loss_fn: Callable, acc_fn: Callable,
                 node_data, test_data, cloud_test, cfg: FleetConfig,
                 profile: Optional[NodeProfile] = None,
                 sampler: Optional[ClientSampler] = None,
                 mesh: Optional[FleetMesh] = None,
                 net=None, tracer=None, attack=None):
        self.cfg = cfg
        # per-round events/metrics go to the injected tracer, else whatever
        # global one `api.run` scoped in (disabled -> all no-ops); the jitted
        # round already returns accs/mask/thr, so tracing needs no program
        # change and cannot perturb numerics
        self.obs = tracer if tracer is not None else get_tracer()
        self.params = init_params
        self.loss_fn = loss_fn
        # the test-set pass's scope; inside the round program, which also
        # scores the uploads with it, those ops count to the cloud score
        self.acc_fn = jax.jit(stages.scoped(stages.EVALUATE, acc_fn))
        (self.data, self.n_nodes, self.test_data, self.cloud_test,
         self.profile, self.n_params) = stages.init_engine_common(
            init_params, node_data, test_data, cloud_test, profile)
        self.sampler = sampler or FullParticipation()
        self.mesh = mesh
        self.net = net          # Optional[repro.net.NetSim]: wire codecs +
                                # link sim replace the analytic comm model
        self.attack = attack    # Optional[stages.AttackPlan]: adversary zoo
        self.n_pad = mesh.padded(self.n_nodes) if mesh else self.n_nodes
        self.state = init_fleet_state(
            init_params, self.n_pad, jax.random.PRNGKey(cfg.seed),
            trust=cfg.trust_on,
            throttle=attack is not None and attack.needs_throttle)
        self.history: List[FleetRoundRecord] = []
        # barrier-clock origin: run_round continues from the last record's
        # t, or from here when the history is empty (repro.sim sets this on
        # checkpoint restore so the resumed clock doesn't restart at zero)
        self._t0 = 0.0
        if mesh is not None:
            self.data = mesh.put_nodes(self.data.pad_to(self.n_pad))
            self.state = dataclasses.replace(
                self.state, residuals=mesh.put_nodes(self.state.residuals),
                chain_key=mesh.put_replicated(self.state.chain_key),
                trust=(mesh.put_nodes(self.state.trust)
                       if self.state.trust is not None else None),
                throttle=(mesh.put_nodes(self.state.throttle)
                          if self.state.throttle is not None else None))
            self.params = mesh.put_replicated(self.params)
            self._round_fn = jax.jit(self._build_round_sharded())
        else:
            self._round_fn = jax.jit(self._build_round())

    # -- per-node upload bytes (wire format: values, or values+indices) -----
    def bytes_per_node(self) -> float:
        return stages.bytes_per_node(self.n_params, self.cfg.sparsify_ratio)

    # -- the single-dispatch round ------------------------------------------
    def _build_round(self):
        cfg = self.cfg
        raw_acc_fn = self.acc_fn
        cloud_x, cloud_y = self.cloud_test
        local_train = stages.make_local_train(self.loss_fn, cfg.local_steps,
                                              cfg.lr, cfg.batch_size)
        need_nnz = self.net is not None     # byte-accurate pricing only
        attack_stage = stages.make_delta_attack(self.attack)
        mal_full = (self.attack.mask(self.n_pad)
                    if attack_stage is not None else None)

        def round_fn(params, residuals, chain_key, trust, throttle,
                     x, y, sizes, idx, valid):
            c = idx.shape[0]
            with jax.named_scope(stages.LOCAL_SGD):
                xg = jnp.take(x, idx, axis=0)
                yg = jnp.take(y, idx, axis=0)
                sz = jnp.take(sizes, idx, axis=0)
            with jax.named_scope(stages.UPLOAD):
                res_c = gather_nodes(residuals, idx)

            with jax.named_scope(stages.LOCAL_SGD):
                if cfg.key_mode == "sequential":
                    chain_key, k1s, k2s = chain_node_keys(chain_key, c)
                else:
                    chain_key, k1s, k2s = parallel_node_keys(chain_key, c)

                local = jax.vmap(local_train, in_axes=(None, 0, 0, 0, 0))(
                    params, xg, yg, sz, k1s)
                deltas = jax.tree.map(
                    lambda l, g: l - g[None].astype(l.dtype), local, params)
            with jax.named_scope(stages.UPLOAD):
                if attack_stage is not None:
                    mal_c = jnp.take(mal_full, idx)
                    thr_c = (jnp.take(throttle, idx)
                             if throttle is not None else None)
                    deltas = attack_stage(deltas, mal_c, thr_c)
                deltas, res_c, nnz = stages.upload_pipeline(
                    cfg, deltas, res_c, k2s, need_nnz=need_nnz)

            # cloud side: rebuild node models, test, detect, aggregate, mix
            with jax.named_scope(stages.CLOUD_SCORE):
                omegas, accs = stages.rebuild_and_evaluate(
                    raw_acc_fn, params, deltas, cloud_x, cloud_y)
                if cfg.detect:
                    mask, thr = detect_masked(accs, valid, cfg.detect_s)
                else:
                    mask, thr = valid, jnp.zeros((), jnp.float32)
            with jax.named_scope(stages.FOLD):
                if trust is not None:
                    trust_c = jnp.take(trust, idx)
                    w = detection.trust_weights(
                        trust_c, accs, mask, cfg.trust_floor,
                        cfg.uncertainty_scale)
                    omega_mean = detection.masked_weighted_mean(omegas,
                                                                mask, w)
                else:
                    omega_mean = detection.masked_mean(omegas, mask)
                new_params = async_update.mix(params, omega_mean, cfg.alpha)

            # write cohort residuals back; padded slots scatter out of bounds
            # and are dropped
            drop_idx = jnp.where(valid, idx, self.n_nodes)
            with jax.named_scope(stages.UPLOAD):
                residuals = jax.tree.map(
                    lambda full, part: full.at[drop_idx].set(part,
                                                             mode="drop"),
                    residuals, res_c)
            with jax.named_scope(stages.FOLD):
                if trust is not None:
                    trust_c = detection.trust_update(
                        jnp.take(trust, idx), mask, valid, cfg.trust_eta)
                    trust = trust.at[drop_idx].set(trust_c, mode="drop")
                if throttle is not None:
                    thr_new = stages.adaptive_throttle_update(
                        jnp.take(throttle, idx), valid & ~mask, valid,
                        self.attack.adapt_poison_scale)
                    throttle = throttle.at[drop_idx].set(thr_new,
                                                         mode="drop")
            m = {"accs": accs, "mask": mask, "thr": thr}
            if need_nnz:
                m["nnz"] = nnz
            return new_params, residuals, chain_key, trust, throttle, m

        return round_fn

    # -- the sharded round: one shard_map over the node mesh ----------------
    def _build_round_sharded(self):
        """The round as a `shard_map` program over the node mesh.

        Each device trains its shard of nodes (local SGD -> DGC -> ALDP ->
        cloud eval) with no communication; detection needs the global
        accuracy set, so the (n_pad,) accuracies are `all_gather`-ed and
        thresholded replicated; the masked-mean aggregate is a per-shard
        partial sum + `psum`. Cohorts arrive as a per-node participation
        mask instead of an index list — gather/scatter of cohort rows
        across shards is thereby avoided entirely for the synchronous
        barrier (every padded slot simply trains and is masked out).
        """
        cfg = self.cfg
        mesh = self.mesh
        raw_acc_fn = self.acc_fn
        cloud_x, cloud_y = self.cloud_test
        local_train = stages.make_local_train(self.loss_fn, cfg.local_steps,
                                              cfg.lr, cfg.batch_size)
        n, n_pad, d, axis = self.n_nodes, self.n_pad, mesh.n_devices, mesh.axis
        need_nnz = self.net is not None     # byte-accurate pricing only
        attack_stage = stages.make_delta_attack(self.attack)
        mal_full = (self.attack.mask(n_pad)
                    if attack_stage is not None else None)

        def round_body(params, residuals, chain_key, trust, throttle,
                       x, y, sizes, valid, cx, cy):
            # local leaves: residuals/x/y/sizes/valid lead with B = n_pad/d
            # keys are derived over the *true* node count then padded, so
            # both modes yield the exact per-node streams the single-device
            # engine draws for an arange cohort (padding rows reuse the last
            # real key for their masked-out dummy updates)
            with jax.named_scope(stages.LOCAL_SGD):
                if cfg.key_mode == "sequential":
                    chain_key, k1s, k2s = chain_node_keys(chain_key, n)
                else:
                    chain_key, k1s, k2s = parallel_node_keys(chain_key, n)
                k1s, k2s = pad_keys(k1s, n_pad), pad_keys(k2s, n_pad)
                k1 = mesh_lib.my_block(k1s, axis, d)
                k2 = mesh_lib.my_block(k2s, axis, d)

                local = jax.vmap(local_train, in_axes=(None, 0, 0, 0, 0))(
                    params, x, y, sizes, k1)
                deltas = jax.tree.map(
                    lambda l, g: l - g[None].astype(l.dtype), local, params)
            with jax.named_scope(stages.UPLOAD):
                if attack_stage is not None:
                    # the attack stage is shard-oblivious: per-node row
                    # scaling on this device's block of the (replicated)
                    # malicious mask
                    mal_blk = mesh_lib.my_block(mal_full, axis, d)
                    deltas = attack_stage(deltas, mal_blk, throttle)
                deltas, res_new, nnz = stages.upload_pipeline(
                    cfg, deltas, residuals, k2, need_nnz=need_nnz)
            with jax.named_scope(stages.CLOUD_SCORE):
                omegas, accs = stages.rebuild_and_evaluate(
                    raw_acc_fn, params, deltas, cx, cy)

                # cloud side, replicated: global accuracy set -> Alg. 2 mask
                accs_all = jax.lax.all_gather(accs, axis, tiled=True)
                valid_all = jax.lax.all_gather(valid, axis, tiled=True)
                if cfg.detect:
                    mask_all, thr = detect_masked(accs_all, valid_all,
                                                  cfg.detect_s)
                else:
                    mask_all, thr = valid_all, jnp.zeros((), jnp.float32)
                mask = mesh_lib.my_block(mask_all, axis, d)

            with jax.named_scope(stages.FOLD):
                if trust is not None:
                    # trust/uncertainty weights against the globally-reduced
                    # accepted-mean accuracy (every shard shares the anchor)
                    m_all = mask_all.astype(jnp.float32)
                    ref = ((accs_all.astype(jnp.float32) * m_all).sum()
                           / jnp.maximum(m_all.sum(), 1.0))
                    w = mask.astype(jnp.float32) * detection.trust_weights(
                        trust, accs, mask, cfg.trust_floor,
                        cfg.uncertainty_scale, ref=ref)
                    total = jax.lax.psum(w.sum(), axis)
                    denom = jnp.where(total > 0, total, 1.0)
                else:
                    # masked mean: per-shard weighted partial sums + psum
                    w = mask.astype(jnp.float32)
                    denom = jnp.maximum(jax.lax.psum(w.sum(), axis), 1.0)

                def agg(o):
                    wf = w.reshape((-1,) + (1,) * (o.ndim - 1))
                    return jax.lax.psum((o.astype(jnp.float32) * wf).sum(0),
                                        axis) / denom

                omega_mean = jax.tree.map(agg, omegas)
                new_params = async_update.mix(params, omega_mean, cfg.alpha)

            # participants' residuals advance; everyone else's stay put
            with jax.named_scope(stages.UPLOAD):
                residuals = jax.tree.map(
                    lambda old, new: jnp.where(
                        valid.reshape((-1,) + (1,) * (old.ndim - 1)), new,
                        old),
                    residuals, res_new)
            with jax.named_scope(stages.FOLD):
                if trust is not None:
                    trust = detection.trust_update(trust, mask, valid,
                                                   cfg.trust_eta)
                if throttle is not None:
                    throttle = stages.adaptive_throttle_update(
                        throttle, valid & ~mask, valid,
                        self.attack.adapt_poison_scale)
            m = {"accs": accs_all, "mask": mask_all, "thr": thr}
            if need_nnz:
                m["nnz"] = jax.lax.all_gather(nnz, axis, tiled=True)
            return new_params, residuals, chain_key, trust, throttle, m

        pn, pr = mesh.spec_nodes(), mesh.spec_replicated()
        m_specs = {"accs": pr, "mask": pr, "thr": pr}
        if need_nnz:
            m_specs["nnz"] = pr
        return mesh.shard_map(
            round_body,
            in_specs=(pr, pn, pr, pn, pn, pn, pn, pn, pn, pr, pr),
            out_specs=(pr, pn, pr, pn, pn, m_specs))

    # -- host-side driver ---------------------------------------------------
    def run_round(self, on_record: Optional[Callable[
            [FleetRoundRecord], None]] = None) -> FleetRoundRecord:
        """One barrier round.  ``on_record`` is called with the new record
        before the round's span closes, so host work a caller does per
        record (the api steppers' privacy accountant) nests in it."""
        tr = self.obs
        r = self.state.round
        t_prev = self.history[-1].t if self.history else self._t0
        with host_span(tr, "round", round=r) as span:
            rec = self._round(tr, r, t_prev)
            if on_record is not None:
                on_record(rec)
            span.set(n_participating=rec.n_participating,
                     n_rejected=rec.n_rejected)
            span.set_virtual(t_prev, rec.t)
        return rec

    def _round(self, tr, r: int, t_prev: float) -> FleetRoundRecord:
        idx, valid = self.sampler.cohort(r, self.n_nodes)
        with timed_stage(tr, "round.device", round=r) as st:
            if self.mesh is not None:
                up = self._participation_mask(idx, valid)
                (self.params, residuals, chain_key, trust, throttle,
                 m) = self._round_fn(
                    self.params, self.state.residuals, self.state.chain_key,
                    self.state.trust, self.state.throttle,
                    self.data.x, self.data.y, self.data.sizes,
                    self.mesh.put_nodes(jnp.asarray(up)), *self.cloud_test)
            else:
                (self.params, residuals, chain_key, trust, throttle,
                 m) = self._round_fn(
                    self.params, self.state.residuals, self.state.chain_key,
                    self.state.trust, self.state.throttle,
                    self.data.x, self.data.y, self.data.sizes,
                    jnp.asarray(idx, jnp.int32), jnp.asarray(valid))
            st.fence((self.params, m))
        self.state = FleetState(residuals=residuals, chain_key=chain_key,
                                round=r + 1, trust=trust, throttle=throttle)

        with timed_stage(tr, "round.readback", round=r):
            mask = np.asarray(m["mask"])
            nnz = np.asarray(m["nnz"]) if self.net is not None else None
        n_part = int(valid.sum())
        if self.mesh is not None:   # sharded mask is per-node over n_pad
            n_rejected = int((up & ~mask).sum())
        else:
            n_rejected = int((np.asarray(valid) & ~mask).sum())
        if self.net is not None:
            # byte-accurate path: the round's measured nonzero counts price
            # each participant's upload through the wire codec; the link
            # model's per-upload transfer times replace the analytic uplink
            # (parallel uploads — the barrier waits on the slowest)
            if self.mesh is not None:       # nnz is per-node over n_pad
                sel_nodes = np.flatnonzero(up[:self.n_nodes])
                nnz_sel = nnz[sel_nodes]
            else:                           # nnz is in cohort (idx) order
                valid_np = np.asarray(valid)
                sel_nodes = np.asarray(idx)[valid_np]
                nnz_sel = nnz[valid_np]
            flood = self.attack.flood_uploads if self.attack else 0
            with timed_stage(tr, "net.draw", round=r):
                draw = self.net.draw(sel_nodes, extra_concurrency=flood)
            with timed_stage(tr, "net.commit", round=r):
                enc = self.net.commit(draw, nnz_sel, ctx={"round": r})
        with timed_stage(tr, "round.evaluate", round=r):
            accuracy = self.global_accuracy()
        with timed_stage(tr, "round.account", round=r):
            bpn = self.bytes_per_node()
            comp, comm = self.profile.round_times(np.asarray(idx),
                                                  np.asarray(valid), bpn)
            comm_bytes = bpn * n_part
            if self.net is not None:
                comm = float(draw.transfer_s.max()) if sel_nodes.size else 0.0
                comm_bytes = float(enc.sum())
            rec = FleetRoundRecord(
                t=t_prev + comp + comm, round=r,
                accuracy=accuracy, comm_bytes=comm_bytes,
                comp_time=comp, comm_time=comm, n_participating=n_part,
                n_rejected=n_rejected)
            self.history.append(rec)
        if tr.enabled:
            self._emit_round_events(rec, idx, valid, m, up if self.mesh
                                    is not None else None)
        return rec

    def _emit_round_events(self, rec: FleetRoundRecord, idx, valid, m,
                           up) -> None:
        """Per-participant detection audit (one `detect.verdict` instant per
        cloud evaluation, Alg. 2's batch top-s form) + round metrics — the
        trace alone reconstructs Fig. 6's per-round rejection series."""
        tr = self.obs
        thr = float(np.asarray(m["thr"]))
        accs = np.asarray(m["accs"])
        mask = np.asarray(m["mask"])
        if up is not None:          # sharded: node-order arrays over n_pad
            nodes = np.flatnonzero(up[:self.n_nodes])
            accs, mask = accs[nodes], mask[nodes]
        else:                       # single-device: cohort (idx) order
            valid_np = np.asarray(valid)
            nodes = np.asarray(idx)[valid_np]
            accs, mask = accs[valid_np], mask[valid_np]
        for i, node in enumerate(nodes):
            tr.instant("detect.verdict", virt_t=rec.t, node=int(node),
                       round=rec.round, accuracy=float(accs[i]),
                       threshold=thr, rejected=bool(~mask[i]),
                       detect=bool(self.cfg.detect))
        mx = tr.metrics
        if self.cfg.detect and nodes.size and detection.detect_fell_back(
                accs, thr):
            # the all-equal guard accepted everyone — the exact state a
            # detection-aware attacker forces; auditable from the trace
            mx.counter("detect.fallback").inc()
        mx.histogram("round.size", WINDOW_SIZE_EDGES).observe(
            rec.n_participating)
        mx.counter("round.participants").inc(rec.n_participating)
        mx.counter("local_sgd.onehot_selects").inc(
            rec.n_participating * self.cfg.local_steps)
        mx.counter("round.rejected").inc(rec.n_rejected)
        mx.counter("round.comm_bytes").inc(rec.comm_bytes)
        mx.gauge("model.accuracy").set(rec.accuracy)

    def run(self, rounds: int) -> List[FleetRoundRecord]:
        for _ in range(rounds):
            self.run_round()
        return self.history

    def global_accuracy(self) -> float:
        return float(self.acc_fn(self.params, *self.test_data))

    def kappa(self) -> float:
        """Eq. (5) over the whole run."""
        comm = sum(r.comm_time for r in self.history)
        comp = sum(r.comp_time for r in self.history)
        return async_update.communication_efficiency(comm, comp)
