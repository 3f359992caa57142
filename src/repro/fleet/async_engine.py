"""AsyncFleetEngine: the paper's asynchronous scheme, one dispatch per window.

The sequential reference event loop (`api` Topology('sequential')) pops one
heap event
at a time and runs one Python-dispatched node update per arrival — O(K)
dispatches per simulated round, dispatch-bound past a few dozen nodes. This
engine vectorizes the event queue itself: per-node virtual clocks
(`FleetState.next_arrival`), dispatched model versions and the streaming
detection window all live on device, and each step

  1. selects the *arrival window*: every in-flight update landing inside
     [t0, t0 + window) where t0 is the earliest pending arrival;
  2. runs the shared upload pipeline (local SGD from each node's stale
     dispatched params -> DGC sparsify -> ALDP) node-batched via
     `fleet.stages` — one device program for the whole window;
  3. folds the window into the global model:
       * ``mixing="sequential"`` — a `lax.scan` over arrival order applying
         Eq. (6) (`async_update.mix`) or the FedAsync staleness-adaptive
         `mix_stale` per arrival, with the device-side accuracy ring buffer
         (`core.detection.ring_*`) reproducing the event loop's sliding
         `acc_window` detection exactly;
       * ``mixing="buffered"`` — FedBuff-style: detect against the window
         once, then mix the masked mean of accepted arrivals in one Eq. (6)
         step (cheaper, coarser — diverges from the event loop by design);
  4. redispatches each processed node with the model it would have received
     from the cloud (sequential: the global model right after its own
     arrival was handled) and advances its clock by uplink + compute time.

With ``window=None`` (auto) the window length is min node compute time, so
no node processed in a window can re-arrive inside it — arrivals are handled
in exactly the event loop's global time order, and with
``key_mode="sequential"`` + `chain_node_keys_masked` the PRNG chain is
consumed identically. That is the *parity mode* the api's single-device
async path runs in (tested float-close in tests/test_async_fleet.py).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import async_update, detection
from ..obs import (STALENESS_EDGES, WINDOW_SIZE_EDGES, get_tracer,
                   host_span, timed_stage)
from . import mesh as mesh_lib
from . import stages
from .engine import ClientSampler, FleetConfig, NodeProfile
from .mesh import FleetMesh, MeshStateIO
from .state import (FleetState, chain_node_keys_masked, gather_nodes,
                    init_async_fleet_state, parallel_node_keys)


@dataclass
class AsyncFleetConfig(FleetConfig):
    """`FleetConfig` + the asynchronous scheduler knobs."""
    window: Optional[float] = None  # virtual-time window length; None =>
                                    # min node compute time (parity-safe:
                                    # preserves event-loop arrival order)
    mixing: str = "sequential"      # sequential (scan of Eq. 6/mix_stale)
                                    # | buffered (FedBuff-style masked mean)
    staleness_adaptive: bool = False
    staleness_a: float = 0.5        # FedAsync polynomial exponent
    detect_warmup: int = 4          # arrivals observed before detecting
    detect_window: int = 8          # accuracy ring-buffer capacity


@dataclass
class AsyncWindowRecord:
    t: float                        # simulated clock at window end
    window: int                     # window index
    version: int                    # global model version after the window
    accuracy: float                 # global model on the test set
    comm_bytes: float               # total window upload bytes
    comp_time: float                # summed node compute time in the window
    comm_time: float                # summed uplink time in the window
    n_processed: int                # arrivals handled this window
    n_rejected: int                 # arrivals rejected by detection
    max_staleness: int              # max τ = version − dispatched_version


def make_window_folds(cfg: "AsyncFleetConfig", need_audit: bool = False):
    """(sequential_fold, buffered_fold) — the window-to-global-model mixing
    programs, shared between the single-device window and the mesh-sharded
    window (where they run replicated on every device after the in-window
    arrival set has been `all_gather`-ed).

    Both folds return ``(..., audit)``: an empty dict normally (zero extra
    pytree leaves and zero extra ops, so untraced programs stay
    structurally identical), or — with ``need_audit`` (a traced run) — the
    per-slot detection audit: the ring threshold and occupancy each
    arrival was judged against, enough to replay every Alg. 2 verdict from
    the event stream alone.

    With ``cfg.backend == "pallas"`` the sequential fold splits into a
    scalar control scan (ring / staleness / version bookkeeping, emitting
    per-arrival mix gates + coefficients) and the
    `kernels.window_fold.window_fold_fleet` Pallas kernel, which folds the
    param mixing with each param block resident in VMEM across the window
    instead of carrying the whole model through a lax.scan.  Bit-equal for
    f32 params; non-f32 models fall back to the reference scan."""

    def sequential_fold_reference(params, version, ring, count, omegas,
                                  accs, vdisp_c, arrived, trust_c=None):
        """Eq. (6)/mix_stale over arrival order with streaming
        detection — the event loop, as one lax.scan.  With ``trust_c``
        (the cohort's per-node trust scores; defense
        ``kind="trust_weighted"``) each arrival's new-model mixing
        coefficient is scaled by its `detection.trust_weights` weight —
        w ∈ (0, 1], anchored on the sliding window's mean accuracy, so
        low-trust / outlier arrivals take proportionally smaller steps."""
        use_trust = trust_c is not None

        def body(carry, inp):
            params, version, ring, count = carry
            if use_trust:
                omega_i, acc_i, vdisp_i, arr_i, t_i = inp
            else:
                omega_i, acc_i, vdisp_i, arr_i = inp
            r2, c2 = detection.ring_push(ring, count, acc_i)
            ring = jnp.where(arr_i, r2, ring)
            count = jnp.where(arr_i, c2, count)
            if cfg.detect:
                rej = arr_i & detection.ring_detect(
                    ring, count, acc_i, cfg.detect_s, cfg.detect_warmup)
            else:
                rej = jnp.zeros((), bool)
            tau = version - vdisp_i
            if use_trust:
                # uncertainty anchor: mean of the occupied ring slots (the
                # arrival's own accuracy is already pushed, matching the
                # detection semantics)
                occupied = jnp.arange(ring.shape[0]) < count
                held = jnp.minimum(count, ring.shape[0])
                ref = (jnp.where(occupied, ring, 0.0).sum()
                       / jnp.maximum(held, 1).astype(jnp.float32))
                w = detection.trust_weights(
                    t_i, acc_i, arr_i, cfg.trust_floor,
                    cfg.uncertainty_scale, ref=ref)
                if cfg.staleness_adaptive:
                    b = async_update.staleness_alpha(
                        cfg.alpha, tau, cfg.staleness_a) * w
                else:
                    b = jnp.float32(1.0 - cfg.alpha) * w
                mixed = jax.tree.map(
                    lambda p, o: ((1.0 - b) * p.astype(jnp.float32)
                                  + b * o.astype(jnp.float32)),
                    params, omega_i)
            elif cfg.staleness_adaptive:
                mixed = async_update.mix_stale(params, omega_i, cfg.alpha,
                                               tau, cfg.staleness_a)
            else:
                mixed = async_update.mix(params, omega_i, cfg.alpha)
            do_mix = arr_i & ~rej
            params = jax.tree.map(lambda m, p: jnp.where(do_mix, m, p),
                                  mixed, params)
            version = version + do_mix.astype(jnp.int32)
            out = (params, version, rej, tau)
            if need_audit:
                out += (detection.ring_threshold(ring, count, cfg.detect_s),
                        jnp.minimum(count, ring.shape[0]))
            return (params, version, ring, count), out

        xs = (omegas, accs, vdisp_c, arrived)
        if use_trust:
            xs += (trust_c,)
        (params, version, ring, count), ys = \
            jax.lax.scan(body, (params, version, ring, count), xs)
        p_seq, v_seq, rej, taus = ys[:4]
        audit = {"thr": ys[4], "held": ys[5]} if need_audit else {}
        return params, version, ring, count, p_seq, v_seq, rej, taus, audit

    def control_scan(version, ring, count, accs, vdisp_c, arrived):
        """The reference fold's scalar bookkeeping only: ring pushes,
        detection verdicts, staleness and version tracking — emitting, per
        arrival, the gate + (a, b) coefficients of the params mix
        ``gate ? a·params + b·omega : params`` for the param-fold kernel.
        Rejection never depends on params, so the split is exact."""

        def body(carry, inp):
            version, ring, count = carry
            acc_i, vdisp_i, arr_i = inp
            r2, c2 = detection.ring_push(ring, count, acc_i)
            ring = jnp.where(arr_i, r2, ring)
            count = jnp.where(arr_i, c2, count)
            if cfg.detect:
                rej = arr_i & detection.ring_detect(
                    ring, count, acc_i, cfg.detect_s, cfg.detect_warmup)
            else:
                rej = jnp.zeros((), bool)
            tau = version - vdisp_i
            if cfg.staleness_adaptive:
                w = async_update.staleness_alpha(cfg.alpha, tau,
                                                 cfg.staleness_a)
                a_i, b_i = 1.0 - w, w
            else:
                a_i = jnp.float32(cfg.alpha)
                b_i = jnp.float32(1.0 - cfg.alpha)
            do_mix = arr_i & ~rej
            version = version + do_mix.astype(jnp.int32)
            out = (version, rej, tau, do_mix, a_i, b_i)
            if need_audit:
                out += (detection.ring_threshold(ring, count, cfg.detect_s),
                        jnp.minimum(count, ring.shape[0]))
            return (version, ring, count), out

        (version, ring, count), ys = jax.lax.scan(
            body, (version, ring, count), (accs, vdisp_c, arrived))
        v_seq, rej, taus, gates, a, b = ys[:6]
        audit = {"thr": ys[6], "held": ys[7]} if need_audit else {}
        return version, ring, count, v_seq, rej, taus, gates, a, b, audit

    def sequential_fold_pallas(params, version, ring, count, omegas, accs,
                               vdisp_c, arrived, trust_c=None):
        from ..kernels.window_fold import window_fold_fleet

        if trust_c is not None:
            # trust-weighted mixing needs the per-arrival ring mean, which
            # the control/param-fold split doesn't carry — reference scan
            return sequential_fold_reference(params, version, ring, count,
                                             omegas, accs, vdisp_c, arrived,
                                             trust_c)
        if any(l.dtype != jnp.float32 for l in jax.tree.leaves(params)):
            return sequential_fold_reference(params, version, ring, count,
                                             omegas, accs, vdisp_c, arrived)
        version, ring, count, v_seq, rej, taus, gates, a, b, audit = \
            control_scan(version, ring, count, accs, vdisp_c, arrived)
        layout = stages.cohort_layout(omegas)
        final, seq = window_fold_fleet(layout.flatten_one(params),
                                       layout.flatten(omegas), gates, a, b)
        return (layout.unflatten_one(final), version, ring, count,
                layout.unflatten(seq), v_seq, rej, taus, audit)

    sequential_fold = (sequential_fold_pallas if cfg.backend == "pallas"
                       else sequential_fold_reference)

    def buffered_fold(params, version, ring, count, omegas, accs,
                      vdisp_c, arrived, trust_c=None):
        """FedBuff-style: one detection pass over the updated window, one
        masked-mean Eq. (6) mix for the whole buffer.  With
        ``staleness_adaptive`` the buffer mean is staleness-weighted per
        update — (τ+1)^-a FedAsync discounts inside the FedBuff mean
        (uniform weights reproduce the plain masked mean bit-for-bit).
        With ``trust_c`` the buffer mean is additionally trust/uncertainty
        weighted via `detection.trust_weights`."""

        def push(carry, inp):
            ring, count = carry
            acc_i, arr_i = inp
            r2, c2 = detection.ring_push(ring, count, acc_i)
            return (jnp.where(arr_i, r2, ring),
                    jnp.where(arr_i, c2, count)), None

        version0 = version
        (ring, count), _ = jax.lax.scan(push, (ring, count),
                                        (accs, arrived))
        if cfg.detect or need_audit:
            thr = detection.ring_threshold(ring, count, cfg.detect_s)
            held = jnp.minimum(count, ring.shape[0])
        if cfg.detect:
            rej = arrived & (held >= cfg.detect_warmup) & (accs <= thr)
        else:
            rej = jnp.zeros_like(arrived)
        mask = arrived & ~rej
        taus = version0 - vdisp_c         # staleness at mix time
        if trust_c is not None:
            w = detection.trust_weights(trust_c, accs, mask,
                                        cfg.trust_floor,
                                        cfg.uncertainty_scale)
            if cfg.staleness_adaptive:
                w = w * detection.staleness_weights(taus, cfg.staleness_a)
            omega_mean = detection.masked_weighted_mean(omegas, mask, w)
        elif cfg.staleness_adaptive:
            omega_mean = detection.masked_weighted_mean(
                omegas, mask, detection.staleness_weights(taus,
                                                          cfg.staleness_a))
        else:
            omega_mean = detection.masked_mean(omegas, mask)
        mixed = async_update.mix(params, omega_mean, cfg.alpha)
        any_mix = mask.any()
        params = jax.tree.map(lambda m, p: jnp.where(any_mix, m, p),
                              mixed, params)
        version = version + any_mix.astype(jnp.int32)
        # every processed node receives the post-window model/version
        c = vdisp_c.shape[0]
        p_seq = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (c,) + x.shape), params)
        v_seq = jnp.broadcast_to(version, (c,))
        # the whole buffer was judged against one threshold/ring state
        audit = ({"thr": jnp.broadcast_to(thr, (c,)),
                  "held": jnp.broadcast_to(held, (c,))} if need_audit
                 else {})
        return params, version, ring, count, p_seq, v_seq, rej, taus, audit

    return sequential_fold, buffered_fold


class AsyncFleetEngine(MeshStateIO):
    """Event-driven async FEL over a stacked node fleet, batched per window.

    Args mirror `FleetEngine`; `sampler` (optional) models churn: a node
    whose arrival lands in a window while the sampler marks it unavailable
    loses that upload (no mix, no detection entry) but is redispatched —
    mid-flight churn rather than cohort sampling.

    With ``mesh`` (a `FleetMesh`) the node axis of every per-node state
    array is sharded across devices and the window runs under `shard_map`:
    the in-window cohort is gathered out of the shards via collectives,
    its local SGD / upload pipeline is split over the mesh along the cohort
    axis, and the (small) arrival set is `all_gather`-ed so the sequential
    Eq. (6)/`mix_stale` fold and the detection ring run replicated —
    keeping exact parity with the event-loop processing order.

    Sharded-vs-unsharded PRNG parity: exact with ``key_mode="sequential"``
    (the masked chain only advances on in-window slots, so the shard-
    rounded cohort bucket is irrelevant); with ``key_mode="parallel"`` the
    key split count tracks the bucket size, which the mesh rounds up to a
    shard multiple — statistically equivalent, but not stream-identical.
    """

    def __init__(self, init_params, loss_fn: Callable, acc_fn: Callable,
                 node_data, test_data, cloud_test, cfg: AsyncFleetConfig,
                 profile: Optional[NodeProfile] = None,
                 sampler: Optional[ClientSampler] = None,
                 mesh: Optional[FleetMesh] = None,
                 net=None, tracer=None, attack=None):
        self.cfg = cfg
        self.params = init_params
        self.attack = attack    # Optional[stages.AttackPlan]: adversary zoo
        # the obs tracer is bound at construction: whether the jitted
        # window carries detection-audit outputs is decided here, so an
        # untraced engine's program is structurally identical to pre-obs
        self.obs = tracer if tracer is not None else get_tracer()
        self._need_audit = self.obs.enabled
        self.loss_fn = loss_fn
        # the test-set pass's scope; inside the window program, which also
        # scores the uploads with it, those ops count to the cloud score
        self.acc_fn = jax.jit(stages.scoped(stages.EVALUATE, acc_fn))
        (self.data, self.n_nodes, self.test_data, self.cloud_test,
         self.profile, self.n_params) = stages.init_engine_common(
            init_params, node_data, test_data, cloud_test, profile)
        self.sampler = sampler
        self.mesh = mesh
        self.net = net          # Optional[repro.net.NetSim]: per-upload
                                # wire-encoded bytes + stochastic link times
                                # drive the node clocks instead of _comm_s
        self.n_pad = mesh.padded(self.n_nodes) if mesh else self.n_nodes
        self._bpn = stages.bytes_per_node(self.n_params, cfg.sparsify_ratio)
        # per-node uplink + compute, fixed over the run (float64 host copies
        # feed window selection and record accounting; an f32 copy padded to
        # the mesh width feeds the jitted clock update as the per-window
        # uplink-time input when no network simulation is attached)
        self._comm_s = np.asarray(self._bpn / self.profile.bandwidth_bps,
                                  np.float64)
        self._comp_s = np.asarray(self.profile.compute_s, np.float64)
        self._window_len = (cfg.window if cfg.window is not None
                            else float(self._comp_s.min()))
        if self._window_len <= 0:
            raise ValueError(f"window must be positive, got "
                             f"{self._window_len}")
        # padding rows never arrive (+inf clocks) and never participate
        self._comm_pad32 = np.concatenate(
            [self._comm_s, np.zeros(self.n_pad - self.n_nodes)]
        ).astype(np.float32)
        first_arrival = np.concatenate(
            [self._comp_s, np.full(self.n_pad - self.n_nodes, np.inf)])
        self.state = init_async_fleet_state(
            init_params, self.n_pad, jax.random.PRNGKey(cfg.seed),
            first_arrival=first_arrival, detect_window=cfg.detect_window,
            trust=cfg.trust_on,
            throttle=attack is not None and attack.needs_throttle)
        self._window_idx = 0
        self.history: List[AsyncWindowRecord] = []
        if mesh is not None:
            self.data = mesh.put_nodes(self.data.pad_to(self.n_pad))
            self.state = dataclasses.replace(
                self.state,
                residuals=mesh.put_nodes(self.state.residuals),
                dispatched=mesh.put_nodes(self.state.dispatched),
                next_arrival=mesh.put_nodes(self.state.next_arrival),
                dispatched_version=mesh.put_nodes(
                    self.state.dispatched_version),
                chain_key=mesh.put_replicated(self.state.chain_key),
                version=mesh.put_replicated(self.state.version),
                acc_ring=mesh.put_replicated(self.state.acc_ring),
                acc_count=mesh.put_replicated(self.state.acc_count),
                trust=(mesh.put_nodes(self.state.trust)
                       if self.state.trust is not None else None),
                throttle=(mesh.put_nodes(self.state.throttle)
                          if self.state.throttle is not None else None))
            self.params = mesh.put_replicated(self.params)
            self._window_fn = jax.jit(self._build_window_sharded())
        else:
            self._window_fn = jax.jit(self._build_window())

    # -- the single-dispatch arrival window ---------------------------------
    def _build_window(self):
        cfg = self.cfg
        raw_acc_fn = self.acc_fn
        cloud_x, cloud_y = self.cloud_test
        local_train = stages.make_local_train(self.loss_fn, cfg.local_steps,
                                              cfg.lr, cfg.batch_size)
        comp_s = jnp.asarray(self._comp_s, jnp.float32)
        n = self.n_nodes
        need_nnz = self.net is not None     # byte-accurate pricing only
        need_audit = self._need_audit
        sequential_fold, buffered_fold = make_window_folds(cfg, need_audit)
        attack_stage = stages.make_delta_attack(self.attack)
        mal_full = (self.attack.mask(self.n_pad)
                    if attack_stage is not None else None)
        eta, adapt_scale = cfg.trust_eta, (
            self.attack.adapt_poison_scale if self.attack else 1.0)

        def window_fn(params, state: FleetState, x, y, sizes,
                      order, proc, avail, up_s):
            """order: node ids sorted by (arrival time, node id), truncated
            to the compute bucket (in-window arrivals are a prefix of the
            sort, so the host passes the smallest power-of-two cohort
            covering them — one compiled program per bucket size); proc:
            in-window flags (sorted positions); avail: churn mask; up_s:
            per-slot uplink transfer seconds (the fixed analytic per-node
            times, or the network simulator's per-upload draws)."""
            with jax.named_scope(stages.FOLD):
                t_arr = jnp.take(state.next_arrival, order)
                vdisp_c = jnp.take(state.dispatched_version, order)
            with jax.named_scope(stages.LOCAL_SGD):
                disp_c = gather_nodes(state.dispatched, order)
            with jax.named_scope(stages.UPLOAD):
                res_c = gather_nodes(state.residuals, order)
            with jax.named_scope(stages.LOCAL_SGD):
                xg = jnp.take(x, order, axis=0)
                yg = jnp.take(y, order, axis=0)
                sz = jnp.take(sizes, order, axis=0)

                if cfg.key_mode == "sequential":
                    chain_key, k1s, k2s = chain_node_keys_masked(
                        state.chain_key, proc)
                else:
                    chain_key, k1s, k2s = parallel_node_keys(
                        state.chain_key, order.shape[0])

                local = jax.vmap(local_train)(disp_c, xg, yg, sz, k1s)
                deltas = jax.tree.map(lambda l, d: l - d.astype(l.dtype),
                                      local, disp_c)
            with jax.named_scope(stages.UPLOAD):
                if attack_stage is not None:
                    mal_c = jnp.take(mal_full, order)
                    thr_c = (jnp.take(state.throttle, order)
                             if state.throttle is not None else None)
                    deltas = attack_stage(deltas, mal_c, thr_c)
                deltas, res_c, nnz = stages.upload_pipeline(
                    cfg, deltas, res_c, k2s, need_nnz=need_nnz)
            with jax.named_scope(stages.CLOUD_SCORE):
                omegas, accs = stages.rebuild_and_evaluate(
                    raw_acc_fn, disp_c, deltas, cloud_x, cloud_y)

            arrived = proc & avail
            with jax.named_scope(stages.FOLD):
                trust_c = (jnp.take(state.trust, order)
                           if state.trust is not None else None)
                fold = (sequential_fold if cfg.mixing == "sequential"
                        else buffered_fold)
                params, version, ring, count, p_seq, v_seq, rej, taus, aud \
                    = fold(params, state.version, state.acc_ring,
                           state.acc_count, omegas, accs, vdisp_c, arrived,
                           trust_c=trust_c)

            # redispatch: processed nodes get the model right after their
            # own slot (sequential) / the post-window model (buffered), the
            # matching version, and a fresh clock = arrival + uplink + next
            # local compute. Untouched slots scatter out of bounds.
            drop_idx = jnp.where(proc, order, n)
            scatter = lambda full, part: jax.tree.map(
                lambda f, p: f.at[drop_idx].set(p, mode="drop"), full, part)
            with jax.named_scope(stages.FOLD):
                dispatched = scatter(state.dispatched, p_seq)
            with jax.named_scope(stages.UPLOAD):
                residuals = scatter(state.residuals, res_c)
            with jax.named_scope(stages.FOLD):
                dv = state.dispatched_version.at[drop_idx].set(v_seq,
                                                               mode="drop")
                t_next = t_arr + up_s + jnp.take(comp_s, order)
                na = state.next_arrival.at[drop_idx].set(t_next, mode="drop")

                # trust EWMA / adaptive-attacker throttle, from this
                # window's verdicts (only arrived slots were judged;
                # churned slots keep their scores — trust_update's `seen`
                # mask is the identity for them, so the proc-indexed
                # scatter is harmless)
                trust = state.trust
                if trust is not None:
                    t_new = detection.trust_update(trust_c, arrived & ~rej,
                                                   arrived, eta)
                    trust = trust.at[drop_idx].set(t_new, mode="drop")
                throttle = state.throttle
                if throttle is not None:
                    th_new = stages.adaptive_throttle_update(
                        thr_c, rej & arrived, arrived, adapt_scale)
                    throttle = throttle.at[drop_idx].set(th_new,
                                                         mode="drop")

            new_state = dataclasses.replace(
                state, residuals=residuals, chain_key=chain_key,
                dispatched=dispatched, next_arrival=na,
                dispatched_version=dv, version=version, acc_ring=ring,
                acc_count=count, trust=trust, throttle=throttle)
            metrics = {
                "n_rejected": (rej & arrived).sum(),
                "max_staleness": jnp.where(arrived, taus, 0).max(),
            }
            if need_nnz:
                metrics["nnz"] = nnz
            if need_audit:
                metrics["audit"] = dict(aud, accs=accs, rej=rej, taus=taus)
            return params, new_state, metrics

        return window_fn

    # -- the sharded window: one shard_map over the node mesh ---------------
    def _build_window_sharded(self):
        """The arrival window as a `shard_map` program over the node mesh.

        Data flow per window (cohort size C, devices D, node blocks B):
          1. gather the C cohort rows (dispatched params, residuals, clocks,
             data shards) out of the node-sharded fleet arrays — a masked
             `psum` reconstructs them replicated on every device;
          2. each device trains its C/D cohort block (local SGD -> DGC ->
             ALDP -> cloud eval), embarrassingly parallel;
          3. `all_gather` the per-arrival models/accuracies back to cohort
             order and run the sequential Eq. (6)/`mix_stale` fold (or the
             buffered FedBuff mix) replicated — identical on every device,
             so the global model/version/ring need no further collective;
          4. scatter redispatched models, residuals, versions and fresh
             clocks back to whichever device owns each processed node.

        The transient replicated cohort (step 1) is the price of arbitrary
        arrival order; it is bounded by the power-of-two arrival bucket,
        not the fleet size, so per-device memory stays O(N/D + C).
        """
        cfg = self.cfg
        mesh = self.mesh
        raw_acc_fn = self.acc_fn
        local_train = stages.make_local_train(self.loss_fn, cfg.local_steps,
                                              cfg.lr, cfg.batch_size)
        pad = self.n_pad - self.n_nodes
        comp_s = jnp.asarray(np.concatenate([self._comp_s,
                                             np.full(pad, np.inf)]),
                             jnp.float32)
        d, axis = mesh.n_devices, mesh.axis
        b = self.n_pad // d
        need_nnz = self.net is not None     # byte-accurate pricing only
        need_audit = self._need_audit
        sequential_fold, buffered_fold = make_window_folds(cfg, need_audit)
        attack_stage = stages.make_delta_attack(self.attack)
        mal_full = (self.attack.mask(self.n_pad)
                    if attack_stage is not None else None)
        eta, adapt_scale = cfg.trust_eta, (
            self.attack.adapt_poison_scale if self.attack else 1.0)

        def window_body(params, residuals, chain_key, dispatched,
                        next_arrival, dispatched_version, version, ring,
                        count, trust, throttle, x, y, sizes, order, proc,
                        avail, up_s, cx, cy):
            # 1. cohort gather: node-sharded -> replicated (C, ...) rows
            with jax.named_scope(stages.FOLD):
                t_arr = mesh_lib.gather_rows(next_arrival, order, axis, b)
                vdisp_c = mesh_lib.gather_rows(dispatched_version, order,
                                               axis, b)
            with jax.named_scope(stages.LOCAL_SGD):
                disp_c = mesh_lib.gather_rows_tree(dispatched, order, axis,
                                                   b)
            with jax.named_scope(stages.UPLOAD):
                res_c = mesh_lib.gather_rows_tree(residuals, order, axis, b)
            with jax.named_scope(stages.LOCAL_SGD):
                xg = mesh_lib.gather_rows(x, order, axis, b)
                yg = mesh_lib.gather_rows(y, order, axis, b)
                sz = mesh_lib.gather_rows(sizes, order, axis, b)

                if cfg.key_mode == "sequential":
                    chain_key, k1s, k2s = chain_node_keys_masked(chain_key,
                                                                 proc)
                else:
                    chain_key, k1s, k2s = parallel_node_keys(
                        chain_key, order.shape[0])

                # 2. this device's cohort block through the upload pipeline
                blk = lambda t: mesh_lib.my_block_tree(t, axis, d)
                disp_b = blk(disp_c)
                local = jax.vmap(local_train)(disp_b, blk(xg), blk(yg),
                                              blk(sz), blk(k1s))
                deltas = jax.tree.map(lambda l, dd: l - dd.astype(l.dtype),
                                      local, disp_b)
            with jax.named_scope(stages.UPLOAD):
                res_b = blk(res_c)
                thr_c = (mesh_lib.gather_rows(throttle, order, axis, b)
                         if throttle is not None else None)
                if attack_stage is not None:
                    # shard-oblivious per-node row scaling on this device's
                    # cohort block (mal_full closes over as a replicated
                    # const)
                    mal_b = mesh_lib.my_block(jnp.take(mal_full, order),
                                              axis, d)
                    thr_b = (mesh_lib.my_block(thr_c, axis, d)
                             if thr_c is not None else None)
                    deltas = attack_stage(deltas, mal_b, thr_b)
                deltas, res_b, nnz_b = stages.upload_pipeline(
                    cfg, deltas, res_b, blk(k2s), need_nnz=need_nnz)
            with jax.named_scope(stages.CLOUD_SCORE):
                omegas_b, accs_b = stages.rebuild_and_evaluate(
                    raw_acc_fn, disp_b, deltas, cx, cy)

            # 3. gather the arrival set; fold replicated
            with jax.named_scope(stages.FOLD):
                omegas = mesh_lib.all_gather_tree(omegas_b, axis)
                accs = jax.lax.all_gather(accs_b, axis, tiled=True)
            with jax.named_scope(stages.UPLOAD):
                res_c = mesh_lib.all_gather_tree(res_b, axis)

            arrived = proc & avail
            with jax.named_scope(stages.FOLD):
                # the cohort trust rows are gathered replicated, so the
                # fold's trust-weighted mixing stays identical on every
                # device
                trust_c = (mesh_lib.gather_rows(trust, order, axis, b)
                           if trust is not None else None)
                fold = (sequential_fold if cfg.mixing == "sequential"
                        else buffered_fold)
                params, version, ring, count, p_seq, v_seq, rej, taus, aud \
                    = fold(params, version, ring, count, omegas, accs,
                           vdisp_c, arrived, trust_c=trust_c)

                # 4. redispatch: scatter processed rows back to their owners
                dispatched = mesh_lib.scatter_rows_tree(dispatched, order,
                                                        p_seq, proc, axis, b)
            with jax.named_scope(stages.UPLOAD):
                residuals = mesh_lib.scatter_rows_tree(residuals, order,
                                                       res_c, proc, axis, b)
            with jax.named_scope(stages.FOLD):
                dispatched_version = mesh_lib.scatter_rows(
                    dispatched_version, order, v_seq, proc, axis, b)
                t_next = t_arr + up_s + jnp.take(comp_s, order)
                next_arrival = mesh_lib.scatter_rows(next_arrival, order,
                                                     t_next, proc, axis, b)
                if trust is not None:
                    t_new = detection.trust_update(trust_c, arrived & ~rej,
                                                   arrived, eta)
                    trust = mesh_lib.scatter_rows(trust, order, t_new, proc,
                                                  axis, b)
                if throttle is not None:
                    th_new = stages.adaptive_throttle_update(
                        thr_c, rej & arrived, arrived, adapt_scale)
                    throttle = mesh_lib.scatter_rows(throttle, order,
                                                     th_new, proc, axis, b)
            metrics = {
                "n_rejected": (rej & arrived).sum(),
                "max_staleness": jnp.where(arrived, taus, 0).max(),
            }
            if need_nnz:
                metrics["nnz"] = jax.lax.all_gather(nnz_b, axis, tiled=True)
            if need_audit:
                # accs and the fold outputs are already replicated
                metrics["audit"] = dict(aud, accs=accs, rej=rej, taus=taus)
            return (params, residuals, chain_key, dispatched, next_arrival,
                    dispatched_version, version, ring, count, trust,
                    throttle, metrics)

        pn, pr = mesh.spec_nodes(), mesh.spec_replicated()
        m_specs = {"n_rejected": pr, "max_staleness": pr}
        if need_nnz:
            m_specs["nnz"] = pr
        if need_audit:
            m_specs["audit"] = {"accs": pr, "rej": pr, "taus": pr,
                                "thr": pr, "held": pr}
        # trust/throttle are node-sharded when present and leafless Nones
        # when the spec keeps the defaults (specs over None are vacuous)
        return mesh.shard_map(
            window_body,
            in_specs=(pr, pn, pr, pn, pn, pn, pr, pr, pr, pn, pn,
                      pn, pn, pn, pr, pr, pr, pr, pr, pr),
            out_specs=(pr, pn, pr, pn, pn, pn, pr, pr, pr, pn, pn,
                       m_specs))

    # -- host-side driver ---------------------------------------------------
    def select_window(self, max_arrivals: Optional[int] = None
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """(order, proc): node ids sorted by (arrival, id) and in-window
        flags — every pending arrival inside [t0, t0 + window). Padding
        rows of a sharded fleet carry +inf clocks: they sort last and are
        never in-window."""
        na = np.asarray(self.state.next_arrival, np.float64)
        order = np.lexsort((np.arange(self.n_pad), na))
        proc = na[order] < na[order[0]] + self._window_len
        if max_arrivals is not None:
            proc &= np.cumsum(proc) <= max_arrivals
        # in-window arrivals are a prefix of the sort: truncate the cohort
        # to the smallest power-of-two bucket covering them so the device
        # program only trains nodes that can arrive (one compile per bucket;
        # floored at 16 — small fleets get a single full-size program). On a
        # mesh the bucket is additionally rounded up to a shard multiple so
        # the cohort axis splits evenly across devices.
        c = 16
        while c < int(proc.sum()):
            c *= 2
        c = min(c, self.n_pad)
        if self.mesh is not None:
            d = self.mesh.n_devices
            c = min(self.n_pad, ((c + d - 1) // d) * d)
        return order[:c], proc[:c]

    def run_window(self, max_arrivals: Optional[int] = None,
                   evaluate: bool = True) -> AsyncWindowRecord:
        """Process one arrival window. `evaluate=False` skips the global
        test-set accuracy (recorded as NaN) — callers that only consume
        accuracy at coarser boundaries (the trainer: once per n_nodes
        arrivals) avoid a test forward pass + device sync per window."""
        tr = self.obs
        w = self._window_idx
        with host_span(tr, "window", window=w) as span:
            rec, t_first = self._window(tr, w, max_arrivals, evaluate)
            span.set(n_processed=rec.n_processed, n_rejected=rec.n_rejected,
                     version=rec.version)
            span.set_virtual(t_first, rec.t)
        return rec

    def _window(self, tr, w: int, max_arrivals: Optional[int],
                evaluate: bool) -> Tuple[AsyncWindowRecord, float]:
        with timed_stage(tr, "window.select", window=w):
            order, proc = self.select_window(max_arrivals)
        t_arr = np.asarray(self.state.next_arrival, np.float64)[order]
        if self.sampler is not None:
            # cohort() returns (idx, valid) aligned to idx; fold it into a
            # per-node availability mask (a node absent from the cohort, or
            # present but invalid, loses arrivals this window)
            idx_s, up = self.sampler.cohort(w, self.n_nodes)
            avail = self._participation_mask(idx_s, up)[order]
        else:
            avail = np.ones(order.size, bool)

        # per-slot uplink seconds: the analytic per-node constants, or one
        # stochastic link draw per in-window upload (non-proc slots never
        # scatter a clock, their value is irrelevant)
        sel = order[proc]
        draw = None
        if self.net is not None:
            up_host = np.zeros(order.size, np.float64)
            # DDoS flash traffic: flood flows contend for the shared
            # uplink alongside every window's real uploads
            flood = self.attack.flood_uploads if self.attack else 0
            with timed_stage(tr, "net.draw", window=w):
                draw = self.net.draw(sel, extra_concurrency=flood)
            up_host[proc] = draw.transfer_s
        else:
            up_host = self._comm_pad32[order].astype(np.float64)
        up_s = jnp.asarray(up_host, jnp.float32)

        with timed_stage(tr, "window.device", window=w) as dev:
            if self.mesh is not None:
                st = self.state
                (self.params, residuals, chain_key, dispatched, next_arrival,
                 dispatched_version, version, ring, count, trust, throttle,
                 m) = self._window_fn(
                    self.params, st.residuals, st.chain_key, st.dispatched,
                    st.next_arrival, st.dispatched_version, st.version,
                    st.acc_ring, st.acc_count, st.trust, st.throttle,
                    self.data.x, self.data.y,
                    self.data.sizes, jnp.asarray(order, jnp.int32),
                    jnp.asarray(proc), jnp.asarray(avail), up_s,
                    *self.cloud_test)
                self.state = dataclasses.replace(
                    st, residuals=residuals, chain_key=chain_key,
                    dispatched=dispatched, next_arrival=next_arrival,
                    dispatched_version=dispatched_version, version=version,
                    acc_ring=ring, acc_count=count, trust=trust,
                    throttle=throttle)
            else:
                self.params, self.state, m = self._window_fn(
                    self.params, self.state, self.data.x, self.data.y,
                    self.data.sizes, jnp.asarray(order, jnp.int32),
                    jnp.asarray(proc), jnp.asarray(avail), up_s)
            dev.fence((self.params, m))
        self._window_idx = w + 1

        # host-side clock/traffic accounting over the processed arrivals.
        # Churned-out slots (proc & ~avail) are billed too, by design: the
        # node transmitted its update before going unreachable (its clock
        # pays uplink + compute above), the cloud just discards it — the
        # same semantics as the analytic path's bpn * n_processed.
        if self.net is not None:
            # byte-accurate: price each upload's measured nonzero count
            # through the wire codec; times are the link draws
            with timed_stage(tr, "net.commit", window=w):
                enc = self.net.commit(draw, np.asarray(m["nnz"])[proc],
                                      ctx={"window": w})
            uplink = draw.transfer_s
            comm_bytes = float(enc.sum())
        else:
            uplink = self._comm_s[sel]
            comm_bytes = float(self._bpn * sel.size)
        t_arrive = t_arr[proc] + uplink             # arrival + uplink times
        if evaluate:
            with timed_stage(tr, "window.evaluate", window=w):
                accuracy = self.global_accuracy()
        else:
            accuracy = float("nan")
        rec = AsyncWindowRecord(
            t=float(t_arrive.max()) if sel.size else 0.0,
            window=w, version=int(self.state.version),
            accuracy=accuracy,
            comm_bytes=comm_bytes,
            comp_time=float(self._comp_s[sel].sum()),
            comm_time=float(uplink.sum()),
            n_processed=int(sel.size),
            n_rejected=int(m["n_rejected"]),
            max_staleness=int(m["max_staleness"]))
        self.history.append(rec)
        if tr.enabled:
            self._emit_window_events(rec, sel, proc, avail, t_arrive, m)
        return rec, float(t_arr[0]) if t_arr.size else 0.0

    def _emit_window_events(self, rec: AsyncWindowRecord, sel, proc, avail,
                            t_arrive, m) -> None:
        """One window's trace: arrival instants (every processed upload),
        a `detect.verdict` instant per cloud evaluation (the Alg. 2 audit
        log — accuracy, ring threshold/occupancy, verdict, staleness), and
        the aggregated window metrics."""
        tr = self.obs
        arrived = avail[proc]
        aud = m.get("audit")
        if aud is not None:
            accs = np.asarray(aud["accs"])[proc]
            rej = np.asarray(aud["rej"])[proc]
            taus = np.asarray(aud["taus"])[proc]
            thr = np.asarray(aud["thr"])[proc]
            held = np.asarray(aud["held"])[proc]
        for i in range(sel.size):
            t_i = float(t_arrive[i])
            node = int(sel[i])
            tr.instant("arrival", virt_t=t_i, node=node, window=rec.window,
                       arrived=bool(arrived[i]))
            if aud is not None and arrived[i]:
                tr.instant(
                    "detect.verdict", virt_t=t_i, node=node,
                    window=rec.window, accuracy=float(accs[i]),
                    threshold=float(thr[i]), ring_held=int(held[i]),
                    rejected=bool(rej[i]), tau=int(taus[i]),
                    detect=bool(self.cfg.detect))
        mx = tr.metrics
        mx.histogram("window.size", WINDOW_SIZE_EDGES).observe(
            rec.n_processed)
        mx.histogram("window.max_staleness", STALENESS_EDGES).observe(
            rec.max_staleness)
        mx.counter("window.arrivals").inc(rec.n_processed)
        mx.counter("window.rejected").inc(rec.n_rejected)
        mx.counter("window.comm_bytes").inc(rec.comm_bytes)
        mx.gauge("model.version").set(rec.version)

    def run(self, windows: int) -> List[AsyncWindowRecord]:
        for _ in range(windows):
            self.run_window()
        return self.history

    def run_arrivals(self, total: int) -> List[AsyncWindowRecord]:
        """Process exactly `total` arrivals (the trainer's rounds×nodes
        budget), truncating the final window."""
        done = 0
        while done < total:
            done += self.run_window(max_arrivals=total - done).n_processed
        return self.history

    def global_accuracy(self) -> float:
        return float(self.acc_fn(self.params, *self.test_data))

    def kappa(self) -> float:
        """Eq. (5) over the whole run (per-arrival totals)."""
        comm = sum(r.comm_time for r in self.history)
        comp = sum(r.comp_time for r in self.history)
        return async_update.communication_efficiency(comm, comp)
