"""Execute an `ExperimentPlan`: plan -> engines -> `RunReport`.

This is the one execution layer behind every entry point — the
declarative `run(compile_plan(spec))` surface and the scenario builders
all land here.  The four execution paths (sync/async × sequential
reference loop / fleet engines) are the seed trainer's former ``_run_*``
branches, ported verbatim so the round-record trajectories stay
bit-equal-to-float-close with the pre-redesign implementation (enforced
by tests/test_api.py):

  * ``engine="fleet"``      — the cohort-batched `FleetEngine` (sync) or
    window-batched `AsyncFleetEngine` (async/buffered), optionally
    node-sharded over a `FleetMesh`;
  * ``engine="sequential"`` — the per-node / per-arrival reference loops
    (the seed implementation: one Python dispatch per update, kept as the
    bit-exact ground truth the engines are tested against).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import heapq
from dataclasses import dataclass, field
from functools import partial
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import accumulator as accum
from ..core import aldp, async_update, detection
from ..core.accountant import MomentsAccountant
from .. import fleet
from .. import obs as _obs
from ..fleet import stages as fleet_stages
from ..net import netsim_from_network
from .plan import ExperimentPlan, SpecError
from .population import Population, materialize
from .report import RoundRecord, RunReport, detection_log
from .spec import SCHEMA_VERSION


# ---------------------------------------------------------------------------
# mutable run state (what the trainer used to keep on `self`)
# ---------------------------------------------------------------------------

@dataclass
class RunState:
    """Everything that evolves over a run and survives it: the global
    model, the host-side PRNG chain key, per-node DGC residuals, the
    privacy accountant, and the record history.  Repeated `execute` calls
    over the same state continue the PRNG chain / residuals faithfully."""
    params: Any
    key: Any
    residuals: List[Any]
    accountant: Optional[MomentsAccountant]
    history: List[RoundRecord] = field(default_factory=list)
    net: Optional[dict] = None      # NetTrace summary when repro.net ran


def init_state(plan: ExperimentPlan, population: Population) -> RunState:
    """Fresh run state: ω_0 from the population, chain key from the spec
    seed, zero residuals, and an accountant only when σ > 0 (no-noise runs
    must spend exactly zero privacy budget)."""
    return RunState(
        params=population.params,
        key=jax.random.PRNGKey(plan.spec.seed),
        residuals=[accum.init_residual(population.params)
                   for _ in range(population.n_nodes)],
        accountant=(MomentsAccountant(plan.sigma, 1.0)
                    if plan.sigma > 0 else None))


# ---------------------------------------------------------------------------
# the per-run observability session (ObsSpec -> tracer + sinks + streams)
# ---------------------------------------------------------------------------

class _ObsSession:
    """Materialize one run's `ObsSpec`: build the tracer and its sinks,
    stream `RoundRecord`s as they land, export the Chrome trace and the
    metrics snapshot at the end.  With the spec disabled every method is a
    no-op and no tracer is installed — the run is byte-identical to a
    pre-obs build."""

    def __init__(self, plan: ExperimentPlan):
        o = plan.spec.obs
        self.enabled = o.enabled
        self.tracer: Optional[_obs.Tracer] = None
        self.analytics: Optional[_obs.FleetAnalytics] = None
        self.health: Optional[_obs.HealthMonitor] = None
        self._chrome_path = o.chrome_trace
        self._mem: Optional[_obs.MemorySink] = None
        self._events: Optional[_obs.JsonlSink] = None
        self._records: Optional[_obs.JsonlWriter] = None
        self._last_virt_t = 0.0
        self._last_records_done = 0
        if not self.enabled:
            return
        engine_name = ("fleet-mesh" if plan.mesh_devices is not None
                       else plan.engine)
        header = {"schema_version": SCHEMA_VERSION, "mode": plan.mode,
                  "engine": engine_name, "spec": plan.spec.to_dict()}
        sinks = []
        if o.chrome_trace:
            self._mem = _obs.MemorySink()
            sinks.append(self._mem)
        if o.events_jsonl:
            self._events = _obs.JsonlSink(o.events_jsonl,
                                          header=dict(header,
                                                      stream="events"))
            sinks.append(self._events)
        if o.health is not None:
            # the analytics sink sees every event the file sinks see —
            # including the monitor's own alerts/incidents, which it
            # collects but never probes on
            self.analytics = _obs.FleetAnalytics(
                n_nodes=plan.spec.fleet.n_nodes)
            sinks.append(self.analytics)
        self.tracer = _obs.Tracer(sinks=sinks, enabled=True,
                                  stage_timings=o.stage_timings)
        if o.health is not None:
            self.health = _obs.HealthMonitor(
                o.health, self.analytics, self.tracer,
                n_nodes=plan.spec.fleet.n_nodes)
        if o.records_jsonl:
            self._records = _obs.JsonlWriter(o.records_jsonl,
                                             header=dict(header,
                                                         stream="records"))

    def scope(self):
        """The `use_tracer` context the run executes inside (engines and
        `NetSim` pick the tracer up from the process-global slot)."""
        return (_obs.use_tracer(self.tracer) if self.tracer is not None
                else contextlib.nullcontext())

    def record(self, rec: RoundRecord) -> None:
        """Stream one completed round record (called from the history
        hook the moment each record is appended — crash-safe JSONL, not
        the at-end dump)."""
        if self._records is not None:
            self._records.write({"kind": "record",
                                 **dataclasses.asdict(rec)})

    def history(self) -> Optional[List[RoundRecord]]:
        """An append-hooked record list when ``records_jsonl`` is set
        (swapped in for ``state.history``), else None."""
        if self._records is None:
            return None
        return _StreamingHistory(self.record)

    def poll_health(self, virt_t: float, records_done: int) -> None:
        """Evaluate the health probes between records (no-op without an
        `ObsSpec.health` axis)."""
        if self.health is None:
            return
        self._last_virt_t = virt_t
        self._last_records_done = records_done
        self.health.evaluate(virt_t, records_done)

    def finish(self, report: Optional[RunReport] = None) -> None:
        """Flush everything: close open health incidents, report footer
        on the record stream, metrics snapshot on the event stream, the
        Chrome-trace export, then close every sink."""
        if not self.enabled:
            return
        if self.health is not None:
            # run end closes whatever is still open (tagged unresolved),
            # before the metrics snapshot so incident counters land in it
            t = max(self._last_virt_t,
                    self.analytics.t_max or 0.0)
            self.health.finalize(t, self._last_records_done)
        if self._records is not None:
            if report is not None:
                footer = {k: v for k, v in report.to_dict().items()
                          if k != "records"}
                self._records.write({"kind": "report", **footer})
            self._records.close()
        if self._events is not None:
            snap = self.tracer.metrics.snapshot()
            if snap:
                self._events.writer.write({"kind": "metrics",
                                           "metrics": snap})
        if self._chrome_path and self._mem is not None:
            _obs.write_chrome_trace(self._chrome_path, self._mem.events)
        self.tracer.close()


class _StreamingHistory(list):
    """A record list that streams each append (the `_run_*` drivers and
    the sequential runner all append to ``state.history`` — hooking the
    list streams every path without touching the drivers)."""

    def __init__(self, callback):
        super().__init__()
        self._callback = callback

    def append(self, rec) -> None:
        super().append(rec)
        self._callback(rec)


# ---------------------------------------------------------------------------
# engine construction (shared with the scenario builders)
# ---------------------------------------------------------------------------

def make_engine(plan: ExperimentPlan, population: Population,
                mesh: Optional["fleet.FleetMesh"] = None):
    """Build the fleet engine a plan selects, faithful to the trainer's
    construction (sequential PRNG chain, reference/pallas backend, the
    population's profile/sampler).  ``mesh`` overrides the plan's
    topology-derived mesh (scenario builders pass prebuilt meshes)."""
    if plan.engine != "fleet":
        raise ValueError("make_engine: plan selects the sequential "
                         "reference loop, which has no engine object")
    spec = plan.spec
    if mesh is None and plan.mesh_devices is not None:
        mesh = fleet.FleetMesh.create(plan.mesh_devices or None)

    common = dict(
        local_steps=spec.train.local_steps, batch_size=spec.train.batch_size,
        lr=spec.train.lr, alpha=spec.schedule.alpha,
        clip_s=spec.privacy.clip_s, sigma=plan.sigma,
        detect=spec.defense.detect, detect_s=spec.defense.detect_s,
        defense_kind=spec.defense.kind, trust_eta=spec.defense.trust_eta,
        trust_floor=spec.defense.trust_floor,
        uncertainty_scale=spec.defense.uncertainty_scale,
        sparsify_ratio=spec.compression.sparsify_ratio,
        key_mode=plan.key_mode, backend=spec.topology.backend,
        seed=spec.seed)
    args = (population.params, population.loss_fn, population.acc_fn,
            population.node_data, population.test_data, population.cloud_test)
    # model-delta adversary stages (sybil/adaptive scaling, ddos flood
    # accounting) ride the engines only when the spec staffs the fleet
    # with malicious nodes; None keeps the jitted programs byte-identical
    attack = (fleet_stages.AttackPlan.from_spec(
                  spec.fleet.attack, population.n_nodes,
                  population.malicious_ids)
              if population.malicious_ids else None)

    n_params = sum(x.size for x in jax.tree.leaves(population.params))
    # the repro.net transport (None with NetworkSpec at its analytic
    # defaults — the engines then keep the pre-net comm model exactly)
    net = netsim_from_network(
        spec.network, population.profile.bandwidth_bps, n_params,
        sparsify_ratio=spec.compression.sparsify_ratio, seed=spec.seed)

    if plan.mode == "sync":
        cfg = fleet.FleetConfig(**common)
        return fleet.FleetEngine(
            *args, cfg, profile=population.profile,
            sampler=population.sampler or fleet.FullParticipation(),
            mesh=mesh, net=net, attack=attack)

    bpn = fleet_stages.bytes_per_node(n_params,
                                      spec.compression.sparsify_ratio)
    cfg = fleet.AsyncFleetConfig(
        **common,
        window=spec.schedule.window.resolve(population.profile, bpn),
        mixing="buffered" if plan.mixing == "buffered" else "sequential",
        staleness_adaptive=spec.schedule.staleness_adaptive,
        staleness_a=spec.schedule.staleness_a,
        detect_warmup=spec.defense.detect_warmup,
        detect_window=plan.detect_window)
    return fleet.AsyncFleetEngine(*args, cfg, profile=population.profile,
                                  sampler=population.sampler, mesh=mesh,
                                  net=net, attack=attack)


# ---------------------------------------------------------------------------
# record steppers (the trainer's former _run_* drivers, one record at a time)
# ---------------------------------------------------------------------------
#
# Each execution path is a *stepper*: `step()` advances the run by exactly
# one `RoundRecord` (a barrier round, or n_nodes async arrivals, or one
# buffered window), `done` says whether the record budget is spent, and
# `finalize()` hands node-local state back to the `RunState`.  `execute`
# just drains a stepper — byte-for-byte the old loops — while `repro.sim`
# drives the same steppers incrementally: its coordinator installs a
# `pre_step` hook (traffic-trace modulation), checkpoints between steps
# via `export_state`/`restore_state` (only ever called at a record
# boundary, where the span accumulators are exactly zero), and swaps
# steppers mid-run to apply `SimEvent` spec mutations.

class _SyncFleetStepper:
    """Barrier rounds on the cohort-batched `FleetEngine`."""

    def __init__(self, plan, pop, state, eng):
        self.plan, self.pop, self.state, self.eng = plan, pop, state, eng
        self.n = pop.n_nodes
        self.src = "encoded" if eng.net is not None else "analytic"
        eng.load_state(fleet.stack_trees(state.residuals), state.key)
        self.emitted = 0
        self.pre_step = None
        self.gc = _obs.GcSpans()    # Python's collections during steps

    @property
    def net(self):
        return self.eng.net

    @property
    def done(self) -> bool:
        return self.emitted >= self.plan.spec.rounds

    def virtual_time(self) -> float:
        h = self.eng.history
        return float(h[-1].t) if h else float(self.eng._t0)

    def step(self) -> None:
        if self.pre_step is not None:
            self.pre_step(self)
        state, eng = self.state, self.eng
        with self.gc:
            rec = eng.run_round(on_record=self._charge)
        state.params = eng.params
        state.history.append(RoundRecord(
            rec.t, self.emitted, rec.accuracy, rec.comm_bytes, rec.comp_time,
            rec.comm_time, rec.n_rejected, bytes_source=self.src))
        self.emitted += 1

    def _charge(self, rec) -> None:
        """Charge only the nodes that actually uploaded a noised delta
        (cohort sampling / availability: n_participating <= n_nodes)."""
        if self.state.accountant is not None:
            with _obs.timed_stage(self.eng.obs, "record.accountant"):
                self.state.accountant.step(rec.n_participating)

    def finalize(self) -> None:
        _fleet_handback(self.state, self.eng, self.n)

    # -- checkpoint/resume (repro.sim) --------------------------------------
    def export_state(self):
        arrays = self.eng.export_sim_state()
        meta = {"emitted": self.emitted,
                "round": int(self.eng.state.round),
                "t0": self.virtual_time()}
        _export_net(self.eng.net, arrays, meta)
        return arrays, meta

    def restore_state(self, arrays, meta) -> None:
        arrays = dict(arrays)
        _restore_net(self.eng.net, arrays, meta)
        self.eng.load_sim_state(arrays)
        self.eng.state = dataclasses.replace(self.eng.state,
                                             round=int(meta["round"]))
        # the engine's barrier clock continues from the checkpointed time
        # (its own history list is empty after a restore)
        self.eng._t0 = float(meta["t0"])
        self.state.params = self.eng.params
        self.emitted = int(meta["emitted"])


class _AsyncFleetStepper:
    """Event-loop cadence on the window-batched `AsyncFleetEngine`: one
    record per n_nodes arrivals — windows are capped so they never
    straddle a record boundary (a cap only truncates the arrival prefix,
    so the processed order is unchanged)."""

    def __init__(self, plan, pop, state, eng):
        self.plan, self.pop, self.state, self.eng = plan, pop, state, eng
        self.n = pop.n_nodes
        self.src = "encoded" if eng.net is not None else "analytic"
        eng.load_state(fleet.stack_trees(state.residuals), state.key)
        self.acc_fn = eng.acc_fn
        self.test_dev = eng.test_data
        self.emitted = 0
        self.processed = 0
        self.pre_step = None
        self.gc = _obs.GcSpans()    # Python's collections during steps

    @property
    def net(self):
        return self.eng.net

    @property
    def done(self) -> bool:
        return self.processed >= self.plan.total_arrivals

    def virtual_time(self) -> float:
        arr = np.asarray(jax.device_get(self.eng.state.next_arrival),
                         np.float64)[:self.n]
        return float(arr.min())

    def step(self) -> None:
        with self.gc:
            self._step()

    def _charge(self, rec) -> None:
        if self.state.accountant is not None:
            with _obs.timed_stage(self.eng.obs, "record.accountant"):
                self.state.accountant.step(rec.n_processed)

    def _step(self) -> None:
        state, eng, n = self.state, self.eng, self.n
        target = min(self.processed + n, self.plan.total_arrivals)
        span_bytes = span_comp = span_comm = 0.0
        span_rejected = 0
        rec = None
        while self.processed < target:
            if self.pre_step is not None:
                self.pre_step(self)
            rec = eng.run_window(max_arrivals=target - self.processed,
                                 evaluate=False)
            self.processed += rec.n_processed
            self._charge(rec)
            state.params = eng.params
            span_bytes += rec.comm_bytes
            span_comp += rec.comp_time
            span_comm += rec.comm_time
            span_rejected += rec.n_rejected
        state.history.append(RoundRecord(
            rec.t, rec.version,
            float(self.acc_fn(state.params, *self.test_dev)),
            span_bytes, span_comp, span_comm, span_rejected,
            bytes_source=self.src))
        self.emitted += 1

    def finalize(self) -> None:
        _fleet_handback(self.state, self.eng, self.n)

    # -- checkpoint/resume (repro.sim) --------------------------------------
    def export_state(self):
        arrays = self.eng.export_sim_state()
        meta = {"emitted": self.emitted, "processed": self.processed,
                "window_idx": int(self.eng._window_idx)}
        _export_net(self.eng.net, arrays, meta)
        return arrays, meta

    def restore_state(self, arrays, meta) -> None:
        arrays = dict(arrays)
        _restore_net(self.eng.net, arrays, meta)
        self.eng.load_sim_state(arrays)
        self.state.params = self.eng.params
        self.emitted = int(meta["emitted"])
        self.processed = int(meta["processed"])
        # the window index seeds the cohort sampler's round stream
        self.eng._window_idx = int(meta["window_idx"])


class _BufferedFleetStepper(_AsyncFleetStepper):
    """Buffered (FedBuff-style) windows: process the arrival budget window
    by window without the event-loop record boundary — one record per
    window (load-aware policies make windows fat on purpose)."""

    def _step(self) -> None:
        if self.pre_step is not None:
            self.pre_step(self)
        state, eng = self.state, self.eng
        rec = eng.run_window(
            max_arrivals=self.plan.total_arrivals - self.processed,
            evaluate=False)
        self.processed += rec.n_processed
        self._charge(rec)
        state.params = eng.params
        state.history.append(RoundRecord(
            rec.t, rec.version,
            float(self.acc_fn(state.params, *self.test_dev)),
            rec.comm_bytes, rec.comp_time, rec.comm_time, rec.n_rejected,
            bytes_source=self.src))
        self.emitted += 1


def _fleet_handback(state, eng, n) -> None:
    """Hand node-local state back so follow-on runs stay faithful."""
    state.key = jax.device_get(eng.state.chain_key)
    state.residuals = fleet.unstack_tree(eng.export_residuals(), n)
    if eng.net is not None:
        state.net = eng.net.summary()


def _export_net(net, arrays, meta) -> None:
    """Fold the `NetSim` counter/trace state into a stepper snapshot."""
    if net is not None:
        counters, columns = net.export_sim_state()
        arrays["net_counters"] = counters
        meta["net_trace"] = columns


def _restore_net(net, arrays, meta) -> None:
    counters = arrays.pop("net_counters", None)
    if net is not None and counters is not None:
        net.restore_sim_state(counters, meta.get("net_trace"))


# ---------------------------------------------------------------------------
# sequential reference loops (the seed implementation, kept bit-exact)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _jitted_local_train(loss_fn, steps, lr, bs):
    """One jitted local-SGD program per (loss_fn, hyperparams) — repeated
    `execute` calls (the trainer shim's run-again pattern, benchmark
    timing loops) reuse the trace instead of recompiling."""
    return jax.jit(partial(_local_train_impl, loss_fn, steps, lr, bs))


@functools.lru_cache(maxsize=64)
def _jitted_acc(acc_fn):
    return jax.jit(acc_fn)


def _local_train_impl(loss_fn, steps, lr, bs, params, x, y, key):
    n = x.shape[0]

    def body(carry, k):
        p, = carry
        idx = jax.random.randint(k, (bs,), 0, n)
        batch = {"x": x[idx], "y": y[idx]}
        g = jax.grad(lambda pp: loss_fn(pp, batch)[0])(p)
        p = jax.tree.map(lambda a, b: a - lr * b, p, g)
        return (p,), None

    keys = jax.random.split(key, steps)
    (p,), _ = jax.lax.scan(body, (params,), keys)
    return p


class _SequentialRunner:
    """The per-node upload pipeline + both reference loops, operating on a
    (plan, population, state) triple instead of trainer attributes.

    Stepper protocol: `step()` emits one `RoundRecord` (a barrier round,
    or n_nodes arrivals of the event loop); the loop state (clock /
    arrival heap / dispatch cache) lives on the instance so `repro.sim`
    can snapshot and restore it between records."""

    def __init__(self, plan: ExperimentPlan, pop: Population,
                 state: RunState):
        spec = plan.spec
        self.plan, self.pop, self.state, self.spec = plan, pop, state, spec
        self.node_data = [(jnp.asarray(x), jnp.asarray(y))
                          for x, y in pop.node_data]
        self.test_data = (jnp.asarray(pop.test_data[0]),
                          jnp.asarray(pop.test_data[1]))
        self.cloud_test = (jnp.asarray(pop.cloud_test[0]),
                           jnp.asarray(pop.cloud_test[1]))
        self.acc_fn = _jitted_acc(pop.acc_fn)
        self.n_params = sum(x.size for x in jax.tree.leaves(pop.params))
        self.node_time = np.asarray(pop.profile.compute_s, np.float64)
        self.node_bw = np.asarray(pop.profile.bandwidth_bps, np.float64)
        self._local_train = _jitted_local_train(
            pop.loss_fn, spec.train.local_steps, spec.train.lr,
            spec.train.batch_size)
        # -- stepper loop state -------------------------------------------
        n = pop.n_nodes
        self.emitted = 0
        self.pre_step = None
        self.net = None             # no repro.net on the reference loops
        if plan.mode == "sync":
            self.clock = 0.0
        else:
            self.version = 0
            # (arrival_time, node, dispatched_version, seq) heap
            self.events = []
            for node in range(n):
                heapq.heappush(self.events,
                               (self.node_time[node], node, 0, node))
            self.dispatched_params = {k: state.params for k in range(n)}
            self.acc_window: List[float] = []
            self.seq = n
            self.processed = 0

    # -- per-node upload pipeline ------------------------------------------
    def node_update(self, node: int, start_params):
        """Local train -> delta -> [accumulate/sparsify] -> [ALDP] -> ω_new.
        Returns (uploaded model, upload_bytes, cloud-test accuracy)."""
        plan, spec, state = self.plan, self.spec, self.state
        x, y = self.node_data[node]
        state.key, k1, k2 = jax.random.split(state.key, 3)
        local = self._local_train(start_params, x, y, k1)
        delta = jax.tree.map(lambda a, b: a - b, local, start_params)

        ratio = spec.compression.sparsify_ratio
        if ratio < 1.0:
            delta, state.residuals[node], _ = accum.accumulate_and_sparsify(
                state.residuals[node], delta, ratio)
            bytes_up = accum.upload_bytes(delta, ratio)
        else:
            bytes_up = self.n_params * 4

        if plan.sigma > 0:
            delta, _ = aldp.aldp_perturb(delta, k2, plan.sigma,
                                         spec.privacy.clip_s)
            state.accountant.step()   # accountant exists whenever sigma > 0

        omega_new = jax.tree.map(lambda a, b: a + b, start_params, delta)
        acc = float(self.acc_fn(omega_new, *self.cloud_test))
        return omega_new, bytes_up, acc

    def global_accuracy(self) -> float:
        return float(self.acc_fn(self.state.params, *self.test_data))

    # -- stepper protocol ---------------------------------------------------
    @property
    def done(self) -> bool:
        if self.plan.mode == "sync":
            return self.emitted >= self.spec.rounds
        return self.processed >= self.plan.total_arrivals

    def virtual_time(self) -> float:
        if self.plan.mode == "sync":
            return float(self.clock)
        return float(self.events[0][0])

    def step(self) -> None:
        if self.pre_step is not None:
            self.pre_step(self)
        if self.plan.mode == "sync":
            self._step_sync()
        else:
            self._step_async()

    def finalize(self) -> None:
        pass        # params/key/residuals already live on the RunState

    # -- synchronous barrier loop (one round per step) ----------------------
    def _step_sync(self) -> None:
        spec, state = self.spec, self.state
        n = self.pop.n_nodes
        alpha = spec.schedule.alpha
        uploads, accs, nbytes = [], [], 0.0
        for node in range(n):
            w, b, a = self.node_update(node, state.params)
            uploads.append(w)
            accs.append(a)
            nbytes += b
        accs = jnp.asarray(accs)
        if spec.defense.detect:
            mask, _ = detection.detect(accs, spec.defense.detect_s)
        else:
            mask = jnp.ones(n, bool)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *uploads)
        omega_new = detection.masked_mean(stacked, mask)
        state.params = async_update.mix(state.params, omega_new, alpha)
        comp = float(np.max(self.node_time))         # barrier: slowest
        comm = float(np.max((nbytes / n) / self.node_bw))  # parallel up
        self.clock += comp + comm
        state.history.append(RoundRecord(
            self.clock, self.emitted, self.global_accuracy(), nbytes, comp,
            comm, int(n - mask.sum())))
        self.emitted += 1

    # -- asynchronous per-arrival event loop (n_nodes arrivals per step) ----
    def _step_async(self) -> None:
        plan, spec, state = self.plan, self.spec, self.state
        n = self.pop.n_nodes
        alpha = spec.schedule.alpha
        # per-record accumulators: a RoundRecord spans n_nodes arrivals, so
        # traffic/time must be summed over the span, not the last arrival
        # (steps align with record boundaries, where the spans are zero)
        span_bytes = span_comp = span_comm = 0.0
        span_rejected = 0
        target = min(self.processed + n, plan.total_arrivals)
        t_arrive = 0.0
        while self.processed < target:
            t, node, v_disp, _ = heapq.heappop(self.events)
            w, b, a = self.node_update(node, self.dispatched_params[node])
            comm = float(b / self.node_bw[node])
            t_arrive = t + comm
            self.acc_window.append(a)
            self.acc_window = self.acc_window[-plan.detect_window:]
            rejected = 0
            if spec.defense.detect and \
                    len(self.acc_window) >= spec.defense.detect_warmup:
                accs = jnp.asarray(self.acc_window)
                thr = detection.detection_threshold(accs,
                                                    spec.defense.detect_s)
                if a <= float(thr):
                    rejected = 1
            if not rejected:
                staleness = self.version - v_disp
                if spec.schedule.staleness_adaptive:
                    state.params = async_update.mix_stale(
                        state.params, w, alpha, staleness)
                else:
                    state.params = async_update.mix(state.params, w, alpha)
                self.version += 1
            self.processed += 1
            span_bytes += b
            span_comp += float(self.node_time[node])
            span_comm += comm
            span_rejected += rejected
            # redispatch node with the fresh global model
            self.dispatched_params[node] = state.params
            heapq.heappush(self.events,
                           (t_arrive + self.node_time[node], node,
                            self.version, self.seq))
            self.seq += 1
        state.history.append(RoundRecord(
            t_arrive, self.version, self.global_accuracy(), span_bytes,
            span_comp, span_comm, span_rejected))
        self.emitted += 1

    # -- checkpoint/resume (repro.sim) --------------------------------------
    def export_state(self):
        state, n = self.state, self.pop.n_nodes
        arrays = {
            "params": jax.tree.map(np.asarray,
                                   jax.device_get(state.params)),
            "key": np.asarray(jax.device_get(state.key)),
            "residuals": jax.tree.map(
                np.asarray,
                jax.device_get(fleet.stack_trees(state.residuals))),
        }
        meta = {"emitted": self.emitted}
        if self.plan.mode == "sync":
            meta["clock"] = float(self.clock)
        else:
            # the heap is a multiset with a total order (seq is unique), so
            # any serialization order restores the identical pop sequence
            ev = sorted(self.events)
            arrays["heap_t"] = np.asarray([e[0] for e in ev], np.float64)
            arrays["heap_node"] = np.asarray([e[1] for e in ev], np.int64)
            arrays["heap_vdisp"] = np.asarray([e[2] for e in ev], np.int64)
            arrays["heap_seq"] = np.asarray([e[3] for e in ev], np.int64)
            arrays["dispatched"] = jax.tree.map(
                np.asarray,
                jax.device_get(fleet.stack_trees(
                    [self.dispatched_params[i] for i in range(n)])))
            meta.update(processed=self.processed, version=self.version,
                        seq=self.seq,
                        acc_window=[float(a) for a in self.acc_window])
        return arrays, meta

    def restore_state(self, arrays, meta) -> None:
        state, n = self.state, self.pop.n_nodes
        state.params = jax.tree.map(jnp.asarray, arrays["params"])
        state.key = jnp.asarray(arrays["key"])
        state.residuals = fleet.unstack_tree(
            jax.tree.map(jnp.asarray, arrays["residuals"]), n)
        self.emitted = int(meta["emitted"])
        if self.plan.mode == "sync":
            self.clock = float(meta["clock"])
        else:
            events = [(float(t), int(nd), int(v), int(s))
                      for t, nd, v, s in zip(arrays["heap_t"],
                                             arrays["heap_node"],
                                             arrays["heap_vdisp"],
                                             arrays["heap_seq"])]
            heapq.heapify(events)
            self.events = events
            disp = jax.tree.map(jnp.asarray, arrays["dispatched"])
            self.dispatched_params = {
                i: jax.tree.map(lambda x, i=i: x[i], disp) for i in range(n)}
            self.processed = int(meta["processed"])
            self.version = int(meta["version"])
            self.seq = int(meta["seq"])
            self.acc_window = [float(a) for a in meta["acc_window"]]


# ---------------------------------------------------------------------------
# top-level execution
# ---------------------------------------------------------------------------

def make_stepper(plan: ExperimentPlan, population: Population,
                 state: RunState, mesh: Optional["fleet.FleetMesh"] = None):
    """Build the record stepper a plan selects (engines constructed here
    pick up any installed obs tracer — call inside the session scope)."""
    if population.n_nodes != plan.spec.fleet.n_nodes:
        raise SpecError(
            f"population has {population.n_nodes} nodes but the plan was "
            f"compiled for fleet.n_nodes={plan.spec.fleet.n_nodes} — the "
            f"arrival budget and record cadence derive from the spec, so "
            f"a mismatched population would run the wrong experiment")
    tr = _obs.get_tracer()
    if tr.enabled:
        # ground truth for trace-only detection-quality reconstruction:
        # which nodes actually run the attack (analytics folds this into
        # the detect.verdict confusion matrix)
        tr.instant("fleet.population", n_nodes=population.n_nodes,
                   malicious=sorted(population.malicious_ids))
    if plan.engine == "fleet":
        eng = make_engine(plan, population, mesh=mesh)
        if plan.mode == "sync":
            return _SyncFleetStepper(plan, population, state, eng)
        if plan.mixing == "buffered":
            return _BufferedFleetStepper(plan, population, state, eng)
        return _AsyncFleetStepper(plan, population, state, eng)
    return _SequentialRunner(plan, population, state)


def execute(plan: ExperimentPlan, population: Population,
            state: RunState,
            session: Optional[_ObsSession] = None) -> List[RoundRecord]:
    """Run ``plan`` over ``population``, mutating ``state`` (records are
    appended to ``state.history``; params/key/residuals/accountant advance
    in place), so follow-on `execute` calls continue the run.  With a
    health-carrying obs ``session``, the probes are polled between
    records through the stepper's ``pre_step`` hook (the same seam the
    simulation service modulates traffic through)."""
    stepper = make_stepper(plan, population, state)
    if session is not None and session.health is not None:
        def _poll(st) -> None:
            session.poll_health(st.virtual_time(), len(state.history))
        stepper.pre_step = _poll
    while not stepper.done:
        stepper.step()
    stepper.finalize()
    return state.history


def run(plan: ExperimentPlan, population: Optional[Population] = None,
        sampler=None) -> RunReport:
    """Execute a compiled plan and return a structured `RunReport`.

    ``population`` defaults to `population.materialize(plan.spec)` (the
    declarative synthetic fleet); pass one explicitly to run the plan over
    real params/data.  ``sampler`` overrides the population's declared
    participation model.

    Plans carrying a `SimSpec` route through the always-on simulation
    service (`repro.sim.SimService`) — same report, plus checkpoint/
    traffic-trace/event-timeline behaviour along the way.
    """
    if plan.spec.sim is not None:
        from ..sim import SimService     # lazy: api must not import sim
        return SimService(plan, population=population, sampler=sampler).run()
    pop = population if population is not None else materialize(plan.spec)
    if sampler is not None:
        pop = dataclasses.replace(pop, sampler=sampler)
    state = init_state(plan, pop)
    session = _ObsSession(plan)
    streamed = session.history()
    if streamed is not None:
        state.history = streamed
    try:
        with session.scope():
            records = execute(plan, pop, state, session=session)
    except BaseException:
        session.finish(None)        # flush what streamed before the crash
        raise

    comm = sum(r.comm_time for r in records)
    comp = sum(r.comp_time for r in records)
    engine_name = ("fleet-mesh" if plan.mesh_devices is not None
                   else plan.engine)
    report = RunReport(
        mode=plan.mode, engine=engine_name, records=list(records),
        kappa=async_update.communication_efficiency(comm, comp),
        epsilon_spent=(state.accountant.epsilon(plan.spec.privacy.delta)
                       if state.accountant is not None else 0.0),
        final_accuracy=records[-1].accuracy if records else 0.0,
        detections=detection_log(records),
        spec=plan.spec.to_dict(),
        net=state.net,
        final_params=state.params)
    session.finish(report)
    return report
