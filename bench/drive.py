"""The system under test, driven through the path `api.run` drains:
`compile_plan` -> `init_state` -> `make_stepper` -> `step()` ...

`build` turns a cell's configuration and traffic into an `ExperimentSpec`
(the spec builders of the repo's `chip_smoke.py`, copied here so that a
change there cannot move the yardstick) and a `Population` on the
benchmark's own inputs.  `check_records` drives the stepper through its
first records, the ones the reference follows, and keeps host copies of
the params.  `window` then drives the same stepper, closed loop, for a
fixed time: each record starts when the previous `step()` returns, and
nothing waits on the device until the window's end."""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import List, Optional

from .cells import model_module
from .check import Readings
from .population import Inputs

# never `done` inside a window: the stepper's record budget
ROUNDS = 10 ** 6
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
CACHE_EVENTS = COMPILE_EVENTS + ("/jax/compilation_cache/cache_hits",
                                 "/jax/compilation_cache/cache_misses")


def build_spec(config: dict, traffic: dict, seed: int):
    """The cell's `ExperimentSpec`: the paper's ALDPFL setting
    (`chip_smoke.paper_spec`) with the sizes of `config`, the model's own
    `FleetSpec` fields (its module's `spec_fields`) and the schedule and
    node profile of `traffic`, on one chip."""
    from repro import api
    return api.ExperimentSpec(
        fleet=api.FleetSpec(
            n_nodes=config["n_nodes"],
            samples_per_node=config["samples_per_node"],
            n_test=config["n_test"], n_cloud_test=config["n_cloud_test"],
            profile=api.NodeHeterogeneity(**traffic["profile"]),
            **model_module(config).spec_fields(config)),
        schedule=api.SchedulePolicy(kind=traffic["schedule"],
                                    alpha=config["alpha"]),
        privacy=api.PrivacySpec(sigma=config["sigma"],
                                clip_s=config["clip_s"]),
        compression=api.CompressionSpec(
            sparsify_ratio=config["sparsify_ratio"]),
        defense=api.DefenseSpec(detect=True, detect_s=config["detect_s"],
                                detect_warmup=config["detect_warmup"]),
        network=api.NetworkSpec(codec=config["codec"]),
        topology=api.Topology(kind="single", backend=config["backend"]),
        train=api.TrainSpec(local_steps=config["local_steps"],
                            batch_size=config["batch_size"],
                            lr=config["lr"]),
        rounds=ROUNDS, seed=int(seed))


def population(config: dict, inputs: Inputs):
    """The program's `Population` over the benchmark's inputs, with the
    model's `loss_fn` and `acc_fn` (its module's `program_fns`) and the
    fields its module's `population_fields` gives, which carry the
    model's own arrays (`inputs.extra`).  A field that is set here for
    every model (`params`, say) raises, naming it."""
    from repro import api
    from repro.fleet import NodeProfile
    model = model_module(config)
    loss_fn, acc_fn = model.program_fns()
    fields = dict(
        params=inputs.params, loss_fn=loss_fn, acc_fn=acc_fn,
        node_data=[(inputs.x[i], inputs.y[i])
                   for i in range(inputs.x.shape[0])],
        test_data=inputs.test, cloud_test=inputs.cloud,
        profile=NodeProfile(compute_s=inputs.compute_s,
                            bandwidth_bps=inputs.bandwidth_bps),
        malicious_ids=tuple(inputs.malicious))
    own = model.population_fields(config, inputs)
    clash = sorted(set(own) & set(fields))
    if clash:
        raise ValueError(f"model {config['model']!r}: population_fields "
                         f"sets {clash}, which every model's Population "
                         "already sets")
    return api.Population(**fields, **own)


@dataclasses.dataclass
class System:
    state: object           # api.RunState: params and history
    stepper: object

    def params_host(self):
        import jax
        return jax.device_get(self.state.params)


def build(config: dict, traffic: dict, seed: int, inputs: Inputs) -> System:
    from repro import api
    plan = api.compile_plan(build_spec(config, traffic, seed))
    pop = population(config, inputs)
    state = api.init_state(plan, pop)
    return System(state, api.make_stepper(plan, pop, state))


def check_records(system: System, n: int, phases: Optional[dict] = None,
                  t_start: float = 0.0) -> Readings:
    """Drive the first `n` records through the window's own `step()`.
    These records compile every program the window runs.  `phases` gets
    each record's end, in seconds from `t_start`."""
    params, acc, rej, nbytes = [], [], [], []
    for i in range(n):
        system.stepper.step()
        if phases is not None:
            phases[f"record{i + 1}"] = time.perf_counter() - t_start
        rec = system.state.history[-1]
        params.append(system.params_host())
        acc.append(float(rec.accuracy))
        rej.append(int(rec.n_rejected))
        nbytes.append(float(rec.comm_bytes))
    return Readings(params, acc, rej, nbytes)


class CompileCounter:
    """Counts JAX's compile events (by default traces and backend
    compiles) while it is open."""

    def __init__(self, names=COMPILE_EVENTS):
        self.names = names
        self.events: List[str] = []

    def _listen(self, event, *args, **kwargs):
        if event in self.names:
            self.events.append(event)

    def counts(self) -> dict:
        return {e.rsplit("/", 1)[-1]: self.events.count(e)
                for e in self.names}

    def __enter__(self):
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._listen)
        mon.register_event_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._listen)
        mon.unregister_event_listener(self._listen)


@dataclasses.dataclass
class Window:
    records: int
    seconds: float              # first step() call to the final fence
    compiles: int


def window(system: System, seconds: float) -> Window:
    """Closed loop for `seconds`: no fence per record, one
    `block_until_ready` at the end, whose wait the window includes."""
    import jax
    from jax.profiler import TraceAnnotation
    step = system.stepper.step
    records = 0
    with CompileCounter() as cc, TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            with TraceAnnotation("bench.step"):
                step()
            records += 1
            if time.perf_counter() - t0 >= seconds:
                break
        with TraceAnnotation("bench.fence"):
            jax.block_until_ready(system.state.params)
        total = time.perf_counter() - t0
    return Window(records, total, len(cc.events))


@contextlib.contextmanager
def profiled(log_dir: Optional[str]):
    """The JAX profiler around the block, host spans kept and the Python
    tracer off; a no-op when `log_dir` is None."""
    if log_dir is None:
        yield
        return
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
