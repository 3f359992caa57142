"""The fleet round's stages on the profiler's clock.

Device side: every stage of the sync round (one device and sharded) and
of the async window lowers to ops whose HLO `op_name` metadata carries
the stage's `jax.named_scope`.  Host side: the stage spans land in a
`jax.profiler` trace with no `ObsSpec` at all, nested in their record's
``round`` span, and nothing is fenced; Python's collections show as
``py.gc`` spans."""
import gc
import glob
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro import api, obs
from repro.api.population import materialize
from repro.fleet import stages

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ROUND_SCOPES = {stages.LOCAL_SGD, stages.UPLOAD, stages.CLOUD_SCORE,
                stages.FOLD}


def _spec(**kw):
    base = dict(
        fleet=api.FleetSpec(n_nodes=4, samples_per_node=20, n_test=32,
                            n_cloud_test=16),
        schedule=api.SchedulePolicy(kind="sync"),
        privacy=api.PrivacySpec(sigma=0.05),
        compression=api.CompressionSpec(sparsify_ratio=0.5),
        defense=api.DefenseSpec(detect=True),
        network=api.NetworkSpec(codec="sparse_coo"),
        train=api.TrainSpec(local_steps=2, batch_size=8, lr=0.1),
        rounds=2, seed=0)
    base.update(kw)
    return api.ExperimentSpec(**base)


def _stepper(spec):
    plan = api.compile_plan(spec)
    pop = materialize(plan.spec)
    return api.make_stepper(plan, pop, api.init_state(plan, pop))


def scopes_of(jitted, *args) -> set:
    """The stages the compiled program's ops are put to: the first fleet
    scope of each op's `op_name` path, as a profile's reader takes it (a
    scoped jit called inside another stage counts to the outer one).
    Reducer bodies (``to_apply``) are left out: they run inside their
    reduce, never as ops of their own, and keep only the inner path."""
    hlo = jitted.lower(*args).compile().as_text()
    reducers = set(re.findall(r"to_apply=%([\w.\-]+)", hlo))
    found, comp = set(), None
    for line in hlo.splitlines():
        if line and not line[0].isspace():
            m = re.match(r"(?:ENTRY )?%([\w.\-]+) ", line)
            comp = m.group(1) if m else None
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        if m is None or comp in reducers:
            continue
        found.add(next((part for part in m.group(1).split("/")
                        if part.startswith("fleet.")), None))
    return found - {None}


def test_sync_round_and_evaluation_carry_their_scopes():
    eng = _stepper(_spec()).eng
    idx, valid = eng.sampler.cohort(0, eng.n_nodes)
    st = eng.state
    assert scopes_of(eng._round_fn, eng.params, st.residuals, st.chain_key,
                     st.trust, st.throttle, eng.data.x, eng.data.y,
                     eng.data.sizes, jnp.asarray(idx, jnp.int32),
                     jnp.asarray(valid)) == ROUND_SCOPES
    assert scopes_of(eng.acc_fn, eng.params,
                     *eng.test_data) == {stages.EVALUATE}


def test_async_window_carries_the_round_scopes():
    eng = _stepper(_spec(schedule=api.SchedulePolicy(kind="async"))).eng
    order, proc = eng.select_window()
    assert scopes_of(eng._window_fn, eng.params, eng.state, eng.data.x,
                     eng.data.y, eng.data.sizes,
                     jnp.asarray(order, jnp.int32), jnp.asarray(proc),
                     jnp.ones(order.size, bool),
                     jnp.zeros(order.size, jnp.float32)) == ROUND_SCOPES


def test_sharded_round_carries_the_round_scopes_on_4_devices():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import json, sys
        import jax, jax.numpy as jnp
        sys.path.insert(0, {tests!r})
        from repro import api
        from test_obs_profiler import _spec, _stepper, scopes_of

        eng = _stepper(_spec(fleet=api.FleetSpec(
            n_nodes=8, samples_per_node=20, n_test=32, n_cloud_test=16),
            topology=api.Topology(kind="mesh", devices=4))).eng
        idx, valid = eng.sampler.cohort(0, eng.n_nodes)
        up = eng.mesh.put_nodes(jnp.asarray(
            eng._participation_mask(idx, valid)))
        st = eng.state
        found = scopes_of(eng._round_fn, eng.params, st.residuals,
                          st.chain_key, st.trust, st.throttle, eng.data.x,
                          eng.data.y, eng.data.sizes, up, *eng.cloud_test)
        print(json.dumps({{"devices": len(jax.devices()),
                          "engine": type(eng).__name__,
                          "mesh": eng.mesh.n_devices,
                          "scopes": sorted(found)}}))
    """).format(tests=os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)          # the child forces its own devices
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4 and out["mesh"] == 4
    assert set(out["scopes"]) == ROUND_SCOPES


# ---------------------------------------------------------------------------
# host spans in a profile
# ---------------------------------------------------------------------------

def host_events(log_dir: str) -> list:
    """(name, start_ns, end_ns, stats) of every host event in a profile."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_stage_spans_land_in_a_profile_unfenced(tmp_path, monkeypatch):
    stepper = _stepper(_spec())
    stepper.step()                      # compiles outside the profile
    assert not obs.get_tracer().enabled
    monkeypatch.setattr(obs.timers, "fence", lambda x: pytest.fail(
        "a stage fenced with stage timings off"))
    with jax.profiler.trace(str(tmp_path)):
        stepper.step()
        stepper.step()
    evs = host_events(str(tmp_path))
    rounds = [e for e in evs if e[0] == "round"]
    assert [e[3]["round"] for e in rounds] == [1, 2]
    names = ("stage.round.device", "stage.round.readback", "stage.net.draw",
             "stage.net.commit", "stage.round.evaluate",
             "stage.round.account", "stage.record.accountant")
    for name in names:
        spans = [e for e in evs if e[0] == name]
        assert len(spans) == 2, name
        for _, s, e, _ in spans:
            assert any(r[1] <= s and e <= r[2] for r in rounds), name


def test_gc_spans_name_each_collection(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with obs.GcSpans() as g:
            gc.collect()
        gc.collect()                    # closed: no span
    assert g._callback not in gc.callbacks
    spans = [e for e in host_events(str(tmp_path)) if e[0] == "py.gc"]
    # one full collection inside the block, none from the one after it
    assert [int(e[3]["generation"]) for e in spans].count(2) == 1
    assert all(e[2] > e[1] for e in spans)
