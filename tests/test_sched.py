"""Hierarchical weighted step scheduler (core/sched.py) — scx_flatcg.

Four claims, each load-bearing for the subsystem:

  * FLATTENING — ``cpu.weight`` hierarchies flatten exactly the way
    scx_flatcg flattens them (product of normalized weights along the
    path), recomputed at lifecycle rate, identical on every backend.
  * FAIRNESS — under a step budget, grants track flattened weights via
    vruntime (pinned golden sequences), ``cpu.max`` is a hard
    per-window throttle, and the default program IS the old binary
    slot gate (weight <= 0 bypasses the budget entirely).
  * PARITY — one schedule op sequence runs bit-identically on every
    backend kind through the conformance kit, including the live
    ``cpu.weight`` write and ``sched_boost`` retune, with the host
    reference pinned to absolute goldens so kinds cannot co-drift.
  * ZERO RETRACE — a weight write or ``sched_boost`` retune is a pure
    state write: the jitted scheduling round never recompiles
    (trace counter + jit cache size), new shares on the next step.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import domains as D
from repro.core.cgroup import (AgentCgroup, DeviceTableBackend, DomainSpec,
                               HostTreeBackend)
from repro.core.sched import (MAX_WEIGHT, MIN_WEIGHT, WeightedFairProgram,
                              check_weight, flat_weights_by_path)
from repro.testing.conformance import (BACKEND_KINDS, ConformanceSuite,
                                       backend_features, get_scenario,
                                       replay, standard_backend_factory)

SCHED_SCENARIOS = ("cpu_weight_fair", "cpu_max_quota", "sched_retune")

SUITE = ConformanceSuite()


def _wfair() -> WeightedFairProgram:
    return WeightedFairProgram(base_delay_ms=0.0, max_delay_ms=0.0)


def mk_cg(kind: str, cap: int = 500) -> AgentCgroup:
    cg = AgentCgroup(standard_backend_factory(kind)(cap, 16))
    cg.attach("/", _wfair())
    cg.mkdir("/a", DomainSpec(weight=300))
    cg.mkdir("/b", DomainSpec(weight=100))
    return cg


# ------------------------------------------------------------- flattening


def test_flat_weights_by_path_flatcg_product():
    f = flat_weights_by_path({"/": 100, "/a": 300, "/b": 100,
                              "/a/x": 100, "/a/y": 300})
    assert f["/"] == 1.0
    assert f["/a"] == 0.75 and f["/b"] == 0.25
    assert f["/a/x"] == pytest.approx(0.75 * 0.25)
    assert f["/a/y"] == pytest.approx(0.75 * 0.75)


def test_single_child_inherits_parent_flat_weight():
    f = flat_weights_by_path({"/": 100, "/t": 37, "/t/only": 9999})
    assert f["/t"] == 1.0 and f["/t/only"] == 1.0


def test_check_weight_bounds():
    assert check_weight(MIN_WEIGHT) == 1
    assert check_weight(MAX_WEIGHT) == 10000
    for bad in (0, -5, 10001):
        with pytest.raises(ValueError):
            check_weight(bad)


@pytest.mark.parametrize("kind", ["host", "device", "sharded"])
def test_cpu_weight_files_and_validation(kind):
    cg = mk_cg(kind)
    assert cg.read("/a", "cpu.weight") == 300
    assert cg.read("/b", "cpu.weight") == 100
    assert cg.read("/a", "cpu.max") == D.UNLIMITED
    with pytest.raises(ValueError):
        cg.write("/a", "cpu.weight", 0)
    with pytest.raises(ValueError):
        cg.write("/a", "cpu.weight", 10001)
    cg.write("/a", "cpu.weight", 10000)
    assert cg.read("/a", "cpu.weight") == 10000


# --------------------------------------------------------------- fairness


def test_weighted_fair_golden_sequence():
    """The worked two-tenant example (README): 300/100 weights under a
    1-slot budget grant exactly 3:1 — the pinned sequence."""
    cg = mk_cg("host")
    seq = [tuple(cg.schedule(["/a", "/b"], [1, 1], s, 1)) for s in range(8)]
    assert seq == [(True, False), (False, True), (True, False),
                   (True, False), (True, False), (False, True),
                   (True, False), (True, False)]
    assert sum(a for a, _ in seq) == 6 and sum(b for _, b in seq) == 2


def test_default_program_is_the_binary_slot_gate():
    """No program attached -> every slot's weight is <= 0 -> every
    runnable slot bypasses the budget: the pre-scheduler behavior."""
    cg = AgentCgroup(standard_backend_factory("host")(500, 16))
    cg.mkdir("/a")
    cg.mkdir("/b")
    for s in range(4):
        assert cg.schedule(["/a", "/b"], [1, 1], s, 0) == [True, True]
    cg.freeze("/a")
    assert cg.schedule(["/a", "/b"], [1, 1], 4, 0) == [False, True]


def test_cpu_max_window_throttle_and_rollover():
    cg = AgentCgroup(standard_backend_factory("host")(500, 16))
    cg.attach("/", _wfair())
    cg.mkdir("/t")
    cg.mkdir("/t/a", DomainSpec(cpu_max=3))
    adv = [cg.schedule(["/t/a"], [1], s, 8)[0] for s in range(6)]
    assert adv == [True, True, True, False, False, False]
    # next window (sched_window=100): quota restored
    assert cg.schedule(["/t/a"], [1], 100, 8) == [True]


def test_cpu_max_applies_to_descendants():
    """The quota is hierarchical: a child's advance charges the capped
    ancestor's window account."""
    cg = AgentCgroup(standard_backend_factory("host")(500, 16))
    cg.attach("/", _wfair())
    cg.mkdir("/t", DomainSpec(cpu_max=2))
    cg.mkdir("/t/kid")
    adv = [cg.schedule(["/t/kid"], [1], s, 8)[0] for s in range(4)]
    assert adv == [True, True, False, False]


def test_empty_slots_never_advance():
    cg = mk_cg("host")
    assert cg.schedule([], [], 0, 4) == []
    view_seq = cg.schedule(["/a"], [1], 0, 1)
    assert view_seq == [True]


# ----------------------------------------------------------------- parity


@pytest.mark.parametrize("kind", BACKEND_KINDS)
def test_sched_conformance(kind):
    """The acceptance loop: the scheduler scenarios — weight writes,
    cpu.max quotas, live sched_boost retunes, freeze/thaw — replay
    bit-identically on every backend kind."""
    report = SUITE.run(standard_backend_factory(kind),
                       features=backend_features(kind),
                       scenarios=SCHED_SCENARIOS)
    assert report.ok, report.summary()


def test_sched_scenarios_absolute_goldens():
    """Pin the reference streams to absolute values so the six kinds
    cannot drift together."""
    host = standard_backend_factory("host")

    sc = get_scenario("cpu_weight_fair")
    obs = replay(AgentCgroup(host(sc.capacity, sc.n_domains)), sc)
    sched = [v for _, n, v in obs if n == "schedule"]
    assert sched[:8] == [(True, False), (False, True), (True, False),
                         (True, False), (True, False), (False, True),
                         (True, False), (True, False)]
    # after the live /b cpu.weight 100 -> 300 write: equal shares,
    # vruntime carried over (no reset on reweight)
    assert sched[8:] == [(True, False), (False, True)] * 4
    reads = [v[2] for _, n, v in obs if n == "read"]
    assert reads == [300, 100, D.UNLIMITED, 300]

    sc = get_scenario("cpu_max_quota")
    obs = replay(AgentCgroup(host(sc.capacity, sc.n_domains)), sc)
    sched = [v for _, n, v in obs if n == "schedule"]
    assert sched == [(True, True)] * 3 + [(False, True)] * 3 \
        + [(True, True)] * 2
    assert [v[2] for _, n, v in obs if n == "read"] == [3]

    sc = get_scenario("sched_retune")
    obs = replay(AgentCgroup(host(sc.capacity, sc.n_domains)), sc)
    sched = [v for _, n, v in obs if n == "schedule"]
    # equal weights alternate; sched_boost=2.0 on /a (x4) shifts to 4:1;
    # freeze removes /a from the runnable set; thaw brings it back with
    # lag-clamped vruntime (it does NOT return with unbounded credit)
    assert sched[:4] == [(True, False), (False, True)] * 2
    assert sched[4:14] == [(True, False), (False, True), (True, False),
                           (True, False), (True, False), (True, False),
                           (False, True), (True, False), (True, False),
                           (True, False)]
    assert sched[14:17] == [(False, True)] * 3
    assert sched[17:] == [(True, False)] * 3


def test_device_inkernel_schedule_matches_host():
    """The in-step entry point (DeviceView.schedule, what the engine
    jits) agrees step for step with the host facade path."""
    cg_h = mk_cg("host")
    cg_d = mk_cg("device")
    view = cg_d.device_view()
    dom = jnp.array([cg_d.handle("/a"), cg_d.handle("/b")], jnp.int32)
    cost = jnp.array([1, 1], jnp.int32)
    for s in range(12):
        want = cg_h.schedule(["/a", "/b"], [1, 1], s, 1)
        st, adv = view.schedule(view.state, dom, cost, s, 1)
        view.commit(st)
        assert [bool(x) for x in np.asarray(adv)] == want, s


# ------------------------------------------------------------ zero retrace


def test_weight_and_boost_retune_zero_retrace():
    """The adaptability pillar, scheduler edition: a live cpu.weight
    write and a sched_boost retune are param/state writes — the jitted
    scheduling round is NOT retraced, and the new shares apply from the
    very next step."""
    cg = mk_cg("device")
    view = cg.device_view()
    traces = 0

    def sched(state, dom, cost, step):
        nonlocal traces
        traces += 1
        return view.schedule(state, dom, cost, step, 1)

    jsched = jax.jit(sched)
    dom = jnp.array([cg.handle("/a"), cg.handle("/b")], jnp.int32)
    cost = jnp.array([1, 1], jnp.int32)

    def rounds(steps):
        a = b = 0
        for s in steps:
            st, adv = jsched(view.state, dom, cost, s)
            view.commit(st)
            ga, gb = np.asarray(adv)
            a, b = a + int(ga), b + int(gb)
        return a, b

    assert rounds(range(8)) == (6, 2)            # 300/100 -> 3:1

    cg.write("/a", "cpu.weight", 100)            # live reweight: 1:1
    cg.update_params("/b", sched_boost=2.0)      # live boost: /b x4
    a, b = rounds(range(8, 28))
    assert b > a and b >= 15                     # ~4:1 the other way
    assert traces == 1                           # never retraced
    assert jsched._cache_size() == 1


# ----------------------------------------------------------------- engine


def test_engine_sched_slots_weighted_completion_order():
    """Engine-level acceptance: with ``sched_slots`` set and a 4:1
    cpu.weight split, the heavy tenant's identical workload finishes
    first; both still complete (no starvation — vruntime fairness)."""
    from repro.configs import get_config, reduced
    from repro.models import model as M
    from repro.models.schema import init_params
    from repro.perf import DEFAULT_PERF, replace as perf_replace
    from repro.serving.engine import Engine, EngineConfig
    from repro.serving.session import Phase, Session, SState

    cfg = dataclasses.replace(reduced(get_config("llama3.2-3b")),
                              dtype="float32")
    params = init_params(M.param_schema(cfg), jax.random.PRNGKey(0),
                         cfg.dtype)
    eng = Engine(cfg, params, perf=perf_replace(DEFAULT_PERF, scan_chunk=32),
                 ecfg=EngineConfig(max_slots=2, s_max=128, pool_pages=64,
                                   page_tokens=16, mode="inkernel",
                                   use_freeze=False, sched_slots=1), seed=0)
    eng.attach_program(_wfair())

    def sess(sid, tenant):
        return Session(sid=sid, tenant=tenant, priority=D.NORMAL,
                       prompt=list(range(2, 10)),
                       phases=[Phase(6, 8, "test"), Phase(6, 0)])

    eng.submit(sess("hi", "ta"))
    eng.submit(sess("lo", "tb"))
    eng.cg.write("/ta", "cpu.weight", 400)
    eng.cg.write("/tb", "cpu.weight", 100)
    eng.run(400)
    hi, lo = eng.sessions["hi"], eng.sessions["lo"]
    assert hi.state is SState.DONE and lo.state is SState.DONE
    assert hi.t_done < lo.t_done
    assert hi.stall_steps < lo.stall_steps


# --------------------------------------------- 8-fake-device subprocess

_SCHED_8DEV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.core.cgroup import AgentCgroup, DomainSpec
from repro.core.sched import WeightedFairProgram
from repro.testing.conformance import (ConformanceSuite, backend_features,
                                       standard_backend_factory)

assert len(jax.devices()) == 8

# 1) the scheduler scenarios on a real 8-shard mesh — /a and /b land on
# DIFFERENT shards, so the flattened weights and the global vruntime
# ranking must come out identical to the single-tree host reference
suite = ConformanceSuite()
for kind in ("sharded", "async-sharded"):
    report = suite.run(standard_backend_factory(kind),
                       features=backend_features(kind),
                       scenarios=("cpu_weight_fair", "cpu_max_quota",
                                  "sched_retune"))
    assert report.ok, report.summary()

# 2) cross-shard fairness: 8 tenants on 8 shards, weights 100..800,
# shares under a 1-slot budget track the weights (heaviest >= lightest)
cg = AgentCgroup(standard_backend_factory("sharded")(800, 16))
assert cg.backend.n_shards == 8
cg.attach("/", WeightedFairProgram(base_delay_ms=0.0, max_delay_ms=0.0))
paths = []
for t in range(8):
    cg.mkdir(f"/t{t}", DomainSpec(weight=100 * (t + 1)))
    paths.append(f"/t{t}")
grants = [0] * 8
for s in range(72):
    adv = cg.schedule(paths, [1] * 8, s, 1)
    for i, a in enumerate(adv):
        grants[i] += int(a)
assert sum(grants) == 72
assert grants == sorted(grants), grants          # monotone in weight
assert grants[-1] >= 3 * grants[0], grants       # 800 vs 100
print("SCHED8 OK")
"""


def test_sched_parity_on_8_fake_devices():
    env = dict(os.environ)
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root])
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _SCHED_8DEV], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0 and "SCHED8 OK" in out.stdout, \
        out.stderr[-3000:]
