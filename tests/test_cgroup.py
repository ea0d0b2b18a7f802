"""The unified cgroupfs-style control plane (core/cgroup.py).

Backend parity is the point of the facade — and since PR 5 the parity
machinery lives in ``repro.testing.conformance``: one declarative
scenario set replayed against every ``Backend`` (host tree /
single-device table / sharded multi-device table / async lifecycle
daemon over each) and diffed against the reference host semantics.
This module certifies all standard backend kinds through that kit,
pins the canonical scenario to absolute golden values (so reference
and backends cannot drift together), and keeps the backend-specific
extras: facade-clock throttle expiry, sharded tenant placement, and
the 8-fake-device subprocess run.
"""
import os
import subprocess
import sys

import pytest

from repro.core.cgroup import (AgentCgroup, DeviceTableBackend, DomainSpec,
                               HostTreeBackend, ancestor_paths, parent_path)
from repro.core.controller import ControllerConfig
from repro.testing.conformance import (BACKEND_KINDS, ConformanceSuite,
                                       OpRecorder, backend_features,
                                       get_scenario, replay,
                                       standard_backend_factory)

# one suite for the whole module: reference observations are computed
# once per scenario and reused across every parametrized backend kind
SUITE = ConformanceSuite()


@pytest.mark.parametrize("kind", BACKEND_KINDS)
def test_backend_conformance(kind):
    """THE acceptance loop: every backend kind — including the async
    daemon over each inner backend — certifies itself against the full
    standard scenario set, bit-identically to the reference."""
    report = SUITE.run(standard_backend_factory(kind),
                       features=backend_features(kind))
    assert report.ok, report.summary()


def test_lifecycle_scenario_absolute_goldens():
    """Pin the canonical op sequence to absolute values (kit runs are
    relative to the reference; this guards against co-drift)."""
    sc = get_scenario("lifecycle")
    obs = replay(AgentCgroup(standard_backend_factory("host")(
        sc.capacity, sc.n_domains)), sc)
    grants = [v[0] for _, n, v in obs if n == "charge"]
    assert grants == [True, True, False, True, False, True, False]
    residual = [v for _, n, v in obs if n == "rmdir"]
    assert residual == [65]
    usage = {p: u for _, n, (p, u) in
             ((i, n, v) for i, n, v in obs if n == "usage")}
    assert usage == {"/": 255, "/t": 255, "/t/a": 55, "/t/b": 200}
    peak = {p: u for _, n, (p, u) in
            ((i, n, v) for i, n, v in obs if n == "peak")}
    assert peak == {"/": 285, "/t": 285, "/t/a": 85, "/t/b": 200}


def test_memcg_events_scenario_absolute_goldens():
    """The events scenario is host-vs-host for the 'host' kind, so pin
    the counters to absolute values here (a DomainTree accounting
    regression must not pass as trivial self-parity)."""
    sc = get_scenario("memcg_events")
    obs = replay(AgentCgroup(standard_backend_factory("host")(
        sc.capacity, sc.n_domains)), sc)
    events = [v[2] for _, n, v in obs if n == "read"]
    assert events == [{"high": 1, "max": 1, "throttle": 1, "oom_kill": 0}]
    charges = [v for _, n, v in obs if n == "charge"]
    assert charges == [(True, False, 110.0),     # over-high: 10*(1+10*1.0)
                       (False, True, 100.0)]     # max wall inside window


def test_recorder_roundtrips_to_replayable_scenario():
    """Drive a live cg through the recorder; the recorded scenario
    replays to identical observations on a fresh backend."""
    rec = OpRecorder(AgentCgroup(HostTreeBackend(500)))
    rec.mkdir("/s")
    rec.mkdir("/s/tool", high=40)
    rec.try_charge("/s/tool", 30, step=0)
    rec.write("/s/tool", "memory.high", 20)
    rec.try_charge("/s/tool", 5, step=1)
    rec.rmdir("/s/tool")
    rec.read("/s", "memory.current")
    sc = rec.to_scenario("recorded")
    a = replay(AgentCgroup(HostTreeBackend(500)), sc)
    b = replay(AgentCgroup(DeviceTableBackend(500, n_domains=8)), sc)
    # the full event stream includes host-only breach/throttle kinds;
    # everything else (including the portable lifecycle stream) matches
    a = [r for r in a if r[1] != "events_all"]
    b = [r for r in b if r[1] != "events_all"]
    assert a == b


@pytest.mark.parametrize("kind", BACKEND_KINDS)
def test_mkdir_requires_parent(kind):
    cg = AgentCgroup(standard_backend_factory(kind)(500, 16))
    with pytest.raises(FileNotFoundError):
        cg.mkdir("/nope/child")


def test_read_write_file_validation():
    cg = AgentCgroup(HostTreeBackend(500))
    cg.mkdir("/s")
    with pytest.raises(AssertionError):
        cg.read("/s", "not.a.file")
    with pytest.raises(AssertionError):
        cg.write("/s", "memory.current", 3)      # read-only


def test_host_driven_throttle_expires_with_facade_clock():
    """A device-backend charge with no explicit step uses the facade
    clock, so an over-``high`` throttle expires instead of pinning all
    later host-driven charges at step 0."""
    cg = AgentCgroup(DeviceTableBackend(500, n_domains=8,
                                        cfg=ControllerConfig()))
    cg.mkdir("/s", DomainSpec(high=10))
    assert cg.try_charge("/s", 20).granted       # over high -> throttled
    assert not cg.try_charge("/s", 1).granted    # still step 0: denied
    cg.set_time(10_000)
    assert cg.try_charge("/s", 1).granted        # throttle expired


def test_path_helpers():
    assert parent_path("/") is None
    assert parent_path("/a") == "/"
    assert parent_path("/a/b/c") == "/a/b"
    assert ancestor_paths("/a/b") == ["/a/b", "/a", "/"]


# ------------------------------------------------------- sharded backend


def mk_sharded(cap: int = 500) -> AgentCgroup:
    return AgentCgroup(standard_backend_factory("sharded")(cap, 16))


def test_sharded_tenant_placement_round_robin():
    """Each tenant subtree lands on its own shard; descendants (sessions,
    tool leases) inherit it — the device-group placement rule."""
    from repro.core.intent import Hint
    cg = mk_sharded()
    be = cg.backend
    for t in range(3):
        cg.mkdir(f"/t{t}")
        cg.mkdir(f"/t{t}/sess")
        lease = cg.intent.declare("tool", Hint.LOW, parent=f"/t{t}/sess")
        shard = be.index[f"/t{t}"][0]
        assert be.index[f"/t{t}/sess"][0] == shard
        assert be.index[lease.path][0] == shard
        lease.close()
    # with one local device everything collapses to shard 0; the true
    # round-robin spread is asserted in the 8-fake-device subprocess test
    assert set(be.placement()) == {"/t0", "/t1", "/t2"}


def test_sharded_device_view_global_handles():
    """The in-step view takes global handles and routes each request to
    the owning shard's table, flat results back."""
    import jax.numpy as jnp
    import numpy as np
    cg = mk_sharded(cap=100)
    cg.mkdir("/t0")
    h = cg.mkdir("/t0/s", DomainSpec(max=30))
    view = cg.device_view()
    dom = jnp.array([h, -1], jnp.int32)
    st, granted, stalled = view.charge(view.state, dom,
                                       jnp.array([10, 5], jnp.int32), 0)
    view.commit(st)
    assert list(np.asarray(granted)) == [True, False]
    assert cg.usage("/t0/s") == 10 and cg.usage("/") == 10
    st, granted, _ = view.charge(view.state, dom,
                                 jnp.array([25, 0], jnp.int32), 1)
    view.commit(st)
    assert list(np.asarray(granted)) == [False, False]    # max=30 wall
    assert list(np.asarray(view.gate(view.state, dom, 2))) == [True, False]
    view.commit(view.uncharge(view.state, dom, jnp.array([10, 0], jnp.int32)))
    assert cg.usage("/") == 0


_SHARDED_8DEV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.core.cgroup import AgentCgroup
from repro.testing.conformance import (ConformanceSuite, backend_features,
                                       standard_backend_factory)

assert len(jax.devices()) == 8

# 1) the full conformance set on a real 8-shard mesh — including the
# async daemon over the sharded backend, and the token-bucket scenario
# whose tenants land on shards > 0
suite = ConformanceSuite()
for kind in ("sharded", "async-sharded"):
    report = suite.run(standard_backend_factory(kind),
                       features=backend_features(kind))
    assert report.ok, report.summary()

# 2) tenants spread round-robin over distinct shards; root reconciles
cg = AgentCgroup(standard_backend_factory("sharded")(800, 16))
assert cg.backend.n_shards == 8
for t in range(8):
    cg.mkdir(f"/t{t}")
    assert cg.try_charge(f"/t{t}", 10 * (t + 1)).granted
assert sorted(cg.backend.placement().values()) == list(range(8))
assert cg.usage("/") == sum(10 * (t + 1) for t in range(8))

# 3) global root capacity enforced across shards host-side
assert not cg.try_charge("/t0", 800).granted
print("SHARDED8 OK")
"""


def test_sharded_parity_on_8_fake_devices():
    env = dict(os.environ)
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root])
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _SHARDED_8DEV], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0 and "SHARDED8 OK" in out.stdout, \
        out.stderr[-3000:]
