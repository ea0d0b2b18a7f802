"""Per-tenant concurrent policy programs.

Three claims, each with its own failure mode the older single-program
control plane could not express:

  * MIXED PARITY — two tenants running *different* programs
    (graduated throttle vs token bucket) in one hierarchy replay
    bit-identically on every backend kind, including the real 8-shard
    mesh (subprocess, like the sharded parity test in test_cgroup).
  * SLOT RETUNE — ``update_params`` on a mixed registry resolves each
    path through its own program's parameter columns and stays a pure
    state write: zero retraces across retunes of *both* slots.
  * SATURATION — the PSI stall accumulators saturate at INT32_MAX
    instead of wrapping negative, on the device path, the gathered
    scheduler path, and the host tree (the satellite bugfix).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cgroup import (AgentCgroup, DeviceTableBackend, DomainSpec,
                               HostTreeBackend)
from repro.core.pressure import INT32_MAX, saturating_count
from repro.core.progs import GraduatedThrottleProgram, TokenBucketProgram
from repro.core.sched import schedule_decision
from repro.testing.conformance import (BACKEND_KINDS, get_scenario, replay,
                                       standard_backend_factory)

# ------------------------------------------------------------ mixed parity

# reference observations for the mixed-program golden, computed once
_REF = {}


def _mixed_obs(kind: str) -> list:
    sc = get_scenario("multi_program")
    cg = AgentCgroup(standard_backend_factory(kind)(sc.capacity,
                                                    sc.n_domains))
    return [o for o in replay(cg, sc) if o[1] != "events_all"]


@pytest.mark.parametrize("kind", BACKEND_KINDS)
def test_mixed_programs_bit_parity_all_kinds(kind):
    """Two tenants on different programs (graduated vs token bucket),
    attach composed at runtime, children inheriting the parent's
    registry slot: bit-identical on every backend kind."""
    if "ref" not in _REF:
        _REF["ref"] = _mixed_obs("host")
    assert _mixed_obs(kind) == _REF["ref"]


def test_mixed_programs_absolute_goldens():
    """Pin the mixed-program scenario to absolute values (kit runs are
    relative to the reference; this guards against co-drift): the
    bucket tenant rate-limits, the graduated tenant throttles, and
    each per-slot retune lands only on its own tenant."""
    obs = _REF.get("ref") or _mixed_obs("host")
    charges = [v for _, n, v in obs if n == "charge"]
    assert charges == [
        (False, True, 0.0),      # /bkt/s 6@0: bucket holds only 4
        (True, False, 0.0),      # /bkt/s 3@0: within the bucket
        (True, False, 110.0),    # /grad/s 20@0: over 1.0 -> 10*(1+10)
        (False, True, 100.0),    # /grad/s 1@1: inside the window
        (True, False, 0.0),      # /bkt/s 30@5: retuned bucket holds 50
        (True, False, 0.0),      # /grad/s 1@200: delays retuned off
    ]
    usage = {p: u for _, n, (p, u) in
             ((i, n, v) for i, n, v in obs if n == "usage")}
    assert usage == {"/": 54, "/grad": 21, "/bkt": 33}


_MIXED_8DEV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
assert len(jax.devices()) == 8
from repro.core.cgroup import AgentCgroup
from repro.testing.conformance import (get_scenario, replay,
                                       standard_backend_factory)

# the mixed-program golden on a real 8-shard mesh, vs the host reference
sc = get_scenario("multi_program")
ref = replay(AgentCgroup(standard_backend_factory("host")(
    sc.capacity, sc.n_domains)), sc)
got = replay(AgentCgroup(standard_backend_factory("sharded")(
    sc.capacity, sc.n_domains)), sc)
drop = lambda obs: [o for o in obs if o[1] != "events_all"]
assert drop(got) == drop(ref)

# the two tenants really live on different shards (round-robin), so the
# registry dispatch crosses shard boundaries, not just table rows
cg = AgentCgroup(standard_backend_factory("sharded")(
    sc.capacity, sc.n_domains))
cg.attach("/", __import__("repro.core.progs", fromlist=["x"])
          .GraduatedThrottleProgram())
cg.mkdir("/grad"); cg.mkdir("/bkt")
place = cg.backend.placement()
assert place["/grad"] != place["/bkt"], place
print("MIXED8 OK")
"""


def test_mixed_programs_on_8_fake_devices():
    env = dict(os.environ)
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(root, "src"), root])
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _MIXED_8DEV], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0 and "MIXED8 OK" in out.stdout, \
        out.stderr[-3000:]


# ------------------------------------------------------- per-slot retune


def test_update_params_zero_retrace_per_program_slot():
    """Retuning either slot of a mixed registry is a pure param-table
    write: the jitted charge function compiles once (lax.switch over
    both programs) and is never retraced."""
    cg = AgentCgroup(DeviceTableBackend(10_000, n_domains=8))
    cg.attach("/", GraduatedThrottleProgram())
    cg.mkdir("/grad", DomainSpec(high=10))
    cg.mkdir("/bkt")
    cg.attach("/bkt", TokenBucketProgram(bucket_capacity=4,
                                         refill=(1.0, 1.0, 1.0)))
    assert len(cg.programs) == 2
    view = cg.device_view()
    traces = 0

    def charge(state, dom, amt, step):
        nonlocal traces
        traces += 1
        return view.charge(state, dom, amt, step)

    jcharge = jax.jit(charge)
    dom = jnp.array([cg.handle("/grad"), cg.handle("/bkt")], jnp.int32)
    st, g, _ = jcharge(view.state, dom, jnp.array([20, 6], jnp.int32), 0)
    view.commit(st)
    assert bool(g[0]) and not bool(g[1])       # bucket holds only 4

    # slot 1 retune: only the bucket tenant sees the new capacity
    cg.update_params("/bkt", bucket_capacity=50.0, bucket_level=50.0)
    st, g, _ = jcharge(view.state, dom, jnp.array([0, 30], jnp.int32), 50)
    view.commit(st)
    assert bool(g[1])

    # slot 0 retune: only the graduated tenant sees the flat curve
    cg.update_params("/grad", base_delay_ms=0.0, max_delay_ms=0.0)
    st, g, _ = jcharge(view.state, dom, jnp.array([1, 0], jnp.int32), 200)
    view.commit(st)
    assert bool(g[0])

    assert traces == 1                         # never retraced
    assert jcharge._cache_size() == 1


# ------------------------------------------------------------- saturation


def test_saturating_count_boundary():
    """The traced helper itself: at the boundary the counter pins to
    INT32_MAX instead of wrapping negative (i32 overflow is UB-shaped
    on device: silent wrap)."""
    c = saturating_count(jnp.int32(INT32_MAX - 1), jnp.int32(1))
    assert int(c) == INT32_MAX
    c = saturating_count(c, jnp.int32(1))
    assert int(c) == INT32_MAX
    c = saturating_count(jnp.int32(INT32_MAX), jnp.int32(INT32_MAX))
    assert int(c) == INT32_MAX
    assert int(saturating_count(jnp.int32(5), jnp.int32(0))) == 5


def test_mem_stall_saturates_on_device_path():
    """Regression for the wrap bug: a domain one event below INT32_MAX
    takes two more denials and stays pinned (the unpatched accumulator
    went negative on the second)."""
    cg = AgentCgroup(DeviceTableBackend(10_000, n_domains=8))
    cg.mkdir("/s", DomainSpec(max=10))
    view = cg.device_view()
    idx = cg.handle("/s")
    st = dict(view.state)
    st["mem_stall"] = st["mem_stall"].at[idx].set(INT32_MAX - 1)
    dom = jnp.array([idx], jnp.int32)
    for step in (0, 1):
        st, g, stalled = view.charge(st, dom,
                                     jnp.array([100], jnp.int32), step)
        assert not bool(g[0]) and bool(stalled[0])
        assert int(st["mem_stall"][idx]) == INT32_MAX


def test_cpu_stall_saturates_with_gathered_slots():
    """The scheduler gathers per-round increments before saturating:
    two frozen slots on ONE domain in one round is +2 on that row —
    exactly the case a per-slot clamp would still wrap."""
    cg = AgentCgroup(DeviceTableBackend(10_000, n_domains=8))
    cg.mkdir("/s")
    cg.freeze("/s")
    view = cg.device_view()
    idx = cg.handle("/s")
    st = dict(view.state)
    st["cpu_stall"] = st["cpu_stall"].at[idx].set(INT32_MAX - 1)
    dom = jnp.array([idx, idx], jnp.int32)
    new, adv = schedule_decision(cg.programs, st, dom,
                                 jnp.array([1, 1], jnp.int32), 0, 8)
    assert not bool(np.asarray(adv).any())     # frozen: nobody advances
    assert int(new["cpu_stall"][idx]) == INT32_MAX


def test_mem_stall_saturates_on_host_tree():
    """The host reference applies the same clamp (one decision path,
    three substrates — the clamped counter must not diverge)."""
    cg = AgentCgroup(HostTreeBackend(10_000))
    cg.mkdir("/s", DomainSpec(max=10))
    cg.backend.tree.get("/s").mem_stall = INT32_MAX - 1
    for step in (0, 1):
        t = cg.try_charge("/s", 100, step=step)
        assert not t.granted
        assert cg.read("/s", "memory.stall") == INT32_MAX
