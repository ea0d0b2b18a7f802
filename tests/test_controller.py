"""Device-resident controller kernel semantics: batched charge
serialization, slot gating, throttle quantization.  (The randomized
host/device cross-validation lives in ``test_properties.py``; the
deterministic cross-backend parity suite in ``test_cgroup.py``.)"""
import heapq
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import dispatch

from repro.core import domains as D
from repro.core.cgroup import (AgentCgroup, DeviceTableBackend, DomainSpec,
                               ancestor_paths, parent_path)
from repro.core.controller import (ControllerConfig, DeviceDomainTable,
                                   charge_batch, host_charge, slot_gate,
                                   uncharge_batch)
from repro.core.daemon import AsyncDaemonBackend
from repro.core.events import Ev
from repro.core.progs import TokenBucketProgram
from repro.core.sched import flat_weights_by_path
from repro.spans import Tracer

CFG = ControllerConfig(step_ms=10.0)


def mk_pair(cap=500):
    tab = DeviceDomainTable(cap, n_domains=16, cfg=CFG)
    tree = D.DomainTree(cap)
    for path, kw in [("/t", {}), ("/t/a", dict(high=120)),
                     ("/t/b", dict(max=200, priority=D.LOW)),
                     ("/t/a/tool", dict(high=40))]:
        tab.create(path, **kw)
        tree.create(path, **kw)
    return tab, tree


def test_batched_charges_serialize_in_order():
    tab, _ = mk_pair(cap=100)
    doms = jnp.array([tab.index["/t/a"], tab.index["/t/b"],
                      tab.index["/t/a"]], jnp.int32)
    amts = jnp.array([60, 60, 60], jnp.int32)
    st_, granted, stalled = charge_batch(tab.state, doms, amts, 0, CFG)
    # 60 + 60 > 100: first wins, second denied, third denied
    assert list(np.asarray(granted)) == [True, False, False]
    assert int(st_["usage"][0]) == 60


def test_throttle_quantization_and_gate():
    tab, _ = mk_pair()
    idx = tab.index["/t/a/tool"]
    st_, granted, _ = charge_batch(tab.state, jnp.array([idx]),
                                   jnp.array([80], jnp.int32), 0, CFG)
    assert bool(granted[0])
    until = int(st_["throttle_until"][idx])
    assert until > 0
    # expected delay: min(2000, 10*(1+10*(80-40)/40)) = 110ms -> 11 steps
    assert until == 11
    gate = slot_gate(st_, jnp.array([idx]), 5)
    assert not bool(gate[0])
    gate = slot_gate(st_, jnp.array([idx]), 11)
    assert bool(gate[0])


def test_zero_amount_respects_freeze():
    tab, _ = mk_pair()
    tab.set_frozen("/t/b", True)
    idx = tab.index["/t/b"]
    st_, granted, stalled = charge_batch(tab.state, jnp.array([idx]),
                                         jnp.array([0], jnp.int32), 0, CFG)
    assert not bool(granted[0]) and bool(stalled[0])


def test_uncharge_and_host_charge_roundtrip():
    tab, _ = mk_pair()
    idx = tab.index["/t/a"]
    tab.state = host_charge(tab.state, idx, 70)
    assert tab.usage("/t/a") == 70 and tab.usage("/") == 70
    tab.state = uncharge_batch(tab.state, jnp.array([idx]),
                               jnp.array([70], jnp.int32))
    assert tab.usage("/t/a") == 0 and tab.usage("/") == 0


def test_inactive_slot_never_granted():
    tab, _ = mk_pair()
    st_, granted, stalled = charge_batch(tab.state, jnp.array([-1]),
                                         jnp.array([5], jnp.int32), 0, CFG)
    assert not bool(granted[0]) and not bool(stalled[0])


# --------------------------------------- compiled host-path table edits


class EagerTableBackend(DeviceTableBackend):
    """The device backend with its lifecycle edits made eagerly, as before
    they were compiled: one dispatch per edited element, the parent's
    program row and slot and the weight column read back to the host.
    The reference the compiled edits must match bit for bit."""

    def _recompute_flat(self):
        st = self.table.state
        w = np.asarray(st["weight"])
        flat = flat_weights_by_path(
            {p: int(w[i]) for p, i in self.table.index.items()})
        arr = np.zeros((self.table.n,), np.float32)
        for p, i in self.table.index.items():
            arr[i] = flat[p]
        self.table.state = dict(st, flat_weight=jnp.asarray(arr))

    def mkdir(self, path, spec):
        t = self.table
        pidx = t.index[parent_path(path)]
        idx = heapq.heappop(t._free)
        t.index[path] = idx
        st = t.state
        t.state = dict(
            st,
            high=st["high"].at[idx].set(spec.high),
            max=st["max"].at[idx].set(spec.max),
            low=st["low"].at[idx].set(spec.low),
            parent=st["parent"].at[idx].set(pidx),
            priority=st["priority"].at[idx].set(spec.priority),
            usage=st["usage"].at[idx].set(0),
            peak=st["peak"].at[idx].set(0),
            frozen=st["frozen"].at[idx].set(False),
            active=st["active"].at[idx].set(True),
            throttle_until=st["throttle_until"].at[idx].set(0),
            prog=st["prog"].at[idx].set(
                jnp.asarray(np.asarray(st["prog"][pidx]))),
            prog_id=st["prog_id"].at[idx].set(
                int(np.asarray(st["prog_id"][pidx]))),
            weight=st["weight"].at[idx].set(spec.weight),
            cpu_max=st["cpu_max"].at[idx].set(spec.cpu_max),
            flat_weight=st["flat_weight"].at[idx].set(0.0),
            vruntime=st["vruntime"].at[idx].set(0.0),
            cpu_used=st["cpu_used"].at[idx].set(0),
            cpu_stamp=st["cpu_stamp"].at[idx].set(-1),
            mem_stall=st["mem_stall"].at[idx].set(0),
            cpu_stall=st["cpu_stall"].at[idx].set(0))
        self._recompute_flat()
        self.log.emit(self._now, Ev.CREATE, path, high=spec.high,
                      max=spec.max)
        return idx

    def rmdir(self, path, transfer_residual):
        t = self.table
        residual = int(np.asarray(t.state["usage"][t.index[path]]))
        idx = t.index.pop(path)
        if residual:
            t.state = uncharge_batch(t.state, jnp.array([idx], jnp.int32),
                                     jnp.array([residual], jnp.int32))
        st = t.state
        t.state = dict(
            st, active=st["active"].at[idx].set(False),
            frozen=st["frozen"].at[idx].set(False),
            parent=st["parent"].at[idx].set(-1),
            weight=st["weight"].at[idx].set(D.DEFAULT_WEIGHT),
            cpu_max=st["cpu_max"].at[idx].set(D.UNLIMITED),
            flat_weight=st["flat_weight"].at[idx].set(0.0),
            vruntime=st["vruntime"].at[idx].set(0.0),
            cpu_used=st["cpu_used"].at[idx].set(0),
            cpu_stamp=st["cpu_stamp"].at[idx].set(-1),
            mem_stall=st["mem_stall"].at[idx].set(0),
            cpu_stall=st["cpu_stall"].at[idx].set(0),
            prog_id=st["prog_id"].at[idx].set(0))
        heapq.heappush(t._free, idx)
        if transfer_residual and residual:
            self.charge_unchecked(parent_path(path), residual)
        self._recompute_flat()
        self.log.emit(self._now, Ev.REMOVE, path)
        return residual

    def uncharge(self, path, pages):
        self.table.state = uncharge_batch(
            self.table.state, jnp.array([self.table.index[path]], jnp.int32),
            jnp.array([pages], jnp.int32))

    def charge_unchecked(self, path, pages):
        self.table.state = host_charge(self.table.state,
                                       self.table.index[path], pages)

    def write(self, path, file, value):
        assert file == "cpu.weight", file
        st, idx = self.table.state, self.table.index[path]
        self.table.state = dict(
            st, weight=st["weight"].at[idx].set(int(value)))
        self._recompute_flat()


def lifecycle_ops(seed: int, n_ops: int, n_domains: int) -> list:
    """A seeded random sequence of table edits: mkdir at depths 1 to 4
    with random specs (Python and numpy ints), unchecked charges (some
    negative), uncharges, cpu.weight writes and rmdir of leaves. A fixed
    prefix gives /a a program and a live row of its own for its children
    to inherit, and makes the clamp at 0 and the transfer's peak update
    bite."""
    rng = random.Random(seed)
    ints = (int, np.int32, np.int64)
    ops = [("mkdir", "/a", DomainSpec()), ("attach", "/a"),
           ("params", "/a", {"overage_gain": 7.5}),
           ("mkdir", "/a/b", DomainSpec()),
           ("charge", "/a/b", 10), ("charge", "/a", -8),
           ("charge", "/a/b", 5),
           # the release clamps /a at 0; a transfer lifts it above its peak
           ("rmdir", "/a/b"), ("uncharge", "/a", 100)]
    live, made = ["/a"], 0
    while len(ops) < n_ops:
        kind = rng.choices(("mkdir", "rmdir", "charge", "uncharge", "weight"),
                           (30, 20, 25, 15, 10))[0]
        anywhere = ["/"] + live
        if kind == "mkdir" and len(live) < n_domains - 1:
            parent = rng.choice([p for p in anywhere
                                 if len(ancestor_paths(p)) < 4])
            made += 1
            path = f"{parent.rstrip('/')}/n{made}"
            big = lambda: rng.choice((D.UNLIMITED, rng.randint(1, 300)))
            spec = DomainSpec(
                high=rng.choice(ints)(big()), max=rng.choice(ints)(big()),
                low=rng.choice(ints)(rng.randint(0, 50)),
                priority=rng.choice(ints)(rng.choice((D.LOW, D.NORMAL,
                                                      D.HIGH))),
                weight=rng.choice(ints)(rng.randint(1, 10000)),
                cpu_max=rng.choice(ints)(big()))
            live.append(path)
            ops.append(("mkdir", path, spec))
        elif kind == "rmdir" and live:
            leaf = rng.choice([p for p in live if not any(
                q.startswith(p + "/") for q in live)])
            live.remove(leaf)
            ops.append(("rmdir", leaf))
        elif kind == "charge":
            ops.append(("charge", rng.choice(anywhere),
                        rng.choice(ints)(rng.randint(-30, 60))))
        elif kind == "uncharge":
            ops.append(("uncharge", rng.choice(anywhere),
                        rng.choice(ints)(rng.randint(0, 50))))
        elif kind == "weight":
            ops.append(("weight", rng.choice(anywhere),
                        rng.choice(ints)(rng.randint(1, 10000))))
    return ops


def apply_op(be, op, transfer: bool):
    kind, path = op[0], op[1]
    if kind == "mkdir":
        return be.mkdir(path, op[2])
    if kind == "rmdir":
        return be.rmdir(path, transfer)
    if kind == "charge":
        return be.charge_unchecked(path, op[2])
    if kind == "uncharge":
        return be.uncharge(path, op[2])
    if kind == "attach":
        return be.attach(path, TokenBucketProgram(bucket_capacity=4))
    if kind == "params":
        return be.update_params(path, op[2])
    return be.write(path, "cpu.weight", op[2])


def assert_same_snapshot(got: dict, want: dict, where) -> None:
    got = {k: v for k, v in got.items() if k != "epoch"}
    assert got.keys() == want.keys(), where
    for key, w in want.items():
        g = got[key]
        if isinstance(w, np.ndarray):
            assert g.dtype == w.dtype and g.shape == w.shape, (where, key)
            assert g.tobytes() == w.tobytes(), (where, key, g, w)
        else:
            assert g == w, (where, key)


@pytest.mark.parametrize("transfer", [True, False])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_compiled_edits_match_the_eager_sequence(seed, transfer):
    n = 16
    ref = EagerTableBackend(500, n, CFG)
    dev = DeviceTableBackend(500, n, CFG)
    asy = AsyncDaemonBackend(DeviceTableBackend(500, n, CFG))
    try:
        for k, op in enumerate(lifecycle_ops(seed, 48, n)):
            want = apply_op(ref, op, transfer)
            assert apply_op(dev, op, transfer) == want, (k, op)
            assert apply_op(asy, op, transfer) == want, (k, op)
            snap = ref.snapshot()
            assert_same_snapshot(dev.snapshot(), snap, (k, op))
            assert_same_snapshot(asy.snapshot(), snap, (k, op))
            if k == 7:             # the prefix's rmdir of /a/b (15 pages)
                a = snap["index"]["/a"]
                assert snap["usage"][a] == (15 if transfer else 0)
                assert snap["peak"][a] == (15 if transfer else 10)
            if k == 24:            # crash recovery rebuilds the weight mirror
                dev = DeviceTableBackend(500, n, CFG)
                apply_op(dev, ("attach", "/a"), transfer)
                dev.restore(snap)
        assert asy.log.events == ref.log.events
    finally:
        asy.close()


def test_table_edits_compile_once_and_read_nothing():
    """After one warm pass no lifecycle edit lowers a new program, whatever
    its indices, limits and priorities and however they are typed; a mkdir
    reads nothing back, a tool call's close reads at most twice, and each
    edit is one ``table_update``."""
    tr = Tracer(on=True)
    cg = AgentCgroup(DeviceTableBackend(1000, 16, CFG), tracer=tr)
    with tr.step(-1):
        cg.mkdir("/w")
        lease = cg.intent.declare("t", parent="/w", high=4)
        cg.charge_unchecked(lease.path, 2)
        cg.uncharge(lease.path, 1)
        lease.close()                              # residual 1, transferred
        cg.uncharge("/w", 1)
        cg.rmdir("/w", transfer_residual=False)
    tr.drain()

    lowerings = []

    def on_event(event, duration, **_):
        if event == dispatch.JAXPR_TO_MLIR_MODULE_EVENT:
            lowerings.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    rng = random.Random(7)
    ints = (int, np.int32, np.int64)
    done = []                                      # (op, counters)
    try:
        sessions, leases = [], []
        for step in range(50):
            with tr.step(step):
                if len(sessions) < 4 or (leases and rng.random() < 0.3):
                    kind = "mkdir"
                    if len(sessions) < 4:
                        path = f"/s{step}"
                        cg.mkdir(path, DomainSpec(
                            high=rng.choice(ints)(rng.randint(1, 900)),
                            max=rng.choice(ints)(D.UNLIMITED),
                            low=rng.choice(ints)(rng.randint(0, 9)),
                            priority=rng.choice(ints)(
                                rng.choice((D.LOW, D.NORMAL, D.HIGH))),
                            weight=rng.choice(ints)(rng.randint(1, 10000))))
                        sessions.append(path)
                    else:
                        kind = "lease.close"
                        leases.pop(rng.randrange(len(leases))).close()
                elif rng.random() < 0.4:
                    kind = "mkdir"
                    leases.append(cg.intent.declare(
                        f"t{step}", parent=rng.choice(sessions),
                        priority=rng.choice(ints)(D.HIGH),
                        high=rng.choice(ints)(rng.randint(1, 64))))
                elif rng.random() < 0.5:
                    kind = "charge_unchecked"
                    target = rng.choice(sessions + [ls.path for ls in leases])
                    cg.charge_unchecked(rng.choice((target, cg.handle(target))),
                                        rng.choice(ints)(rng.randint(1, 9)))
                elif rng.random() < 0.6:
                    kind = "uncharge"
                    cg.uncharge(rng.choice(sessions),
                                rng.choice(ints)(rng.randint(0, 3)))
                else:
                    victim = sessions.pop(rng.randrange(len(sessions)))
                    mine = [ls for ls in leases if ls.parent == victim]
                    kind = ("rmdir", len(mine))
                    for ls in mine:
                        leases.remove(ls)
                        ls.close(transfer_residual=False)
                    assert cg.rmdir(victim, transfer_residual=False) >= 0
            done.append(kind)
    finally:
        jax._src.monitoring.unregister_event_duration_listener(on_event)
    assert lowerings == []
    counters = [c for _, c in tr.drain()["counters"]]
    assert len(counters) == len(done) == 50
    kinds = set()
    for kind, c in zip(done, counters):
        syncs = c.get("host_sync", 0)
        if kind in ("mkdir", "charge_unchecked", "uncharge"):
            assert syncs == 0 and c["table_update"] == 1, (kind, c)
        elif kind == "lease.close":
            assert syncs <= 2 and c["table_update"] == 1, (kind, c)
        else:            # the session's leases close first, then its rmdir
            kind, n = kind
            assert syncs <= 2 * n + 1, (kind, c)
            assert c["table_update"] == n + 1, (kind, c)
        kinds.add(kind)
    assert kinds == {"mkdir", "lease.close", "charge_unchecked", "uncharge",
                     "rmdir"}
