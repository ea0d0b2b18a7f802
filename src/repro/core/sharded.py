"""Sharded multi-tenant backend: the domain table across an N-device mesh.

Third implementation of the ``Backend`` protocol (after the host tree
and the single-device table): domain state lives as ``(n_shards,
n_domains)`` arrays sharded over a 1-axis ``("shard",)`` mesh, one
independent local table per device.  Placement is by *tenant subtree* —
the first path component below ``/`` picks a shard (round-robin), and
every descendant (sessions, tool-call leases) inherits it — so one
tenant's burst is charged, throttled, and frozen entirely on its own
device group, the multi-host analogue of the paper's per-tenant
hierarchical cgroups.

Enforcement runs in two modes, mirroring ``DeviceTableBackend``:

  * host-driven (lifecycle, replay, cross-validation): ``try_charge``
    routes the request to the owning shard's slice and additionally
    enforces the *global* root capacity (sum of shard-root usage), so
    grants match ``HostTreeBackend`` exactly;
  * in-step (serving engine): ``device_view()`` returns pure functions
    that take *global* handles, scatter the per-slot requests into a
    ``(n_shards, m)`` matrix, and run ``controller.charge_batch`` on
    every shard simultaneously inside ``shard_map`` — per-device
    enforcement with no cross-device traffic on the hot path.

Host-side reads reconcile across shards: ``/`` ``memory.current`` is
the sum of shard-root usage, ``memory.peak`` the sum of shard-root
peaks, and ``memory.events`` sums per-shard throttle state.  The root
peak is what provisioning needs — each device group's high-water is
what its HBM must actually hold — but note it is an *upper bound* on
the instantaneous global peak whenever different groups peak at
different times (exact for traffic confined to one shard, which is
what the cross-backend parity sequence replays).
"""
from __future__ import annotations

import heapq
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.core import controller as C
from repro.core import domains as D
from repro.core.cgroup import ChargeTicket, DomainSpec, parent_path
from repro.core.events import Ev, EventLog
from repro.core.pressure import saturating_count
from repro.core.progs import (PolicyProgram, as_program, as_programs,
                              check_registry, pad_row, path_in_scope,
                              registry_unknown_params, registry_width)
from repro.spans import to_host

UNLIMITED = D.UNLIMITED


def _stacked_state(capacity: int, n_shards: int, n_domains: int,
                   prog=None) -> dict:
    """Per-shard local tables: every shard's local index 0 is that device
    group's root, capped at the full pool capacity."""
    one = C.new_state(capacity, n_domains, prog)
    return {k: jnp.broadcast_to(v[None], (n_shards,) + v.shape)
            for k, v in one.items()}


class ShardedDeviceView:
    """Jit-safe slice of the sharded backend: the live ``(S, n)`` state
    pytree plus pure enforcement functions over *global* handles.  Each
    function scatters its flat per-slot requests to the owning shards,
    applies the single-device controller kernel per shard under
    ``shard_map``, and gathers flat results — so the engine's jitted
    step is backend-agnostic."""

    def __init__(self, backend: "ShardedTableBackend"):
        self._backend = backend
        self.cfg = backend.cfg
        self.mesh = backend.mesh
        self.n_shards = backend.n_shards
        self.per_shard = backend.per_shard_domains

    @property
    def state(self) -> dict:
        return self._backend.state

    @property
    def prog(self) -> PolicyProgram:
        return self._backend.prog

    @property
    def progs(self) -> tuple:
        return self._backend.progs

    # ------------------------------------------------------------- helpers

    def _split(self, dom):
        dom = dom.astype(jnp.int32)
        valid = dom >= 0
        shard = jnp.where(valid, dom // self.per_shard, 0)
        local = jnp.where(valid, dom % self.per_shard, -1)
        sel = shard[None, :] == jnp.arange(self.n_shards)[:, None]
        sel = sel & valid[None, :]
        return valid, shard, jnp.where(sel, local[None, :], -1)

    def _shard_specs(self, n_in, n_out):
        return ((P("shard"),) * n_in, (P("shard"),) * n_out)

    def _run(self, fn, state, *operands, n_out):
        """shard_map ``fn`` over the per-shard slices of state+operands."""
        def local(st, *ops):
            st1 = jax.tree.map(lambda x: x[0], st)
            ops1 = [o[0] for o in ops]
            outs = fn(st1, *ops1)
            return tuple(jax.tree.map(lambda x: x[None], o) for o in outs)
        in_specs, out_specs = self._shard_specs(1 + len(operands), n_out)
        return jax.shard_map(local, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs,
                             check_vma=False)(state, *operands)

    # ------------------------------------------------------------ the ops

    def charge(self, state, dom, amt, step):
        """In-step hierarchical charge: (state, granted, stalled); every
        shard serves its own tenants' requests in the same program."""
        m = dom.shape[0]
        valid, shard, dom2 = self._split(dom)
        amt2 = jnp.broadcast_to(amt.astype(jnp.int32)[None, :],
                                (self.n_shards, m))
        step2 = jnp.broadcast_to(jnp.asarray(step, jnp.int32)[None],
                                 (self.n_shards,))

        def local(st, d, a, s):
            return C.charge_batch(st, d, a, s[()], self.progs)

        new_state, g2, s2 = self._run(local, state, dom2, amt2, step2,
                                      n_out=3)
        rows = jnp.arange(m)
        granted = g2[shard, rows] & valid
        stalled = s2[shard, rows] & valid
        return new_state, granted, stalled

    def account(self, state, dom, amt):
        """Post-hoc unconditional charge (user-space baseline path)."""
        return self.uncharge(state, dom, -amt)

    def uncharge(self, state, dom, amt):
        m = dom.shape[0]
        _, _, dom2 = self._split(dom)
        amt2 = jnp.broadcast_to(amt.astype(jnp.int32)[None, :],
                                (self.n_shards, m))

        def local(st, d, a):
            return (C.uncharge_batch(st, d, a),)

        (new_state,) = self._run(local, state, dom2, amt2, n_out=1)
        return new_state

    def gate(self, state, dom, step):
        """Per-slot advance gate (no frozen/throttled ancestor)."""
        m = dom.shape[0]
        valid, shard, dom2 = self._split(dom)
        step2 = jnp.broadcast_to(jnp.asarray(step, jnp.int32)[None],
                                 (self.n_shards,))

        def local(st, d, s):
            return (C.slot_gate(st, d, s[()], self.progs),)

        (g2,) = self._run(local, state, dom2, step2, n_out=1)
        return g2[shard, jnp.arange(m)] & valid

    def schedule(self, state, dom, cost, step, budget):
        """In-step weighted scheduling: every shard runs the shared
        ``schedule_decision`` over its own tenants' slots with a
        *per-shard* budget (the per-device-group convention, like
        ``pool_pages``) — no cross-device traffic on the hot path."""
        from repro.core import sched as S
        m = dom.shape[0]
        valid, shard, dom2 = self._split(dom)
        cost2 = jnp.broadcast_to(cost.astype(jnp.int32)[None, :],
                                 (self.n_shards, m))
        step2 = jnp.broadcast_to(jnp.asarray(step, jnp.int32)[None],
                                 (self.n_shards,))

        def local(st, d, c, s):
            return S.schedule_decision(self.progs, st, d, c, s[()], budget)

        new_state, a2 = self._run(local, state, dom2, cost2, step2, n_out=2)
        return new_state, a2[shard, jnp.arange(m)] & valid

    def commit(self, state: dict) -> None:
        self._backend.state = state


class ShardedTableBackend:
    """Device-sharded backend: per-tenant device-group placement,
    per-shard in-step enforcement, host-side reconciliation."""

    def __init__(self, capacity: int, n_domains: int = 64, cfg=None,
                 log: Optional[EventLog] = None, *,
                 n_shards: Optional[int] = None, mesh=None,
                 prog: Optional[PolicyProgram] = None):
        self.cfg = cfg or C.ControllerConfig()
        self.capacity = capacity
        self.progs = as_programs(prog if prog is not None else self.cfg)
        self.scopes = ["/"] * len(self.progs)
        if mesh is None:
            devs = jax.devices()
            n_shards = n_shards or len(devs)
            mesh = jax.make_mesh((n_shards,), ("shard",),
                                 axis_types=(AxisType.Auto,),
                                 devices=devs[:n_shards])
        self.mesh = mesh
        self.n_shards = mesh.devices.size
        self.per_shard_domains = n_domains
        st = _stacked_state(capacity, self.n_shards, n_domains, self.progs)
        sh = NamedSharding(mesh, P("shard"))
        self.state = {k: jax.device_put(v, sh) for k, v in st.items()}
        # path -> (shard, local idx); "/" is every shard's local root but
        # addressed through shard 0
        self.index: dict[str, tuple[int, int]] = {"/": (0, 0)}
        self._free = [list(range(1, n_domains))    # heaps: lowest index first
                      for _ in range(self.n_shards)]
        self._tenant_shard: dict[str, int] = {}
        self._next_shard = 0
        self.log = log if log is not None else EventLog()
        self._now = 0.0
        self._host_charge = None       # jitted host-path charge, per program

    # ------------------------------------------------------------- programs

    @property
    def prog(self) -> PolicyProgram:
        """The primary (slot 0) program — the registry's trace constants
        (``step_ms`` etc.) and the single-program compatibility surface."""
        return self.progs[0]

    @property
    def attach_scope(self) -> str:
        return self.scopes[0]

    def _in_scope(self, path: str) -> bool:
        return path_in_scope(self.attach_scope, path)

    def attach(self, scope: str, prog: PolicyProgram) -> None:
        """Same compose semantics as ``DeviceDomainTable.attach``: a root
        attach resets the registry; a subtree attach takes (or replaces)
        a registry slot and moves only in-scope domains to it — rows
        padded to the registry width, per-shard."""
        prog = as_program(prog)
        self._host_charge = None
        S, n = self.n_shards, self.per_shard_domains
        sh = NamedSharding(self.mesh, P("shard"))
        if scope == "/":
            self.progs = (prog,)
            self.scopes = ["/"]
            rows = np.broadcast_to(prog.default_row(),
                                   (S, n, prog.n_params)).copy()
            self.state = dict(
                self.state, prog=jax.device_put(jnp.asarray(rows), sh),
                prog_id=jax.device_put(jnp.zeros((S, n), jnp.int32), sh))
            return
        if scope in self.scopes:
            k = self.scopes.index(scope)
            self.progs = self.progs[:k] + (prog,) + self.progs[k + 1:]
        else:
            k = len(self.progs)
            self.progs = self.progs + (prog,)
            self.scopes.append(scope)
        check_registry(self.progs)
        width = registry_width(self.progs)
        old = to_host(self.state["prog"])
        rows = np.zeros((S, n, width), np.float32)
        keep = min(width, old.shape[2])
        rows[:, :, :keep] = old[:, :, :keep]
        ids = to_host(self.state["prog_id"]).copy()
        for path, (s, i) in self.index.items():
            if path_in_scope(scope, path):
                ids[s, i] = k
                rows[s, i] = pad_row(prog.default_row(), width)
        self.state = dict(self.state,
                          prog=jax.device_put(jnp.asarray(rows), sh),
                          prog_id=jax.device_put(jnp.asarray(ids), sh))

    def update_params(self, path: str, kv: dict) -> None:
        unknown = registry_unknown_params(self.progs, kv)
        if unknown:
            raise KeyError(
                f"no registered program has param(s) {sorted(unknown)}; "
                f"knobs: {sorted(set().union(*(p.param_names for p in self.progs)))}")
        ids = to_host(self.state["prog_id"])
        prog = self.state["prog"]
        for p in self._subtree(path):
            s, i = self.index[p]
            pr = self.progs[int(ids[s, i])]
            cols = {pr.col(k): float(v) for k, v in kv.items()
                    if k in pr.param_names}
            for c, v in cols.items():
                if p == "/":            # root params on every shard's root
                    prog = prog.at[:, 0, c].set(v)
                else:
                    prog = prog.at[s, i, c].set(v)
        self.state = dict(self.state, prog=prog)

    def _recompute_flat(self) -> None:
        """Re-flatten hierarchical weights across the *global* logical
        tree (lifecycle rate).  Same host math as every other backend —
        ``flat_weights_by_path`` — so the per-shard rows hold the exact
        values the host reference computes even though each shard only
        sees a slice of the tree.  Every shard's local root mirrors the
        global root (flat 1.0)."""
        from repro.core.sched import flat_weights_by_path
        w = to_host(self.state["weight"])
        flat = flat_weights_by_path(
            {p: int(w[s, i]) for p, (s, i) in self.index.items()})
        arr = np.zeros((self.n_shards, self.per_shard_domains), np.float32)
        arr[:, 0] = 1.0
        for p, (s, i) in self.index.items():
            if p != "/":
                arr[s, i] = flat[p]
        sh = NamedSharding(self.mesh, P("shard"))
        self.state = dict(self.state,
                          flat_weight=jax.device_put(jnp.asarray(arr), sh))

    # ------------------------------------------------------------ placement

    @property
    def n_domains(self) -> int:
        """Global handle space (shard-major), for flat consumers."""
        return self.n_shards * self.per_shard_domains

    def placement(self) -> dict:
        """tenant path -> shard (device group) — the paper's
        tenant-subtree-to-device mapping, for tests and benchmarks."""
        return dict(self._tenant_shard)

    def _shard_for(self, path: str) -> int:
        if path == "/":
            return 0
        tenant = "/" + path.strip("/").split("/")[0]
        if tenant not in self._tenant_shard:
            self._tenant_shard[tenant] = self._next_shard % self.n_shards
            self._next_shard += 1
        return self._tenant_shard[tenant]

    def _handle(self, shard: int, idx: int) -> int:
        return shard * self.per_shard_domains + idx

    def device_view(self) -> ShardedDeviceView:
        return ShardedDeviceView(self)

    # ---------------------------------------------------- per-shard slices

    def _slice(self, shard: int) -> dict:
        return {k: v[shard] for k, v in self.state.items()}

    def _adopt(self, shard: int, sub: dict, keys=None) -> None:
        keys = keys if keys is not None else sub.keys()
        self.state = dict(self.state, **{
            k: self.state[k].at[shard].set(sub[k]) for k in keys})

    # ------------------------------------------------------------ lifecycle

    def mkdir(self, path: str, spec: DomainSpec) -> int:
        from repro.core.cgroup import ancestor_paths
        assert len(ancestor_paths(path)) <= C.DEPTH, f"{path}: deeper than DEPTH"
        assert path not in self.index, path
        shard = self._shard_for(path)
        pshard, pidx = self.index[parent_path(path)]
        if parent_path(path) != "/":
            assert pshard == shard, (path, "crosses its tenant's shard")
        else:
            pidx = 0                       # this shard's local root
        idx = heapq.heappop(self._free[shard])
        self.index[path] = (shard, idx)
        st = self.state
        upd = {
            "high": spec.high, "max": spec.max, "low": spec.low,
            "parent": pidx, "priority": spec.priority, "usage": 0,
            "peak": 0, "frozen": False, "active": True, "throttle_until": 0,
            "weight": spec.weight, "cpu_max": spec.cpu_max,
            "vruntime": 0.0, "cpu_used": 0, "cpu_stamp": -1,
            "mem_stall": 0, "cpu_stall": 0,
        }
        # children inherit their parent's live row AND program slot, so a
        # domain created after a subtree attach runs the subtree's program
        row = to_host(st["prog"][shard, pidx])
        pid = int(to_host(st["prog_id"][shard, pidx]))
        self.state = dict(st, **{
            k: st[k].at[shard, idx].set(v) for k, v in upd.items()},
            prog=st["prog"].at[shard, idx].set(jnp.asarray(row)),
            prog_id=st["prog_id"].at[shard, idx].set(pid))
        self._recompute_flat()
        self.log.emit(self._now, Ev.CREATE, path, high=spec.high,
                      max=spec.max, shard=shard)
        return self._handle(shard, idx)

    def rmdir(self, path: str, transfer_residual: bool) -> int:
        shard, idx = self.index[path]
        residual = int(to_host(self.state["usage"][shard, idx]))
        parent = parent_path(path)
        if residual:
            sub = self._slice(shard)
            sub = C.uncharge_batch(sub, jnp.array([idx], jnp.int32),
                                   jnp.array([residual], jnp.int32))
            self._adopt(shard, sub, keys=("usage",))
        st = self.state
        self.state = dict(
            st,
            active=st["active"].at[shard, idx].set(False),
            frozen=st["frozen"].at[shard, idx].set(False),
            parent=st["parent"].at[shard, idx].set(-1),
            weight=st["weight"].at[shard, idx].set(D.DEFAULT_WEIGHT),
            cpu_max=st["cpu_max"].at[shard, idx].set(UNLIMITED),
            vruntime=st["vruntime"].at[shard, idx].set(0.0),
            cpu_used=st["cpu_used"].at[shard, idx].set(0),
            cpu_stamp=st["cpu_stamp"].at[shard, idx].set(-1),
            mem_stall=st["mem_stall"].at[shard, idx].set(0),
            cpu_stall=st["cpu_stall"].at[shard, idx].set(0),
            prog_id=st["prog_id"].at[shard, idx].set(0))
        del self.index[path]
        heapq.heappush(self._free[shard], idx)
        self._recompute_flat()
        if transfer_residual and residual and parent is not None:
            self.charge_unchecked(parent, residual)
        self.log.emit(self._now, Ev.REMOVE, path)
        return residual

    def exists(self, path: str) -> bool:
        return path in self.index

    def paths(self) -> list[str]:
        return list(self.index)

    def handle(self, path: str) -> int:
        return self._handle(*self.index[path])

    def path_of(self, handle: int) -> str:
        key = (handle // self.per_shard_domains,
               handle % self.per_shard_domains)
        for p, si in self.index.items():
            if si == key:
                return p
        raise KeyError(handle)

    # --------------------------------------------------- charging (host path)

    def _root_total(self) -> int:
        return int(jnp.sum(self.state["usage"][:, 0]))

    def _host_charge_fn(self):
        """One jitted program for the whole host-driven charge: global
        root-capacity check, owning-shard charge, scatter-back — so a
        ``try_charge`` costs a single dispatch plus ONE device->host
        gather (the packed flags vector) instead of per-key slice syncs
        (the ROADMAP open item)."""
        if self._host_charge is None:
            progs = self.progs

            def fn(state, shard, idx, pages, step):
                cap = state["max"][0, 0]
                root_total = jnp.sum(state["usage"][:, 0])
                root_ok = (cap >= UNLIMITED) | (root_total + pages <= cap)
                sub = jax.tree.map(lambda v: v[shard], state)
                dom = jnp.where(root_ok, idx, -1).reshape(1)
                sub, granted, stalled = C.charge_batch(
                    sub, dom, pages.reshape(1).astype(jnp.int32), step, progs)
                # a global-root-capacity denial is a stall event at the
                # charged domain, exactly as the host reference (where
                # the root max sits on the ancestor chain) counts it —
                # charge_batch never saw the request (dom = -1); the
                # counter saturates at INT32_MAX like every other site
                sub = dict(sub, mem_stall=sub["mem_stall"].at[idx].set(
                    saturating_count(sub["mem_stall"][idx],
                                     jnp.where(root_ok, 0, 1))))
                out = {k: state[k].at[shard].set(sub[k]) for k in state}
                window = jnp.maximum(0, sub["throttle_until"][idx] - step)
                flags = jnp.stack([granted[0].astype(jnp.int32),
                                   stalled[0].astype(jnp.int32),
                                   root_ok.astype(jnp.int32),
                                   window.astype(jnp.int32)])
                return out, flags

            self._host_charge = jax.jit(fn)
        return self._host_charge

    def try_charge(self, path: str, pages: int,
                   step: Optional[int]) -> ChargeTicket:
        if step is None:
            step = int(self._now)
        shard, idx = self.index[path]
        # global root capacity: shard-local tables each cap at the full
        # pool, so the cross-shard sum is enforced in the same jitted
        # program, from the live root max — the HostTreeBackend
        # root-max contract with write("/", "memory.max", v) honored.
        state, flags = self._host_charge_fn()(
            self.state, jnp.int32(shard), jnp.int32(idx), jnp.int32(pages),
            jnp.int32(step))
        granted, stalled, root_ok, window = (int(x) for x in
                                             to_host(flags))
        self.state = state
        if not root_ok:
            return ChargeTicket(granted=False, stalled=True, blocked_by="/")
        return ChargeTicket(granted=bool(granted), stalled=bool(stalled),
                            delay_ms=window * self.prog.step_ms)

    def uncharge(self, path: str, pages: int) -> None:
        shard, idx = self.index[path]
        sub = C.uncharge_batch(self._slice(shard),
                               jnp.array([idx], jnp.int32),
                               jnp.array([pages], jnp.int32))
        self._adopt(shard, sub, keys=("usage",))

    def charge_unchecked(self, path: str, pages: int) -> None:
        shard, idx = self.index[path]
        sub = C.host_charge(self._slice(shard), idx, pages)
        self._adopt(shard, sub, keys=("usage", "peak"))

    # ------------------------------------------------ scheduling (host path)

    def schedule(self, paths: list, costs: list, step: int,
                 budget: int) -> list:
        """Host-driven weighted scheduling round, bit-exact with the
        host reference: the per-shard tables are flattened to one
        global view (parents rebased, like ``snapshot``) and run
        through the shared jitted ``schedule_decision`` with the global
        budget; the updated accounts scatter back per shard.  The
        in-step path (``device_view().schedule``) instead runs per
        shard with a per-shard budget — the per-device-group
        convention."""
        from repro.core.sched import jit_schedule
        st = {k: to_host(v) for k, v in self.state.items()}
        S, n = self.n_shards, self.per_shard_domains
        base = (np.arange(S) * n)[:, None]
        parent = np.where(st["parent"] >= 0, st["parent"] + base, -1)
        flat = {k: jnp.asarray(st[k].reshape(-1))
                for k in ("usage", "high", "max", "low", "priority",
                          "frozen", "active", "throttle_until", "weight",
                          "cpu_max", "flat_weight", "vruntime", "cpu_used",
                          "cpu_stamp", "cpu_stall", "prog_id")}
        flat["parent"] = jnp.asarray(parent.reshape(-1))
        flat["prog"] = jnp.asarray(st["prog"].reshape(S * n, -1))
        dom = jnp.asarray([self._handle(*self.index[p]) for p in paths],
                          jnp.int32)
        cost = jnp.asarray(list(costs), jnp.int32)
        new, advance = jit_schedule(self.progs, flat, dom, cost, int(step),
                                    int(budget))
        sh = NamedSharding(self.mesh, P("shard"))
        self.state = dict(self.state, **{
            k: jax.device_put(
                jnp.asarray(to_host(new[k]).reshape(S, n)), sh)
            for k in ("vruntime", "cpu_used", "cpu_stamp", "cpu_stall")})
        return [bool(a) for a in to_host(advance)]

    # ------------------------------------------------------ subtree control

    def _subtree(self, path: str) -> list[str]:
        return [p for p in self.index if path_in_scope(path, p)]

    def _set_frozen(self, path: str, flag: bool) -> None:
        st = self.state
        frozen = st["frozen"]
        for p in self._subtree(path):
            shard, idx = self.index[p]
            if p == "/":               # freeze every device group's root
                frozen = frozen.at[:, 0].set(flag)
            else:
                frozen = frozen.at[shard, idx].set(flag)
        self.state = dict(st, frozen=frozen)

    def freeze(self, path: str) -> None:
        self._set_frozen(path, True)
        self.log.emit(self._now, Ev.FREEZE, path)

    def thaw(self, path: str) -> None:
        self._set_frozen(path, False)
        self.log.emit(self._now, Ev.THAW, path)

    def kill(self, path: str) -> int:
        """Atomic subtree kill, same semantics as ``DeviceTableBackend``:
        usage released from the owning shard's chain, every node retired
        in place (still registered, denying charges via frozen)."""
        shard, idx = self.index[path]
        freed = int(to_host(self.state["usage"][shard, idx]))
        if freed:
            self.uncharge(path, freed)
        st = self.state
        usage, active, frozen = st["usage"], st["active"], st["frozen"]
        for p in self._subtree(path):
            s, i = self.index[p]
            usage = usage.at[s, i].set(0)
            active = active.at[s, i].set(False)
            frozen = frozen.at[s, i].set(True)
        self.state = dict(st, usage=usage, active=active, frozen=frozen)
        self.log.emit(self._now, Ev.OOM_KILL, path, freed=freed)
        return freed

    # --------------------------------------------------------- control files

    _FILE_KEY = {"memory.current": "usage", "memory.peak": "peak",
                 "memory.high": "high", "memory.max": "max",
                 "memory.low": "low", "memory.priority": "priority",
                 "cgroup.freeze": "frozen", "cpu.weight": "weight",
                 "cpu.max": "cpu_max"}

    def reconcile(self) -> dict:
        """Host-side reconciliation of the global root across device
        groups, gathered shard by shard: usage and peak sum over the
        shard-local roots, throttle is a flag (any group throttled).
        This is the seam the chaos harness targets — the optional
        ``reconcile_hook(shard)`` attribute runs between per-shard
        gathers, where fault injection (or a concurrent lifecycle op)
        can land mid-reconciliation."""
        hook = getattr(self, "reconcile_hook", None)
        usage = peak = 0
        throttled = False
        for s in range(self.n_shards):
            if hook is not None:
                hook(s)
            usage += int(to_host(self.state["usage"][s, 0]))
            peak += int(to_host(self.state["peak"][s, 0]))
            tu = to_host(self.state["throttle_until"][s, 0])
            throttled |= bool(tu > 0)
        return {"usage": usage, "peak": peak, "throttled": throttled}

    def read(self, path: str, file: str):
        from repro.core import pressure as PSI
        if file in PSI.STALL_FILES:
            # stall counters are local per domain; roll the subtree up
            # host-side over the logical path tree, gathering each
            # registered path's row from its owning shard
            key = "mem_stall" if file == "memory.stall" else "cpu_stall"
            col = to_host(self.state[key])
            return PSI.subtree_counts_by_path(
                {p: int(col[s, i]) for p, (s, i) in self.index.items()
                 if path_in_scope(path, p)})[path]
        if path == "/":
            # reconcile the global root across device groups
            if file == "memory.current":
                return self.reconcile()["usage"]
            if file == "memory.peak":
                return self.reconcile()["peak"]
            if file == "memory.events":
                # flag, not a shard count — DeviceTableBackend semantics
                return {"high": 0, "max": 0,
                        "throttle": int(self.reconcile()["throttled"]),
                        "oom_kill": 0}
            return int(to_host(self.state[self._FILE_KEY[file]][0, 0]))
        shard, idx = self.index[path]
        if file == "memory.events":
            tu = int(to_host(self.state["throttle_until"][shard, idx]))
            return {"high": 0, "max": 0, "throttle": int(tu > 0),
                    "oom_kill": 0}
        return int(to_host(self.state[self._FILE_KEY[file]][shard, idx]))

    def write(self, path: str, file: str, value) -> None:
        if file == "cgroup.freeze":
            (self.freeze if int(value) else self.thaw)(path)
            return
        if file == "cpu.weight":
            from repro.core.sched import check_weight
            value = check_weight(value)
        key = self._FILE_KEY[file]
        st = self.state
        if path == "/":                # root limits apply to every group
            if file == "memory.max":
                self.capacity = int(value)
            self.state = dict(st, **{
                key: st[key].at[:, 0].set(int(value))})
        else:
            shard, idx = self.index[path]
            self.state = dict(st, **{
                key: st[key].at[shard, idx].set(int(value))})
        if file == "cpu.weight":
            self._recompute_flat()

    # --------------------------------------------------------------- queries

    def snapshot(self) -> dict:
        """One host sync; rows addressable by global handle
        (``shard * n_domains + local``), parent pointers rebased to
        global handles, plus the reconciled root usage."""
        st = {k: to_host(v) for k, v in self.state.items()}
        S, n = self.n_shards, self.per_shard_domains
        base = (np.arange(S) * n)[:, None]
        parent = st["parent"]
        parent = np.where(parent >= 0, parent + base, -1).reshape(-1)
        return {"paths": list(self.index),
                "index": {p: self._handle(*si)
                          for p, si in self.index.items()},
                "usage": st["usage"].reshape(-1),
                "high": st["high"].reshape(-1),
                "max": st["max"].reshape(-1),
                "parent": parent,
                "active": st["active"].reshape(-1),
                "peak": st["peak"].reshape(-1),
                "low": st["low"].reshape(-1),
                "priority": st["priority"].reshape(-1),
                "frozen": st["frozen"].reshape(-1),
                "throttle_until": st["throttle_until"].reshape(-1),
                "params": st["prog"].reshape(S * n, -1),
                "weight": st["weight"].reshape(-1),
                "cpu_max": st["cpu_max"].reshape(-1),
                "flat_weight": st["flat_weight"].reshape(-1),
                "vruntime": st["vruntime"].reshape(-1),
                "cpu_used": st["cpu_used"].reshape(-1),
                "cpu_stamp": st["cpu_stamp"].reshape(-1),
                "mem_stall": st["mem_stall"].reshape(-1),
                "cpu_stall": st["cpu_stall"].reshape(-1),
                "prog_id": st["prog_id"].reshape(-1),
                "root_usage": int(st["usage"][:, 0].sum()),
                "root_handles": [s * n for s in range(S)],
                "placement": dict(self._tenant_shard),
                "next_shard": self._next_shard}

    def restore(self, snap: dict) -> None:
        """Rebuild placement, index, and the stacked device state from a
        ``snapshot()`` dict — crash recovery onto a freshly constructed
        backend with the same mesh shape and ``n_domains`` (see
        ``HostTreeBackend.restore``).  Call after ``attach``."""
        S, n = self.n_shards, self.per_shard_domains
        assert len(snap["usage"]) == S * n, "snapshot/mesh shape mismatch"
        self.index = {p: divmod(h, n) for p, h in snap["index"].items()}
        self.index["/"] = (0, 0)
        used = {s: {0} for s in range(S)}
        for s, i in self.index.values():
            used.setdefault(s, {0}).add(i)
        self._free = [[i for i in range(1, n) if i not in used[s]]
                      for s in range(S)]
        for heap in self._free:
            heapq.heapify(heap)
        self._tenant_shard = dict(snap.get("placement", {}))
        self._next_shard = int(snap.get("next_shard", 0))
        base = (np.arange(S) * n)[:, None]
        parent = np.asarray(snap["parent"]).reshape(S, n)
        parent = np.where(parent >= 0, parent - base, -1)
        sh = NamedSharding(self.mesh, P("shard"))
        new = dict(self.state)
        for key, src, dtype in (
                ("usage", "usage", jnp.int32), ("peak", "peak", jnp.int32),
                ("high", "high", jnp.int32), ("max", "max", jnp.int32),
                ("low", "low", jnp.int32),
                ("priority", "priority", jnp.int32),
                ("frozen", "frozen", jnp.bool_),
                ("active", "active", jnp.bool_),
                ("throttle_until", "throttle_until", jnp.int32),
                ("weight", "weight", jnp.int32),
                ("cpu_max", "cpu_max", jnp.int32),
                ("flat_weight", "flat_weight", jnp.float32),
                ("vruntime", "vruntime", jnp.float32),
                ("cpu_used", "cpu_used", jnp.int32),
                ("cpu_stamp", "cpu_stamp", jnp.int32),
                ("mem_stall", "mem_stall", jnp.int32),
                ("cpu_stall", "cpu_stall", jnp.int32),
                ("prog_id", "prog_id", jnp.int32)):
            if src in snap:
                arr = np.asarray(snap[src]).reshape(S, n)
                new[key] = jax.device_put(jnp.asarray(arr, dtype), sh)
        new["parent"] = jax.device_put(jnp.asarray(parent, jnp.int32), sh)
        params = np.asarray(snap["params"]).reshape(S, n, -1)
        new["prog"] = jax.device_put(jnp.asarray(params, jnp.float32), sh)
        self.state = new
        if "flat_weight" not in snap:      # older snapshot: re-flatten
            self._recompute_flat()

    def set_time(self, t: float) -> None:
        self._now = t
