"""Device-resident domain state + in-step enforcement (the eBPF analogue).

The paper's responsiveness fix is to run control logic *at the kernel
enforcement point* (memcg_bpf_ops / sched_ext) instead of in a
user-space daemon.  The TPU-pod analogue: enforcement decisions are
computed *inside the jitted engine step* from device-resident domain
state (``jax.lax`` ops only), so a burst is throttled in the same step
it occurs — no host round trip.  The host-side daemon (serving engine /
``policy.py``) only manages lifecycle (create/freeze/thaw/remove) via
the shared state arrays, exactly like the paper's "lightweight
user-space daemon managing cgroup lifecycle via shared BPF maps".

The decision logic itself is NOT in this file: ``charge_batch`` and
``slot_gate`` are thin kernels that build a per-request ``ChainView``
and dispatch into the attached ``PolicyProgram`` (``core/progs.py``) —
the memcg_bpf_ops analogue.  The program's parameter table rides in the
state pytree under ``"prog"``, so retuning a live policy is a state
update (no retrace); attaching a different program swaps the traced
code (a recompile, like loading a new BPF object).

State layout (fixed capacity ``n``; index 0 is the root):
  usage/high/max/low : i32 pages          parent : i32 (-1 for root)
  priority           : i32 (0/1/2)        frozen : bool
  throttle_until     : i32 engine step    peak   : i32
  prog               : f32 (n, P) program parameter table

``charge_batch`` serializes grants within a step via ``lax.scan`` —
the same serialization the memcg page-counter hierarchy applies — so
results are deterministic and order-faithful.
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import domains as D
from repro.core.pressure import charge_stall_event, saturating_count
from repro.core.progs import (ChainView, PolicyProgram, Request, as_program,
                              as_programs, charge_decision, check_registry,
                              gate_decision, pad_row, path_in_scope,
                              registry_unknown_params, registry_width)
from repro.spans import note, to_host

UNLIMITED = D.UNLIMITED
DEPTH = 4          # root / tenant / session / tool-call


@dataclass(frozen=True)
class ControllerConfig:
    """Scalar knobs for the stock graduated-throttle program.  The
    defaults single-source from ``domains`` — the same constants the
    host tree's reference ``throttle_delay_ms`` uses."""
    step_ms: float = 10.0             # engine-step duration the delays quantize to
    base_delay_ms: float = D.BASE_DELAY_MS
    max_delay_ms: float = D.MAX_DELAY_MS
    high_priority_discount: float = D.HIGH_PRIORITY_DISCOUNT
    overage_gain: float = D.OVERAGE_GAIN


def new_state(capacity_pages: int, n_domains: int = 64,
              prog: Optional[PolicyProgram] = None) -> dict:
    """Fresh device state with only the root (index 0) configured.
    ``prog`` may be a registry tuple: the param table is sized to the
    widest program and every domain starts on the primary (slot 0)."""
    progs = as_programs(prog)
    width = registry_width(progs)
    n = n_domains
    st = {
        "usage": jnp.zeros((n,), jnp.int32),
        "high": jnp.full((n,), UNLIMITED, jnp.int32),
        "max": jnp.full((n,), UNLIMITED, jnp.int32),
        "low": jnp.zeros((n,), jnp.int32),
        "parent": jnp.full((n,), -1, jnp.int32),
        "priority": jnp.full((n,), D.NORMAL, jnp.int32),
        "frozen": jnp.zeros((n,), bool),
        "active": jnp.zeros((n,), bool),
        "throttle_until": jnp.zeros((n,), jnp.int32),
        "peak": jnp.zeros((n,), jnp.int32),
        "prog": jnp.broadcast_to(
            jnp.asarray(pad_row(progs[0].default_row(), width)),
            (n, width)),
        "prog_id": jnp.zeros((n,), jnp.int32),
        # CPU scheduling rows (cpu.weight / cpu.max, core/sched.py)
        "weight": jnp.full((n,), D.DEFAULT_WEIGHT, jnp.int32),
        "cpu_max": jnp.full((n,), UNLIMITED, jnp.int32),
        "flat_weight": jnp.zeros((n,), jnp.float32),
        "vruntime": jnp.zeros((n,), jnp.float32),
        "cpu_used": jnp.zeros((n,), jnp.int32),
        "cpu_stamp": jnp.full((n,), -1, jnp.int32),
        # PSI-style stall-event counters (core/pressure.py): local to
        # each domain, aggregated up the hierarchy host-side at read
        "mem_stall": jnp.zeros((n,), jnp.int32),
        "cpu_stall": jnp.zeros((n,), jnp.int32),
    }
    st["max"] = st["max"].at[0].set(capacity_pages)
    st["high"] = st["high"].at[0].set(capacity_pages)
    st["active"] = st["active"].at[0].set(True)
    st["flat_weight"] = st["flat_weight"].at[0].set(1.0)
    return st


def _ancestor_chain(parent, idx):
    """(DEPTH,) ancestor indices of ``idx`` (self first), -1-padded."""
    chain = [idx]
    for _ in range(DEPTH - 1):
        prev = chain[-1]
        nxt = jnp.where(prev >= 0, parent[jnp.maximum(prev, 0)], -1)
        chain.append(nxt)
    return jnp.stack(chain)


def _chain_view(state, usage, throttle_until, params, d) -> ChainView:
    """Masked ancestor-chain view for one request (invalid entries are
    neutral: usage 0, limits UNLIMITED, not frozen, no throttle)."""
    chain = _ancestor_chain(state["parent"], jnp.maximum(d, 0))
    valid = (chain >= 0) & (d >= 0)
    cidx = jnp.maximum(chain, 0)
    di = jnp.maximum(d, 0)
    return ChainView(
        valid=valid,
        usage=jnp.where(valid, usage[cidx], 0),
        high=jnp.where(valid, state["high"][cidx], UNLIMITED),
        max=jnp.where(valid, state["max"][cidx], UNLIMITED),
        low=jnp.where(valid, state["low"][cidx], 0),
        frozen=jnp.where(valid, state["frozen"][cidx], False),
        throttle_until=jnp.where(valid, throttle_until[cidx], 0),
        priority=state["priority"][di],
        params=params[di],
        prog_id=state["prog_id"][di],
    )


def charge_batch(state: dict, dom: jax.Array, amt: jax.Array, step,
                 prog=None):
    """Hierarchically charge ``amt[i]`` pages to domain ``dom[i]``,
    dispatching every decision into the attached ``PolicyProgram``
    (``prog`` also accepts a ``ControllerConfig`` for the stock
    graduated program, or None for defaults).

    Returns (new_state, granted (m,) bool, stalled (m,) bool).
    ``stalled`` marks requests denied *because of throttle/freeze* (they
    retry next step); hard-``max`` denials also stall (the engine's
    graceful-degradation path never OOM-kills from inside the step).
    Zero-amount requests are gated only by freeze/throttle (a decode
    step that does not cross a page boundary allocates nothing but must
    still respect cgroup.freeze).
    """
    progs = as_programs(prog)

    def one(carry, req):
        usage, peak, throttle_until, params, mem_stall = carry
        d, a = req
        view = _chain_view(state, usage, throttle_until, params, d)
        verdict, delay_ms, throttle = charge_decision(
            progs, view, Request(d, a, step))
        grant = (d >= 0) & verdict.grant
        stalled = (d >= 0) & verdict.stall

        chain = _ancestor_chain(state["parent"], jnp.maximum(d, 0))
        cvalid = (chain >= 0) & (d >= 0)
        cidx = jnp.maximum(chain, 0)
        add = jnp.where(cvalid & grant, a, 0)
        usage = usage.at[cidx].add(add)
        peak = jnp.maximum(peak, usage)

        di = jnp.maximum(d, 0)
        dly = jnp.ceil(delay_ms / progs[0].step_ms).astype(jnp.int32)
        tu = jnp.where(throttle & (d >= 0),
                       jnp.maximum(throttle_until[di], step + dly),
                       throttle_until[di])
        throttle_until = throttle_until.at[di].set(
            jnp.where(d >= 0, tu, throttle_until[di]))
        params = params.at[di].set(
            jnp.where(d >= 0, verdict.params, params[di]))
        # PSI accounting: a stalled or throttled decision is one
        # memory-stall event on the charged domain (core/pressure.py),
        # saturating at INT32_MAX instead of wrapping negative
        mem_stall = mem_stall.at[di].set(saturating_count(
            mem_stall[di],
            jnp.where(d >= 0,
                      charge_stall_event(stalled, (d >= 0) & throttle), 0)))
        return (usage, peak, throttle_until, params, mem_stall), \
            (grant, stalled)

    (usage, peak, throttle_until, params, mem_stall), (granted, stalled) = \
        jax.lax.scan(
            one, (state["usage"], state["peak"], state["throttle_until"],
                  state["prog"], state["mem_stall"]),
            (dom.astype(jnp.int32), amt.astype(jnp.int32)))
    new_state = dict(state, usage=usage, peak=peak,
                     throttle_until=throttle_until, prog=params,
                     mem_stall=mem_stall)
    return new_state, granted, stalled


def host_charge(state: dict, idx: int, amt: int) -> dict:
    """Unconditional hierarchical charge for host-side lifecycle moves
    (residual transfer on tool-domain close, thaw re-charge).  Never
    denied — the pages are already resident; this is bookkeeping."""
    usage = to_host(state["usage"]).copy()
    parent = to_host(state["parent"])
    i = idx
    for _ in range(DEPTH):
        if i < 0:
            break
        usage[i] = max(0, usage[i] + amt)
        i = int(parent[i])
    return dict(state, usage=jnp.asarray(usage),
                peak=jnp.maximum(state["peak"], jnp.asarray(usage)))


def uncharge_batch(state: dict, dom: jax.Array, amt: jax.Array):
    """Release pages (always succeeds); vectorized scatter over chains."""
    chain = jax.vmap(lambda d: _ancestor_chain(state["parent"],
                                               jnp.maximum(d, 0)))(dom)
    valid = (chain >= 0) & (dom >= 0)[:, None]
    sub = jnp.where(valid, amt[:, None], 0)
    usage = state["usage"].at[jnp.maximum(chain, 0).reshape(-1)].add(
        -sub.reshape(-1))
    return dict(state, usage=jnp.maximum(usage, 0))


# ------------------------------------------------------ host-path table edits
#
# Each lifecycle edit the host makes to the table is one compiled program
# that reads nothing back. Indices and a spec's scalars arrive as one
# traced int32 vector, so no index, limit or priority value retraces;
# ``flat`` is the whole ``flat_weight`` row, re-flattened on the host
# from its mirror of ``cpu.weight`` (``DeviceDomainTable.flat_row``).


def _charge_chain(state: dict, idx, amt) -> dict:
    """``host_charge``'s arithmetic, traced: ``max(0, usage + amt)`` along
    the chain of ``idx`` (nothing for ``idx`` < 0), then ``peak = max(peak,
    usage)`` over the whole table."""
    chain = _ancestor_chain(state["parent"], jnp.maximum(idx, 0))
    valid = (chain >= 0) & (idx >= 0)
    cidx = jnp.maximum(chain, 0)
    usage = state["usage"]
    delta = jnp.where(valid, jnp.maximum(0, usage[cidx] + amt) - usage[cidx],
                      0)
    usage = usage.at[cidx].add(delta)
    return dict(state, usage=usage, peak=jnp.maximum(state["peak"], usage))


@jax.jit
def create_row(state: dict, ints: jax.Array, flat: jax.Array) -> dict:
    """Configure row ``ints[0]`` as a fresh child of row ``ints[1]``;
    ``ints[2:]`` is (high, max, low, priority, weight, cpu_max). The child
    inherits its parent's live program row and slot, copied on the device
    (cgroup settings propagate down: a child made after a subtree attach
    runs the subtree's program)."""
    idx, pidx = ints[0], ints[1]
    high, mx, low, priority, weight, cpu_max = (ints[k] for k in range(2, 8))
    st = state
    return dict(
        st,
        high=st["high"].at[idx].set(high),
        max=st["max"].at[idx].set(mx),
        low=st["low"].at[idx].set(low),
        parent=st["parent"].at[idx].set(pidx),
        priority=st["priority"].at[idx].set(priority),
        usage=st["usage"].at[idx].set(0),
        peak=st["peak"].at[idx].set(0),
        frozen=st["frozen"].at[idx].set(False),
        active=st["active"].at[idx].set(True),
        throttle_until=st["throttle_until"].at[idx].set(0),
        prog=st["prog"].at[idx].set(st["prog"][pidx]),
        prog_id=st["prog_id"].at[idx].set(st["prog_id"][pidx]),
        weight=st["weight"].at[idx].set(weight),
        cpu_max=st["cpu_max"].at[idx].set(cpu_max),
        flat_weight=flat,
        vruntime=st["vruntime"].at[idx].set(0.0),
        cpu_used=st["cpu_used"].at[idx].set(0),
        cpu_stamp=st["cpu_stamp"].at[idx].set(-1),
        mem_stall=st["mem_stall"].at[idx].set(0),
        cpu_stall=st["cpu_stall"].at[idx].set(0),
    )


@functools.partial(jax.jit, static_argnames=("transfer",))
def remove_row(state: dict, idx: jax.Array, flat: jax.Array, *,
               transfer: bool):
    """Retire row ``idx``: release its residual usage up the ancestor
    chain (``uncharge_batch``'s arithmetic, clamped at 0), with
    ``transfer`` charge it to the parent's chain as ``host_charge`` does,
    and reset the row. Returns (state, residual as a device scalar)."""
    usage = state["usage"]
    residual = usage[idx]
    moved = residual != 0
    released = uncharge_batch(state, idx.reshape(1), residual.reshape(1))
    st = dict(state, usage=jnp.where(moved, released["usage"], usage))
    if transfer:
        charged = _charge_chain(st, state["parent"][idx], residual)
        st = dict(st, usage=jnp.where(moved, charged["usage"], st["usage"]),
                  peak=jnp.where(moved, charged["peak"], st["peak"]))
    return dict(
        st,
        active=st["active"].at[idx].set(False),
        frozen=st["frozen"].at[idx].set(False),
        parent=st["parent"].at[idx].set(-1),
        weight=st["weight"].at[idx].set(D.DEFAULT_WEIGHT),
        cpu_max=st["cpu_max"].at[idx].set(UNLIMITED),
        flat_weight=flat,
        vruntime=st["vruntime"].at[idx].set(0.0),
        cpu_used=st["cpu_used"].at[idx].set(0),
        cpu_stamp=st["cpu_stamp"].at[idx].set(-1),
        mem_stall=st["mem_stall"].at[idx].set(0),
        cpu_stall=st["cpu_stall"].at[idx].set(0),
        prog_id=st["prog_id"].at[idx].set(0),
    ), residual


@jax.jit
def charge_unchecked_row(state: dict, ints: jax.Array) -> dict:
    """``host_charge`` of ``ints[1]`` pages to domain ``ints[0]``."""
    return _charge_chain(state, ints[0], ints[1])


@jax.jit
def uncharge_row(state: dict, ints: jax.Array) -> dict:
    """``uncharge_batch`` of ``ints[1]`` pages from domain ``ints[0]``."""
    return uncharge_batch(state, ints[:1], ints[1:])


def slot_gate(state: dict, slot_dom: jax.Array, step, prog=None) -> jax.Array:
    """May each slot advance this step?  Dispatches ``on_gate`` of the
    slot's domain program (default: no frozen/throttled ancestor)."""
    progs = as_programs(prog)

    def one(d):
        view = _chain_view(state, state["usage"], state["throttle_until"],
                           state["prog"], d)
        return (d >= 0) & gate_decision(progs, view, step)
    return jax.vmap(one)(slot_dom.astype(jnp.int32))


# -------------------------------------------------------------- host mirror


class DeviceDomainTable:
    """Host-side index allocator + lifecycle editor for the device state.

    This is the paper's 'lightweight user-space daemon': it creates and
    removes domains, configures limits, freezes/thaws, attaches and
    retunes the policy program — but the per-allocation enforcement runs
    on device inside the jitted step.
    """

    def __init__(self, capacity_pages: int, n_domains: int = 64,
                 cfg: ControllerConfig = ControllerConfig(),
                 prog: Optional[PolicyProgram] = None):
        self.cfg = cfg
        self.n = n_domains
        self.progs = as_programs(prog if prog is not None else cfg)
        self.scopes = ["/"] * len(self.progs)
        self.state = new_state(capacity_pages, n_domains, self.progs)
        self.index: dict[str, int] = {"/": 0}
        # each live path's cpu.weight: only the host writes that column
        self.weights: dict[str, int] = {"/": D.DEFAULT_WEIGHT}
        self._free = list(range(1, n_domains))   # heap: lowest index first

    # ------------------------------------------------------------ programs

    @property
    def prog(self) -> PolicyProgram:
        """The primary (slot 0) program — the registry's trace constants
        (``step_ms`` etc.) and the single-program compatibility surface."""
        return self.progs[0]

    @property
    def attach_scope(self) -> str:
        return self.scopes[0]

    def in_scope(self, path: str) -> bool:
        return path_in_scope(self.attach_scope, path)

    def attach(self, scope: str, prog: PolicyProgram) -> None:
        """Attach ``prog`` to the subtree at ``scope`` (a recompile for
        jitted consumers — like loading a new BPF object).  A root
        attach resets the registry to this single program, every domain
        on its default row (the pre-registry semantics, bit-identical).
        A subtree attach COMPOSES: the program takes a registry slot
        (replacing a previous attach at the same scope), domains inside
        ``scope`` move to it on its default row, and domains outside
        keep their current program and live rows — different tenants
        run truly different enforcement code."""
        prog = as_program(prog)
        if scope == "/":
            self.progs = (prog,)
            self.scopes = ["/"]
            rows = np.broadcast_to(prog.default_row(),
                                   (self.n, prog.n_params)).copy()
            self.state = dict(self.state, prog=jnp.asarray(rows),
                              prog_id=jnp.zeros((self.n,), jnp.int32))
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
        rows = np.zeros((self.n, width), np.float32)
        keep = min(width, old.shape[1])
        rows[:, :keep] = old[:, :keep]
        ids = to_host(self.state["prog_id"]).copy()
        for path, idx in self.index.items():
            if path_in_scope(scope, path):
                ids[idx] = k
                rows[idx] = pad_row(prog.default_row(), width)
        self.state = dict(self.state, prog=jnp.asarray(rows),
                          prog_id=jnp.asarray(ids))

    def update_params(self, paths: list, kv: dict) -> None:
        """Retune the live program for the given domains — a pure state
        write, never a retrace.  Each domain resolves column names
        through its OWN program (its ``prog_id`` slot); names unknown
        to every registered program raise ``KeyError``."""
        unknown = registry_unknown_params(self.progs, kv)
        if unknown:
            raise KeyError(
                f"no registered program has param(s) {sorted(unknown)}; "
                f"knobs: {sorted(set().union(*(p.param_names for p in self.progs)))}")
        ids = to_host(self.state["prog_id"])
        prog = self.state["prog"]
        for p in paths:
            idx = self.index[p]
            pr = self.progs[int(ids[idx])]
            for k, v in kv.items():
                if k in pr.param_names:
                    prog = prog.at[idx, pr.col(k)].set(float(v))
        self.state = dict(self.state, prog=prog)

    # ------------------------------------------------------------ lifecycle

    def flat_row(self) -> np.ndarray:
        """The ``flat_weight`` row, re-flattened scx_flatcg style from the
        host's mirror of every live domain's ``cpu.weight``."""
        # imported here: core/sched.py imports this module
        from repro.core.sched import flat_weights_by_path
        flat = flat_weights_by_path({p: self.weights[p] for p in self.index})
        row = np.zeros((self.n,), np.float32)
        for p, i in self.index.items():
            row[i] = flat[p]
        return row

    def create(self, path: str, *, high: int = UNLIMITED, max: int = UNLIMITED,
               low: int = 0, priority: int = D.NORMAL,
               weight: int = D.DEFAULT_WEIGHT,
               cpu_max: int = UNLIMITED) -> int:
        assert path not in self.index, path
        parent_path = path.rsplit("/", 1)[0] or "/"
        pidx = self.index[parent_path]
        idx = self._free[0]
        ints = np.array([idx, pidx, high, max, low, priority, weight,
                         cpu_max], np.int32)
        heapq.heappop(self._free)
        self.index[path] = idx
        self.weights[path] = int(ints[6])
        note("table_update")
        self.state = create_row(self.state, ints, self.flat_row())
        return idx

    def remove(self, path: str, *, transfer: bool) -> jax.Array:
        """Retire ``path``; its residual usage leaves the ancestor chain
        and, with ``transfer``, is charged to the parent's. Returns the
        residual as a device scalar: reading it is the caller's choice."""
        idx = self.index.pop(path)
        del self.weights[path]
        note("table_update")
        self.state, residual = remove_row(self.state, np.int32(idx),
                                          self.flat_row(),
                                          transfer=transfer)
        heapq.heappush(self._free, idx)
        return residual

    def charge_unchecked(self, path: str, pages: int) -> None:
        """Unconditional hierarchical charge (``host_charge``)."""
        note("table_update")
        self.state = charge_unchecked_row(
            self.state, np.array([self.index[path], pages], np.int32))

    def uncharge(self, path: str, pages: int) -> None:
        note("table_update")
        self.state = uncharge_row(
            self.state, np.array([self.index[path], pages], np.int32))

    def set_frozen(self, path: str, flag: bool) -> None:
        idx = self.index[path]
        st = self.state
        self.state = dict(st, frozen=st["frozen"].at[idx].set(flag))

    def usage(self, path: str) -> int:
        return int(to_host(self.state["usage"][self.index[path]]))

    def peak(self, path: str) -> int:
        return int(to_host(self.state["peak"][self.index[path]]))
