"""Multi-tenant continuous-batching engine with AgentCgroup enforcement.

Every engine step advances all active slots by one token (uniform
chunked prefill: prompt/tool-result tokens are force-fed one per step,
so *every* context-page allocation flows through the same charge path a
decoded token uses).  The resource controller runs in one of two modes:

  * ``inkernel``  — the AgentCgroup design: the control plane's
    ``device_view().charge`` executes INSIDE the jitted step; a slot
    whose page charge is denied (hard limit, freeze, throttle) simply
    does not advance *this same step*.  Microsecond-class reaction, no
    host round trip.
  * ``userspace`` — the baseline the paper's §4.2 criticizes: the daemon
    observes usage with a poll interval + reaction latency and gates
    slots one-or-more steps late; bursts land before control does (the
    engine measures the resulting budget overshoot).

Host-side daemon work (lifecycle only, as in the paper): admission,
per-tool-call child domains with intent-hint highs, freeze/thaw with
state offload (SlotCaches/FrozenStore), downward feedback that lets a
session shrink a pending context append (strategy reconstruction).
With ``EngineConfig(backend="async")`` that lifecycle work runs on the
``AsyncDaemonBackend`` daemon thread in FIFO epochs applied at the
``cg.flush()`` each step issues before reading control state —
bit-exact with the synchronous backends, lifecycle off the step
critical path.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import domains as D
from repro.core import pressure as PSI
from repro.core.adaptive import AdaptiveConfig, AdaptiveController
from repro.core.cgroup import (AgentCgroup, DeviceTableBackend, DeviceView,
                               DomainSpec)
from repro.core.controller import ControllerConfig
from repro.core.daemon import AsyncDaemonBackend, DaemonError
from repro.core.events import Ev, EventLog
from repro.core.intent import Hint
from repro.core.progs import PolicyProgram
from repro.models import model as M
from repro.perf import PerfConfig, DEFAULT_PERF
from repro.serving.kvcache import PageAccountant, SlotCaches
from repro.serving.sampling import sample
from repro.serving.session import Phase, Session, SState


@dataclass(frozen=True)
class EngineConfig:
    max_slots: int = 8
    s_max: int = 512
    pool_pages: int = 256                # KV pool per device group
    page_tokens: int = 16
    mode: str = "inkernel"               # inkernel | userspace | nolimit
    backend: str = "device"              # device | sharded | async
    async_inner: str = "device"          # async: the wrapped backend
    n_shards: Optional[int] = None       # sharded: device-group count
    ctrl: ControllerConfig = ControllerConfig(step_ms=10.0)
    temperature: float = 0.0
    # daemon knobs
    freeze_threshold: float = 0.97
    thaw_threshold: float = 0.80
    feedback_patience_steps: int = 40
    evict_patience_steps: int = 400
    userspace_poll_steps: int = 8        # PSI-poll analogue
    userspace_react_steps: int = 4       # daemon decision+write latency
    use_intent: bool = True
    use_tool_domains: bool = True
    use_freeze: bool = True              # graceful-degradation step 2
    # weighted CPU scheduler (cpu.weight / cpu.max): when set, at most
    # ``sched_slots`` weighted slots advance per step, picked by the
    # hierarchical fair scheduler (core/sched.py).  None keeps the
    # binary slot gate — the pre-scheduler behavior, bit for bit.
    sched_slots: Optional[int] = None
    # closed-loop adaptive retuner over memory.pressure / cpu.pressure
    # (core/adaptive.py): polls at step boundaries (the async backend's
    # epoch cadence), bumps soft limits / retunes params through
    # zero-retrace knobs.  None (the default) keeps behavior
    # bit-identical — the loop never runs, no pressure file is read.
    adaptive: Optional[AdaptiveConfig] = None
    # intent hints in engine pages (LOW/MEDIUM/HIGH priority of Hint enum)
    intent_high_pages: Optional[dict] = None
    session_high: Optional[dict] = None  # sid -> memory.high (pages)
    max_steps: int = 20_000


def _gate_shape(gate, x):
    return gate.reshape((1, gate.shape[0]) + (1,) * (x.ndim - 2))


def _slot_sharding(mesh) -> NamedSharding:
    """Decode-state placement on a control mesh: every state leaf is
    ``(n_groups, slots, ...)``, split over the mesh's axis by slot."""
    return NamedSharding(mesh, P(None, mesh.axis_names[0]))


def _make_step_fn(cfg: ModelConfig, perf: PerfConfig, ecfg: EngineConfig,
                  view: DeviceView):
    decode = functools.partial(M.decode_step, cfg, perf=perf)
    mesh = getattr(view, "mesh", None)
    if mesh is not None:
        # Pallas kernels are not partitioned automatically: on a control
        # mesh each device decodes its own slots with the replicated
        # weights (data parallel over the slot axis)
        slots = P(mesh.axis_names[0])
        state = _slot_sharding(mesh).spec
        decode = jax.shard_map(decode, mesh=mesh,
                               in_specs=(P(), state, slots, slots),
                               out_specs=(slots, state), check_vma=False)

    @functools.partial(jax.jit, static_argnames=("mode",), donate_argnums=(1, 2))
    def step_fn(params, dstate, ctrl, tokens, lengths, dom, amt, host_gate,
                step_no, key, *, mode: str):
        if ecfg.sched_slots is not None:
            # weighted step scheduler: rank this step's runnable slots by
            # vruntime and grant at most sched_slots of them; a slot the
            # scheduler defers simply does not advance this step (its
            # charge never reaches the memory controller).  Slots whose
            # program weight is <= 0 bypass the budget entirely, so the
            # stock program keeps this a no-op.
            cost = (dom >= 0).astype(jnp.int32)
            ctrl, advance = view.schedule(ctrl, dom, cost, step_no,
                                          ecfg.sched_slots)
            dom = jnp.where(advance, dom, -1)
        if mode == "inkernel":
            # in-step enforcement: charge + gate inside the same program
            ctrl, granted, stalled = view.charge(ctrl, dom, amt, step_no)
            gate = granted
        else:
            # user-space baseline: the (stale) host gate decides; usage is
            # charged after the fact, so bursts overshoot the budget
            gate = host_gate & (dom >= 0)
            ctrl = view.account(ctrl, jnp.where(gate, dom, -1), amt)
            granted, stalled = gate, (dom >= 0) & ~gate
        logits, new_state = decode(params, dstate, tokens, lengths)
        finite = jnp.isfinite(logits).all()
        nxt = sample(logits, key, temperature=ecfg.temperature)
        new_state = jax.tree.map(
            lambda n, o: jnp.where(_gate_shape(gate, n), n, o),
            new_state, dstate)
        nxt = jnp.where(gate, nxt, tokens)
        return nxt, new_state, ctrl, granted, stalled, finite

    return step_fn


@dataclass
class EngineMetrics:
    root_usage: list = field(default_factory=list)
    overshoot_pages: int = 0             # max pages over pool budget
    session_overshoot_pages: int = 0     # max pages over any session high
    throttle_triggers: int = 0
    n_feedbacks: int = 0
    n_freezes: int = 0
    n_thaws: int = 0
    n_evictions: int = 0
    n_rebuilds: int = 0                  # poisoned-daemon backend rebuilds
    nonfinite_logit_steps: int = 0       # steps whose logits held NaN/Inf
    steps: int = 0


class Engine:
    def __init__(self, cfg: ModelConfig, params, *,
                 perf: PerfConfig = DEFAULT_PERF,
                 ecfg: EngineConfig = EngineConfig(), seed: int = 0):
        self.cfg = cfg
        self.params = params
        self.perf = perf
        self.ecfg = ecfg
        self.caches = SlotCaches(cfg, ecfg.max_slots, ecfg.s_max)
        self.accountant = PageAccountant(ecfg.page_tokens)
        be = self._make_inner()
        mesh = getattr(be, "mesh", None)
        if mesh is not None:
            # one jitted step takes the weights, the decode state and the
            # control state together, so all three live on the control
            # mesh: the control state split by tenant, the decode state
            # (KV cache) split by slot, the weights a full replica on
            # every device
            if ecfg.max_slots % mesh.devices.size:
                raise ValueError(
                    f"max_slots={ecfg.max_slots} does not split over the "
                    f"{mesh.devices.size}-device control mesh")
            self.params = jax.device_put(params, NamedSharding(mesh, P()))
            self.caches.state = jax.device_put(self.caches.state,
                                               _slot_sharding(mesh))
        if ecfg.backend == "async":
            # lifecycle off the hot path: mkdir/rmdir/write/freeze/thaw/
            # lease ops run on the daemon thread in FIFO epochs, applied
            # at the flush() in step() — the jitted enforcement path
            # closes over the INNER backend's device view and never
            # blocks on lifecycle work
            be = AsyncDaemonBackend(be)
        self.cg = AgentCgroup(be)
        # the engine's facade clock counts steps (set_time(step_no)),
        # not ms: one step per clock unit, PSI windows converted from
        # ms to steps via the controller's step_ms
        self.cg.pressure_clock(
            step_quantum=1.0,
            windows=(PSI.AVG10_MS / ecfg.ctrl.step_ms,
                     PSI.AVG60_MS / ecfg.ctrl.step_ms))
        self._adaptive = (AdaptiveController(self.cg, ecfg.adaptive)
                          if ecfg.adaptive is not None else None)
        self._adaptive_epoch = None
        # pool_pages is per device group: each shard root is capped at
        # pool_pages in-step, so the aggregate the daemon reasons about
        # (root_usage sums every group) is pool_pages * n_shards
        self.pool_capacity = ecfg.pool_pages * getattr(be, "n_shards", 1)
        self._view = self.cg.device_view()
        self.log = EventLog()
        self.metrics = EngineMetrics()
        self.sessions: dict[str, Session] = {}
        self.waiting: list[str] = []
        self.slot_session: list[Optional[str]] = [None] * ecfg.max_slots
        self.step_no = 0
        self.key = jax.random.PRNGKey(seed)
        self._step = _make_step_fn(cfg, perf, ecfg, self._view)
        self._host_gate = np.ones(ecfg.max_slots, bool)
        self._lease: dict[str, object] = {}      # sid -> open tool Lease
        self._tool_seq = 0
        self._prev_throttle = np.zeros(self.cg.backend.n_domains, np.int64)
        # ordered attach history (scope -> program, same-scope replaces in
        # place) so a backend rebuild replays the exact registry slots
        self._attachments: list = []
        self._last_snapshot: Optional[dict] = None

    def _make_inner(self):
        e = self.ecfg
        n_domains = 4 * e.max_slots + 8
        inner_kind = e.async_inner if e.backend == "async" else e.backend
        if inner_kind == "sharded":
            from repro.core.sharded import ShardedTableBackend
            return ShardedTableBackend(e.pool_pages, n_domains=n_domains,
                                       cfg=e.ctrl, n_shards=e.n_shards)
        return DeviceTableBackend(e.pool_pages, n_domains=n_domains,
                                  cfg=e.ctrl)

    # ---------------------------------------------------- policy programs

    def attach_program(self, prog: PolicyProgram, path: str = "/") -> None:
        """Swap or compose in-step enforcement programs (BPF object
        load): the next step re-traces against the new decision code.
        A root attach replaces the whole registry; a subtree attach at
        ``path`` composes — that tenant's domains run ``prog`` while
        everyone else keeps theirs (``AgentCgroup.attach``).  For pure
        parameter retunes use ``update_params`` — no retrace."""
        if path == "/":
            self._attachments = [("/", prog)]
        else:
            for i, (p, _) in enumerate(self._attachments):
                if p == path:
                    self._attachments[i] = (path, prog)
                    break
            else:
                self._attachments.append((path, prog))
        self.cg.attach(path, prog)
        self._view = self.cg.device_view()
        self._step = _make_step_fn(self.cfg, self.perf, self.ecfg,
                                   self._view)

    def update_params(self, path: str = "/", **kv) -> None:
        """Retune the live program mid-run (BPF map write): plain state,
        takes effect the following step, never recompiles."""
        self.cg.update_params(path, **kv)

    # ------------------------------------------------------------ admission

    def submit(self, session: Session) -> None:
        self.sessions[session.sid] = session
        tenant_path = f"/{session.tenant}"
        if not self.cg.exists(tenant_path):
            self.cg.mkdir(tenant_path)
        self.waiting.append(session.sid)

    def _try_admit(self) -> None:
        still = []
        for sid in self.waiting:
            s = self.sessions[sid]
            slot = self.caches.alloc_slot()
            if slot is None:
                still.append(sid)
                continue
            s.slot = slot
            low = 0
            if s.priority == D.HIGH:
                low = self.ecfg.pool_pages            # below_low protection
            high = (self.ecfg.session_high or {}).get(s.sid, D.UNLIMITED)
            s.dom_idx = self.cg.mkdir(s.domain, DomainSpec(
                priority=s.priority, low=low, high=high))
            s.t_admit = self.step_no
            self.slot_session[slot] = sid
            s.start()
            self.log.emit(self.step_no, Ev.ADMIT, s.domain)
        self.waiting = still

    # --------------------------------------------------- tool-call domains

    def _sync_tool_domain(self, s: Session) -> None:
        """Ephemeral child domain per tool-result burst (bash-wrapper
        analogue); intent hints set its memory.high."""
        if not self.ecfg.use_tool_domains:
            return
        in_burst = bool(s.feed_queue) and s.length > len(s.prompt)
        has = s.sid in self._lease
        if in_burst and not has:
            self._tool_seq += 1
            high = D.UNLIMITED
            hint = None
            if self.ecfg.use_intent:
                table = self.ecfg.intent_high_pages or {
                    Hint.LOW: 4, Hint.MEDIUM: 10, Hint.HIGH: 24}
                hint = s.declared_hint()
                high = table.get(hint, table[Hint.MEDIUM])
            lease = self.cg.intent.declare(f"tool_{self._tool_seq}", hint,
                                           parent=s.domain,
                                           priority=s.priority, high=high)
            self._lease[s.sid] = lease
            s.dom_idx = self.cg.handle(lease.path)
        elif not in_burst and has:
            # context pages persist: lease close moves the residual
            # charge up to the session
            self._lease.pop(s.sid).close()
            s.dom_idx = self.cg.handle(s.domain)

    # -------------------------------------------------------------- daemon

    def _userspace_policy(self) -> None:
        """User-space throttle daemon: the SAME graduated-delay policy the
        in-kernel path applies, but computed from telemetry polled every
        ``userspace_poll_steps`` and applied ``userspace_react_steps``
        late — the §4.2 responsiveness gap.  Bursts land before control
        does; the per-session ``high`` overshoot metric quantifies it."""
        e = self.ecfg
        if self.step_no % e.userspace_poll_steps == 0:
            snap = self.cg.snapshot()
            usage, high, maxl = snap["usage"], snap["high"], snap["max"]
            parent = snap["parent"]
            progs = self.cg.programs
            ids = snap.get("prog_id")
            decisions = {}
            for slot, sid in enumerate(self.slot_session):
                if sid is None:
                    continue
                s = self.sessions[sid]
                chain = [s.dom_idx]
                while parent[chain[-1]] >= 0:
                    chain.append(int(parent[chain[-1]]))
                over = max((usage[i] - high[i]) / max(high[i], 1)
                           for i in chain)
                hard = any(usage[i] >= maxl[i] for i in chain)
                if over > 0 or hard:
                    # the SAME delay curve the in-step program applies,
                    # computed from the session's live param row through
                    # the session's OWN program (its prog_id slot) —
                    # just polled late, the §4.2 responsiveness gap
                    pid = int(ids[s.dom_idx]) if ids is not None else 0
                    pr = progs[min(pid, len(progs) - 1)]
                    dly_ms = float(pr.delay_ms(
                        snap["params"][s.dom_idx], max(float(over), 0.0)))
                    dly = int(np.ceil(dly_ms / pr.step_ms)) or 1
                    decisions[slot] = self.step_no + e.userspace_react_steps + dly
            self._pending_gate = (self.step_no + e.userspace_react_steps,
                                  decisions)

    def _apply_pending_gate(self) -> None:
        pg = getattr(self, "_pending_gate", None)
        if pg is not None and self.step_no >= pg[0]:
            self._ungate_step = getattr(self, "_ungate_step",
                                        np.zeros(self.ecfg.max_slots))
            for slot, until in pg[1].items():
                self._ungate_step[slot] = max(self._ungate_step[slot], until)
                self.metrics.throttle_triggers += 1
            self._pending_gate = None
        ug = getattr(self, "_ungate_step", None)
        if ug is not None:
            self._host_gate = ug <= self.step_no

    def _daemon(self) -> None:
        e = self.ecfg
        snap = self.cg.snapshot()
        # last known-good step-boundary snapshot: the rebuild-from-
        # snapshot path (poisoned async daemon) restores from here
        self._last_snapshot = snap
        root_usage = int(snap.get("root_usage", snap["usage"][0]))
        self.metrics.root_usage.append(root_usage)
        self.metrics.overshoot_pages = max(
            self.metrics.overshoot_pages, root_usage - self.pool_capacity)
        usage, high = snap["usage"], snap["high"]
        lim = high < D.UNLIMITED
        if lim.any():
            self.metrics.session_overshoot_pages = max(
                self.metrics.session_overshoot_pages,
                int((usage[lim] - high[lim]).max()))
        # freeze under extreme pressure (graceful degradation step 2)
        if e.use_freeze and root_usage > e.freeze_threshold * self.pool_capacity:
            cands = [self.sessions[sid] for sid in self.slot_session
                     if sid is not None
                     and self.sessions[sid].state is SState.RUNNING
                     and self.sessions[sid].priority == D.LOW]
            if cands:
                victim = max(cands, key=lambda s: s.pages)
                self._freeze(victim)
        else:
            frozen = [s for s in self.sessions.values()
                      if s.state is SState.FROZEN]
            if frozen and self.caches.n_free > 0:
                cand = min(frozen, key=lambda s: s.pages)
                if (root_usage + cand.pages
                        < e.thaw_threshold * self.pool_capacity):
                    self._thaw(cand)
        if self._adaptive is not None:
            # closed loop: poll every step boundary for synchronous
            # backends; for the async daemon, once per applied epoch —
            # pressure reads observe the state the flush just settled
            epoch = snap.get("epoch")
            if epoch is None or epoch != self._adaptive_epoch:
                self._adaptive_epoch = epoch
                self._adaptive.poll(float(self.step_no))
        self._try_admit()

    def _freeze(self, s: Session) -> None:
        if s.sid in self._lease:
            self._lease.pop(s.sid).close()     # residual moves to session
        self.caches.freeze_slot(s.sid, s.slot, pages=s.pages,
                                meta={"length": s.length},
                                now=self.step_no)
        self.slot_session[s.slot] = None
        # release pages (offloaded to host) + freeze the domain
        self.cg.uncharge(s.domain, s.pages)
        self.cg.freeze(s.domain)
        s.slot = -1
        s.state = SState.FROZEN
        s.n_freezes += 1
        self.metrics.n_freezes += 1
        self.log.emit(self.step_no, Ev.FREEZE, s.domain, pages=s.pages)

    def _thaw(self, s: Session) -> None:
        slot, meta = self.caches.thaw_slot(s.sid)
        self.cg.thaw(s.domain)
        self.cg.charge_unchecked(s.domain, s.pages)   # thaw re-charge
        s.slot = slot
        s.dom_idx = self.cg.handle(s.domain)
        self.slot_session[slot] = s.sid
        s.state = SState.RUNNING
        self.metrics.n_thaws += 1
        self.log.emit(self.step_no, Ev.THAW, s.domain)

    def _finish(self, s: Session) -> None:
        if s.sid in self._lease:
            self._lease.pop(s.sid).close()
        self.cg.uncharge(s.domain, s.pages)
        self.cg.rmdir(s.domain, transfer_residual=False)
        self.caches.free_slot(s.slot)
        self.slot_session[s.slot] = None
        s.slot = -1
        s.state = SState.DONE
        s.t_done = self.step_no
        self.log.emit(self.step_no, Ev.DONE, s.domain)

    def _evict(self, s: Session) -> None:
        """Last resort — the paper's triple-penalty path; counted so the
        benchmarks can show how rarely it fires."""
        if s.sid in self._lease:
            self._lease.pop(s.sid).close()
        self.cg.uncharge(s.domain, s.pages)
        self.cg.rmdir(s.domain, transfer_residual=False)
        if s.slot >= 0:
            self.caches.free_slot(s.slot)
            self.slot_session[s.slot] = None
        s.state = SState.EVICTED
        s.t_done = self.step_no
        self.metrics.n_evictions += 1
        self.log.emit(self.step_no, Ev.EVICT, s.domain)

    # ------------------------------------------------- daemon-fault recovery

    def _rebuild_backend(self) -> None:
        """Survive a poisoned/wedged async daemon: drop the backend,
        stand up a fresh one from the last step-boundary ``snapshot()``,
        and reconcile anything newer than the snapshot from the engine's
        Python-side session state (which is authoritative)."""
        e = self.ecfg
        try:
            self.cg.backend.close(flush=False)
        except Exception:                # noqa: BLE001 — already poisoned
            pass
        inner = self._make_inner()
        for path, prog in self._attachments:
            inner.attach(path, prog)
        if self._last_snapshot is not None:
            inner.restore(self._last_snapshot)
        be = inner
        if e.backend == "async":
            be = AsyncDaemonBackend(inner)
        self.cg.backend = be
        self.cg.set_time(self.step_no)
        self._reconcile_sessions()
        self._view = self.cg.device_view()
        self._step = _make_step_fn(self.cfg, self.perf, self.ecfg,
                                   self._view)
        self._prev_throttle = np.asarray(
            self._view.state["throttle_until"]).reshape(-1).astype(
                np.int64).copy()
        self.metrics.n_rebuilds += 1
        self.log.emit(self.step_no, Ev.REBUILD, "/")

    def _reconcile_sessions(self) -> None:
        """The snapshot is up to one step-boundary stale: admissions,
        freeze/thaw flips and charge drift since it was taken exist only
        in the Session objects — re-apply them to the rebuilt tree."""
        e = self.ecfg
        for s in self.sessions.values():
            if s.state in (SState.DONE, SState.EVICTED):
                continue
            tenant_path = f"/{s.tenant}"
            if not self.cg.exists(tenant_path):
                self.cg.mkdir(tenant_path)
            if s.state is SState.WAITING:
                continue
            if not self.cg.exists(s.domain):
                low = e.pool_pages if s.priority == D.HIGH else 0
                high = (e.session_high or {}).get(s.sid, D.UNLIMITED)
                self.cg.mkdir(s.domain, DomainSpec(
                    priority=s.priority, low=low, high=high))
            lease = self._lease.get(s.sid)
            if lease is not None and not self.cg.exists(lease.path):
                # the lease postdates the snapshot: drop it rather than
                # resurrect it — the next burst step re-declares
                self._lease.pop(s.sid)
                self.cg.intent._open.pop(lease.path, None)
                lease.closed = True
                lease = None
            path = lease.path if lease is not None else s.domain
            s.dom_idx = self.cg.handle(path)
            frozen = bool(self.cg.read(s.domain, "cgroup.freeze"))
            if s.state is SState.FROZEN and not frozen:
                self.cg.freeze(s.domain)
            elif s.state is not SState.FROZEN and frozen:
                self.cg.thaw(s.domain)
            want = 0 if s.state is SState.FROZEN else s.pages
            have = self.cg.usage(s.domain)
            if want > have:
                self.cg.charge_unchecked(path, want - have)
            elif have > want:
                self.cg.uncharge(path, have - want)

    # ----------------------------------------------------------------- step

    def step(self) -> None:
        e = self.ecfg
        # epoch boundary: queued lifecycle ops (async backend) apply
        # here, before the step reads the control state — never between
        # the state read and the post-step commit.  A wedged/poisoned
        # daemon surfaces here as DaemonError; the engine rebuilds the
        # whole backend from the last step-boundary snapshot and the
        # step proceeds on the fresh control plane.
        try:
            self.cg.set_time(self.step_no)
            self.cg.flush()
        except DaemonError:
            self._rebuild_backend()
        if self.ecfg.mode == "userspace":
            self._userspace_policy()
            self._apply_pending_gate()
        tokens = np.zeros(e.max_slots, np.int32)
        lengths = np.zeros(e.max_slots, np.int32)
        dom = np.full(e.max_slots, -1, np.int32)
        amt = np.zeros(e.max_slots, np.int32)
        for slot, sid in enumerate(self.slot_session):
            if sid is None:
                continue
            s = self.sessions[sid]
            if s.state is not SState.RUNNING:
                continue
            self._sync_tool_domain(s)
            tokens[slot] = s.next_input() % self.cfg.padded_vocab
            lengths[slot] = min(s.length, e.s_max - 1)
            dom[slot] = s.dom_idx
            amt[slot] = self.accountant.crossing(s.length)
        self.key, sub = jax.random.split(self.key)
        nxt, self.caches.state, new_ctrl, granted, stalled, finite = \
            self._step(self.params, self.caches.state, self._view.state,
                       jnp.asarray(tokens), jnp.asarray(lengths),
                       jnp.asarray(dom), jnp.asarray(amt),
                       jnp.asarray(self._host_gate), self.step_no, sub,
                       mode=("inkernel" if e.mode == "inkernel"
                             else "userspace"))
        self._view.commit(new_ctrl)
        nxt = np.asarray(nxt)
        granted = np.asarray(granted)
        self.metrics.nonfinite_logit_steps += int(not bool(finite))
        # throttle-trigger accounting (memcg_bpf_ops delay counter)
        tu = np.asarray(self._view.state["throttle_until"]).reshape(-1)
        self.metrics.throttle_triggers += int(np.sum(tu > self._prev_throttle))
        self._prev_throttle = np.maximum(tu, self._prev_throttle)

        for slot, sid in enumerate(self.slot_session):
            if sid is None:
                continue
            s = self.sessions[sid]
            if s.state is not SState.RUNNING:
                continue
            if granted[slot]:
                if s.stall_started is not None:
                    s.alloc_latencies_steps.append(
                        self.step_no - s.stall_started)
                    s.stall_started = None
                elif amt[slot]:
                    s.alloc_latencies_steps.append(0)
                s.pages += int(amt[slot])
                s.advance(int(nxt[slot]))
                if s.finished or s.length >= e.s_max - 1:
                    self._finish(s)
            else:
                s.stall_steps += 1
                if s.stall_started is None:
                    s.stall_started = self.step_no
                stall = self.step_no - s.stall_started
                # graduated feedback: first shrink the pending append;
                # if the session is wedged against the pool wall, roll
                # the whole tool call back (subprocess-kill + retry
                # analogue) so its pages free and a smaller retry fits
                if (stall > 0 and stall % e.feedback_patience_steps == 0
                        and s.feed_queue):
                    fb = self.cg.intent.feedback(
                        s.domain, "throttled", peak=s.pages,
                        limit=int(self.cg.read(self.cg.path_of(s.dom_idx),
                                               "memory.high")))
                    if (stall >= 2 * e.feedback_patience_steps
                            and s.burst_start_len >= 0):
                        freed = s.rollback_burst(scale=0.5)
                        if freed:
                            self.cg.uncharge(s.dom_idx, freed)
                        s.feedbacks.append(fb)
                        self.log.emit(self.step_no, Ev.FEEDBACK, s.domain,
                                      action="rollback", freed=freed)
                    else:
                        s.apply_feedback(fb, scale=0.5)
                        self.log.emit(self.step_no, Ev.FEEDBACK, s.domain,
                                      action="shrink")
                    self.metrics.n_feedbacks += 1
                elif stall > e.evict_patience_steps:
                    self._evict(s)
        self._daemon()
        self.step_no += 1
        self.metrics.steps = self.step_no

    def close(self) -> None:
        """Release backend resources — stops the async lifecycle daemon
        thread (a no-op for the synchronous backends)."""
        fn = getattr(self.cg.backend, "close", None)
        if fn is not None:
            fn()

    def run(self, max_steps: Optional[int] = None) -> EngineMetrics:
        limit = max_steps or self.ecfg.max_steps
        for _ in range(limit):
            if all(s.state in (SState.DONE, SState.EVICTED)
                   for s in self.sessions.values()) and not self.waiting:
                break
            self.step()
        return self.metrics

    # -------------------------------------------------------------- report

    def report(self) -> dict:
        e = self.ecfg
        done = [s for s in self.sessions.values() if s.state is SState.DONE]
        evicted = [s for s in self.sessions.values()
                   if s.state is SState.EVICTED]
        lat_by_prio: dict[int, list] = {}
        for s in self.sessions.values():
            lat_by_prio.setdefault(s.priority, []).extend(
                x * e.ctrl.step_ms for x in s.alloc_latencies_steps)

        def pct(xs, p):
            if not xs:
                return 0.0
            xs = sorted(xs)
            return xs[min(len(xs) - 1, int(round(p / 100 * (len(xs) - 1))))]

        return {
            "mode": e.mode,
            "completed": len(done),
            "evicted": len(evicted),
            "survival": len(done) / max(len(self.sessions), 1),
            "steps": self.step_no,
            "high_p50_ms": pct(lat_by_prio.get(D.HIGH, []), 50),
            "high_p95_ms": pct(lat_by_prio.get(D.HIGH, []), 95),
            "low_p95_ms": pct(lat_by_prio.get(D.LOW, []), 95),
            "throttle_triggers": self.metrics.throttle_triggers,
            "freezes": self.metrics.n_freezes,
            "thaws": self.metrics.n_thaws,
            "feedbacks": self.metrics.n_feedbacks,
            "overshoot_pages": self.metrics.overshoot_pages,
            "session_overshoot_pages": self.metrics.session_overshoot_pages,
            "peak_pool_pages": max(self.metrics.root_usage, default=0),
            "nonfinite_logit_steps": self.metrics.nonfinite_logit_steps,
        }
