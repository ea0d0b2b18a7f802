"""Closed-loop agent fleets: the one traffic generator every mix file drives.

``agents`` agents each run coding tasks back to back through the engine's
public entry (``Engine.submit`` / ``Engine.step``). An agent waits for each
reply before its next tool call (the session's phases), and when a task ends
(DONE or EVICTED) its next task is submitted at once. With more agents than
slots the rest wait in the engine's admission queue.

The sizes and order of the work come from the mix file alone (a fixed task
corpus, agent ``a`` running list ``a`` from its own start step); ``--seed`` makes the prompt token ids
and the weights. Token values steer no control flow (phases are counts), so
every seed serves the same work: which agent, tenant and priority runs which
task changes the control plane's decisions, and with it the numbers, far
more than two runs of one seed differ.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from repro.core import domains as D
from repro.serving.session import Phase, Session, SState

from bench import tracegen


class StepClock:
    """The host time at which the latest engine step's outputs were ready;
    every token a step produces is stamped with it."""

    def __init__(self):
        self.t_ready = 0.0


@dataclass(eq=False)
class AgentSession(Session):
    """A session that records what it was served: the token at every cache
    position (``ctx``), each reply token with the position whose logits
    produced it (``served``), and the time of every reply token."""
    agent: int = -1
    clock: Optional[StepClock] = None
    ready_t: float = 0.0             # when this turn's input was ready
    last_t: Optional[float] = None   # time of the previous reply token
    ctx: list = field(default_factory=list)
    served: list = field(default_factory=list)    # (position, token)
    turns: list = field(default_factory=list)     # (t_first, seconds)
    gaps: list = field(default_factory=list)      # (t, seconds)

    def advance(self, sampled: int) -> None:
        pos = self.length
        feeding = bool(self.feed_queue)
        del self.ctx[pos:]
        self.ctx.append(int(self.next_input()))
        phase = self.phase_idx
        super().advance(sampled)
        if feeding and self.feed_queue:
            return                       # a fed token; its output is dropped
        if self.finished:
            return                       # last tool result in: the task ends
        t = self.clock.t_ready
        self.served.append((pos, int(sampled)))
        if feeding:
            self.turns.append((t, t - self.ready_t))
        else:
            self.gaps.append((t, t - self.last_t))
        self.last_t = t
        if self.phase_idx != phase:      # the reply ended in a tool call,
            self.ready_t = t             # whose result is appended at once

    def rollback_burst(self, scale: float) -> int:
        freed = super().rollback_burst(scale)
        self.served = [(p, tok) for p, tok in self.served if p < self.length]
        return freed


def task_phases(trace, sess: dict) -> list:
    """A trace's tool calls, in time order, as (generated, appended,
    category) phases: the arithmetic of the program's
    ``session_from_trace``."""
    calls = sorted(trace.tool_calls, key=lambda c: c.t_start_s)
    return [(sess["gen_per_call"],
             max(4, int(c.peak_mb * sess["tokens_per_mb"])), c.category)
            for c in calls[:sess["max_phases"]]]


def task_corpus(traffic: dict) -> list:
    """Per agent, its list of tasks (each a list of phases); fixed by the
    mix file."""
    t, sess = traffic["tasks"], traffic["session"]
    n_agents, per = traffic["agents"], t["tasks_per_agent"]
    n = n_agents * per
    if t["kind"] == "generate_task":
        models = t["models"]
        traces = [tracegen.generate_task(
            f"task-{i:04d}", models[i % len(models)],
            seed=t["corpus_seed"] * 100003 + i, scale=t.get("scale"))
            for i in range(n)]
    elif t["kind"] == "generate_spike_corpus":
        traces = tracegen.generate_spike_corpus(
            n, seed=t["corpus_seed"], model=t.get("model", "haiku"))
    else:
        raise ValueError(f"unknown task kind {t['kind']!r}")
    phases = [task_phases(tr, sess) for tr in traces]
    return [phases[a * per:(a + 1) * per] for a in range(n_agents)]


def start_steps(traffic: dict) -> list:
    """The warm-up step at which each agent submits its first task. One
    agent per slot starts, in turn, evenly over ``start_spread_steps``; the
    agents beyond the slots start with the last of them and queue. So the
    contexts in the slots are spread over their steady range when the last
    agent has started, and the admission queue is full, as in a fleet that
    has been running."""
    n, spread = traffic["agents"], traffic.get("start_spread_steps", 0)
    slots = traffic["engine"]["max_slots"]
    return [min(a, slots - 1) * spread // slots for a in range(n)]


class Fleet:
    """Drives one engine with a closed-loop fleet described by a mix
    file."""

    def __init__(self, eng, traffic: dict, seed: int, vocab: int):
        self.eng = eng
        self.traffic = traffic
        self.clock = StepClock()
        self.rng = np.random.default_rng(seed)
        n = traffic["agents"]
        self.lists = task_corpus(traffic)
        self.starts = start_steps(traffic)
        self.steps = 0
        self.vocab = vocab
        self.next_task = [0] * n
        self.current: list[Optional[AgentSession]] = [None] * n
        self.ended: list[AgentSession] = []      # DONE or EVICTED, in order
        self.submitted = 0
        self.pool_overshoot = 0
        self.capacity = eng.pool_capacity
        self._page = eng.ecfg.page_tokens
        # per session domain, the (priority, memory.low) its agent is due:
        # a HIGH agent's sessions are protected by the whole pool
        self.spec_of: dict[str, tuple] = {}

    def _session(self, a: int) -> AgentSession:
        tr, sess = self.traffic, self.traffic["session"]
        k = self.next_task[a]
        self.next_task[a] += 1
        tasks = self.lists[a]
        phases = tasks[k % len(tasks)]
        prompt = self.rng.integers(2, self.vocab, sess["prompt_tokens"])
        high = a in tr["high_agents"]
        s = AgentSession(
            sid=f"a{a}.{k}", tenant=f"t{tr['tenant_of_agent'][a]}",
            priority=D.HIGH if high else D.LOW,
            prompt=[int(x) for x in prompt],
            phases=[Phase(g, n, c) for g, n, c in phases],
            agent=a, clock=self.clock)
        self.spec_of[s.domain] = (s.priority,
                                  tr["engine"]["pool_pages"] if high else 0)
        return s

    def _submit(self, a: int) -> None:
        s = self._session(a)
        s.ready_t = time.perf_counter()
        self.current[a] = s
        self.submitted += 1
        self.eng.submit(s)

    def start(self) -> None:
        for a, at in enumerate(self.starts):
            if at == 0:
                self._submit(a)

    def step_after(self) -> None:
        """After an engine step: submit each agent whose start step has
        come and each ended task's successor, and count the pages the
        sessions in slots hold."""
        self.steps += 1
        used = 0
        for a, s in enumerate(self.current):
            if s is None:
                if self.starts[a] <= self.steps:
                    self._submit(a)
            elif s.state in (SState.DONE, SState.EVICTED):
                self.ended.append(s)
                self._submit(a)
            elif s.state is SState.RUNNING and s.slot >= 0:
                used += math.ceil(s.length / self._page)
        self.pool_overshoot = max(self.pool_overshoot, used - self.capacity)

    def open_waits(self, t: float) -> list:
        """At time ``t``, how long each in-flight session has waited for
        its next reply token: (priority, "gap" or "turn", seconds). A wait
        that has not ended by the window's close (a frozen or throttled
        session, a task still in the admission queue) counts in the tails
        as at least this long."""
        out = []
        for s in self.current:
            if s is None or s.state in (SState.DONE, SState.EVICTED):
                continue
            if s.last_t is not None and s.last_t > s.ready_t:
                out.append((s.priority, "gap", t - s.last_t))
            else:
                out.append((s.priority, "turn", t - s.ready_t))
        return out

    def sessions(self) -> list:
        return self.ended + [s for s in self.current if s is not None]
