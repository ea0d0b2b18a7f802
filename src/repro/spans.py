"""Spans and counters on the served path.

A ``Tracer`` records what the engine, its control-plane facade and its
slot caches were doing, on the host's ``perf_counter_ns`` clock. Each
span is also opened as a ``jax.profiler.TraceAnnotation`` named
``agentcgroup.<name>``, so a profiler trace shows it on the host plane,
on the clock its device events use; the step's root span is a
``StepTraceAnnotation("agentcgroup.step")``::

    tracer = Tracer(on=True)
    eng = Engine(cfg, params, tracer=tracer)
    ...                                  # eng.submit / eng.step
    tracer.write_jsonl("spans.jsonl")    # one JSON object per line

Three kinds of record:

* nested spans (``span``), each with its parent and the engine step it
  ran in;
* request spans (``begin`` / ``end``), keyed by a session id, which
  cross steps (queued, tool call, stalled, frozen). They have no
  profiler annotation, because a profiler scope has to nest;
* per-step counters (``count``): ``host_sync`` counts device-to-host
  reads (every one goes through ``to_host``); ``table_update`` counts
  the compiled lifecycle edits of the device domain table
  (``core/controller.py``). Counts made between two steps go to the
  next step.

Spans are recorded on the calling thread only: on the async backend the
daemon thread's work shows up as the engine's wait in ``engine.flush``.
Off (``OFF``, the engine's default) every call returns after one
attribute check and allocates nothing. Spans never enter control state
or a snapshot.

On, the tracer keeps every record in memory until ``drain`` (or
``write_jsonl``) takes it: about 13 spans and one counter dict a step,
plus one open request span per waiting session. A server that runs
with the tracer on has to drain it on a schedule, say every few hundred
steps, each time to a new file.
"""
from __future__ import annotations

import contextvars
import json
import time
from typing import NamedTuple, Optional

import jax
import numpy as np

PREFIX = "agentcgroup."


class Span(NamedTuple):
    id: int
    name: str
    parent: int              # id of the enclosing span; -1 at the top
    step: int                # the engine step open (or last run) at its start
    t0_ns: int               # time.perf_counter_ns()
    t1_ns: int               # -1 while a request span is still open
    key: Optional[str]       # the session id of a request span
    attrs: Optional[dict]


# the tracer whose span is open on this thread (and context), for
# ``note`` and ``to_host``: the backends that read and edit the device do
# not hold a tracer.
# Each span sets it on entry and restores the previous value on exit, so
# it names the innermost open span's tracer and nothing once the
# outermost span closes. Several engines on one thread therefore count
# each read for the engine whose step (or facade call) made it, since
# one engine's spans close before another's open; a context variable is
# also per thread, so engines on other threads never see it.
_ACTIVE: contextvars.ContextVar = contextvars.ContextVar(
    "agentcgroup_tracer", default=None)


def note(name: str) -> None:
    """Count ``name`` once on the tracer whose span is open on this
    thread; with none open, do nothing."""
    t = _ACTIVE.get()
    if t is not None:
        t.count(name)


def to_host(x) -> np.ndarray:
    """``np.asarray(x)``: one device-to-host read, counted as
    ``host_sync`` by the tracer whose span is open on this thread."""
    note("host_sync")
    return np.asarray(x)


class _Null:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _Null()


class _Open:
    """One nested span while it is open."""
    __slots__ = ("tr", "name", "attrs", "ann", "id", "parent", "t0",
                 "token")

    def __init__(self, tr: "Tracer", name: str, attrs, ann):
        self.tr, self.name, self.attrs, self.ann = tr, name, attrs, ann

    def __enter__(self):
        tr = self.tr
        self.parent = tr._stack[-1] if tr._stack else -1
        self.id = tr._new_id()
        tr._stack.append(self.id)
        self.token = _ACTIVE.set(tr)
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        _ACTIVE.reset(self.token)
        tr = self.tr
        tr._stack.pop()
        tr._spans.append(Span(self.id, self.name, self.parent, tr._step,
                              self.t0, t1, None, self.attrs))
        return False


class _Step(_Open):
    """The root span of one engine step: it sets the step number its
    children carry and closes the step's counters."""
    __slots__ = ("step_no",)

    def __enter__(self):
        self.tr._step = self.step_no
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        tr = self.tr
        tr._step_counts.append((self.step_no, tr._counts))
        tr._counts = {}
        return False


class Tracer:
    def __init__(self, on: bool = False):
        self.on = on
        self._spans: list[Span] = []
        self._step_counts: list[tuple[int, dict]] = []
        self._counts: dict[str, int] = {}
        self._open: dict[tuple, tuple] = {}  # (name, key) -> begin record
        self._stack: list[int] = []
        self._step = -1
        self._ids = 0

    def _new_id(self) -> int:
        self._ids += 1
        return self._ids

    def step(self, step_no: int):
        """The root span of engine step ``step_no``."""
        if not self.on:
            return _NULL
        op = _Step(self, "step", None, jax.profiler.StepTraceAnnotation(
            PREFIX + "step", step_num=step_no))
        op.step_no = step_no
        return op

    def span(self, name: str, attrs: Optional[dict] = None):
        """A nested span around a ``with`` block."""
        if not self.on:
            return _NULL
        return _Open(self, name, attrs,
                     jax.profiler.TraceAnnotation(PREFIX + name))

    def begin(self, name: str, key: str, attrs: Optional[dict] = None):
        """Open request span ``name`` of session ``key``; it ends at
        ``end(name, key)``, in this step or a later one."""
        if self.on:
            self._open[(name, key)] = (self._new_id(), self._step,
                                       time.perf_counter_ns(), attrs)

    def end(self, name: str, key: str) -> None:
        """Close request span ``name`` of ``key``; a no-op if none is
        open (it began while the tracer was off)."""
        if self.on:
            rec = self._open.pop((name, key), None)
            if rec is not None:
                i, step, t0, attrs = rec
                self._spans.append(Span(i, name, -1, step, t0,
                                        time.perf_counter_ns(), key, attrs))

    def count(self, name: str, n: int = 1) -> None:
        if self.on:
            self._counts[name] = self._counts.get(name, 0) + n

    def drain(self) -> dict:
        """What was recorded since the last drain, which it clears:
        ``spans`` (closed spans, in the order they closed), ``counters``
        ((step, {name: count}) per finished step) and ``open`` (request
        spans still open, ``t1_ns`` -1; they stay open)."""
        out = {"spans": self._spans, "counters": self._step_counts,
               "open": [Span(i, name, -1, step, t0, -1, key, attrs)
                        for (name, key), (i, step, t0, attrs)
                        in self._open.items()]}
        self._spans, self._step_counts = [], []
        return out

    def write_jsonl(self, path) -> int:
        """Drain to ``path`` as JSON lines, one per span (open request
        spans with ``t1_ns`` -1) and one per step's counters; returns
        the number of lines."""
        rec = self.drain()
        n = 0
        with open(path, "w") as f:
            for s in rec["spans"] + rec["open"]:
                f.write(json.dumps(s._asdict()) + "\n")
                n += 1
            for step, counts in rec["counters"]:
                f.write(json.dumps({"step": step, "counters": counts}) + "\n")
                n += 1
        return n


# the shared tracer that is off: the default of every engine, facade and
# cache; never turn it on
OFF = Tracer()
