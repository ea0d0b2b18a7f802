"""The served path's spans and counters (repro/spans.py): the tracer on
its own, and inside a tiny engine on the CPU."""
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import domains as D
from repro.models import model as M
from repro.models.schema import init_params
from repro.perf import DEFAULT_PERF, replace as perf_replace
from repro.serving.engine import Engine, EngineConfig
from repro.serving.session import Phase, Session
from repro.spans import OFF, Tracer, to_host

PHASES = ["engine.flush", "engine.inputs", "engine.dispatch",
          "engine.readback", "engine.bookkeeping", "engine.daemon"]


# ------------------------------------------------------------ the tracer


def test_off_records_nothing():
    tr = Tracer()
    assert tr.span("a") is tr.span("b") is OFF.span("c")
    with tr.step(0):
        with tr.span("a"):
            to_host(jnp.arange(3))
            tr.count("x")
    tr.begin("session.queued", "s")
    tr.end("session.queued", "s")
    assert tr.drain() == {"spans": [], "counters": [], "open": []}
    assert OFF.drain() == {"spans": [], "counters": [], "open": []}


def test_parent_links_and_self_time():
    tr = Tracer(on=True)
    with tr.step(7):
        with tr.span("outer"):
            time.sleep(0.002)
            with tr.span("inner", {"k": 1}):
                time.sleep(0.003)
    spans = {s.name: s for s in tr.drain()["spans"]}
    root, outer, inner = spans["step"], spans["outer"], spans["inner"]
    assert (root.parent, outer.parent, inner.parent) == (-1, root.id,
                                                          outer.id)
    assert {s.step for s in spans.values()} == {7}
    assert inner.attrs == {"k": 1} and inner.key is None
    assert root.t0_ns <= outer.t0_ns <= inner.t0_ns
    assert inner.t1_ns <= outer.t1_ns <= root.t1_ns
    self_outer = (outer.t1_ns - outer.t0_ns) - (inner.t1_ns - inner.t0_ns)
    assert 2e6 <= self_outer < outer.t1_ns - outer.t0_ns
    assert inner.t1_ns - inner.t0_ns >= 3e6


def test_keyed_request_spans_cross_steps():
    tr = Tracer(on=True)
    with tr.step(0):
        tr.begin("session.queued", "a")
        tr.begin("session.queued", "b", {"why": "full"})
    with tr.step(1):
        tr.end("session.queued", "a")
        tr.end("session.queued", "nobody")      # began while off: no-op
    rec = tr.drain()
    done = [s for s in rec["spans"] if s.key is not None]
    assert [(s.name, s.key, s.step) for s in done] == [
        ("session.queued", "a", 0)]
    assert done[0].t1_ns >= done[0].t0_ns
    assert [(s.key, s.t1_ns, s.attrs) for s in rec["open"]] == [
        ("b", -1, {"why": "full"})]
    # an open span stays open across drains and closes later
    tr.end("session.queued", "b")
    assert [s.key for s in tr.drain()["spans"]] == ["b"]


def test_counters_are_per_step_and_reset_per_drain():
    tr = Tracer(on=True)
    tr.count("probe")                           # between steps: next step
    with tr.step(0):
        tr.count("host_sync", 2)
    with tr.step(1):
        with tr.span("engine.readback"):
            to_host(jnp.arange(2))
    to_host(jnp.arange(2))                      # no span open: not counted
    assert tr.drain()["counters"] == [
        (0, {"probe": 1, "host_sync": 2}), (1, {"host_sync": 1})]
    assert tr.drain()["counters"] == []
    with tr.step(2):
        tr.count("host_sync")
    assert tr.drain()["counters"] == [(2, {"host_sync": 1})]


def test_write_jsonl(tmp_path):
    tr = Tracer(on=True)
    with tr.step(3):
        with tr.span("engine.flush"):
            tr.count("host_sync")
    tr.begin("session.frozen", "s1")
    path = tmp_path / "spans.jsonl"
    assert tr.write_jsonl(path) == 4
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert [x.get("name") for x in lines[:3]] == ["engine.flush", "step",
                                                  "session.frozen"]
    assert lines[2]["key"] == "s1" and lines[2]["t1_ns"] == -1
    assert lines[3] == {"step": 3, "counters": {"host_sync": 1}}


# ------------------------------------------------------------ the engine


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(reduced(get_config("llama3.2-3b")),
                              dtype="float32")
    params = init_params(M.param_schema(cfg), jax.random.PRNGKey(0),
                         cfg.dtype)
    return cfg, params


def fleet():
    """Three sessions on two slots (one queues), tool calls under small
    memory.high values (throttles, stalls) and a pool that binds
    (freeze, thaw)."""
    def sess(sid, prio):
        return Session(sid=sid, tenant="t", priority=prio,
                       prompt=list(range(2, 26)),
                       phases=[Phase(6, 60, "test"), Phase(6, 40, "git"),
                               Phase(6, 0)])
    return [sess("hi", D.HIGH), sess("lo1", D.LOW), sess("lo2", D.LOW)]


ECFG = EngineConfig(max_slots=2, s_max=256, pool_pages=9, page_tokens=16,
                    mode="inkernel", use_freeze=True,
                    session_high={"lo1": 4, "lo2": 4})
STEPS = 160


def serve(model, tracer=None, observe=None):
    cfg, params = model
    eng = Engine(cfg, params, perf=perf_replace(DEFAULT_PERF, scan_chunk=32),
                 ecfg=ECFG, seed=0, tracer=tracer)
    for s in fleet():
        eng.submit(s)
    for _ in range(STEPS):
        eng.step()
        if observe is not None:
            observe()
    return eng


class ReadCounter:
    """Counts device-to-host reads from outside the program: numpy
    conversions of a ``jax.Array`` and Python scalar conversions (which
    read ``ArrayImpl._value``)."""

    def __init__(self, monkeypatch):
        from jax._src import array as A
        self.n = 0
        real_asarray, real_array = np.asarray, np.array
        value = A.ArrayImpl._value

        def counted(real):
            def f(x, *a, **kw):
                if isinstance(x, jax.Array):
                    self.n += 1
                return real(x, *a, **kw)
            return f

        def _value(arr):
            if arr._npy_value is None:
                self.n += 1
            return value.fget(arr)
        monkeypatch.setattr(np, "asarray", counted(real_asarray))
        monkeypatch.setattr(np, "array", counted(real_array))
        monkeypatch.setattr(A.ArrayImpl, "_value", property(_value))


def test_engine_spans_tile_the_step_and_count_every_host_read(
        model, monkeypatch):
    tr = Tracer(on=True)
    reads = ReadCounter(monkeypatch)
    per_step = []

    def observe():
        per_step.append(reads.n)
        reads.n = 0
    eng = serve(model, tr, observe)
    monkeypatch.undo()
    rec = tr.drain()
    spans = rec["spans"]
    roots = [s for s in spans if s.name == "step"]
    assert [r.step for r in roots] == list(range(STEPS))
    covered = total = 0
    for r in roots:
        kids = sorted((s for s in spans if s.parent == r.id),
                      key=lambda s: s.t0_ns)
        assert [k.name for k in kids] == PHASES
        assert r.t0_ns <= kids[0].t0_ns and kids[-1].t1_ns <= r.t1_ns
        for a, b in zip(kids, kids[1:]):
            assert a.t1_ns <= b.t0_ns
        covered += sum(k.t1_ns - k.t0_ns for k in kids)
        total += r.t1_ns - r.t0_ns
    assert covered >= 0.95 * total
    # the counter matches the reads an outside observer saw, step by step
    # (the submits' reads come before step 0 and count in it)
    counted = [c.get("host_sync", 0) for _, c in rec["counters"]]
    assert counted == per_step
    assert min(counted) >= 25           # 4 readbacks + the daemon snapshot
    # the scenario reaches every phase of the served path
    names = {s.name for s in spans}
    assert {"daemon.snapshot", "daemon.admit", "daemon.freeze",
            "daemon.thaw", "cg.mkdir", "cg.rmdir", "cg.freeze", "cg.thaw",
            "lease.open", "lease.close", "kv.freeze_slot", "kv.thaw_slot",
            "kv.free_slot", "session.queued", "session.tool_call",
            "session.stalled", "session.frozen"} <= names
    tool = [s for s in spans if s.name == "session.tool_call"]
    assert all(s.attrs["memory.high"] in (4, 10, 24)
               and s.attrs["hint"].startswith("memory:") for s in tool)
    assert eng.report()["freezes"] > 0


def test_report_is_the_same_with_the_tracer_on_and_off(model):
    on = serve(model, Tracer(on=True))
    off = serve(model)
    assert off.tracer is OFF and off.cg.tracer is OFF
    assert on.report() == off.report()
    assert on.report()["peak_pool_pages"] > 0
    assert not hasattr(on, "log")
    assert not hasattr(on.metrics, "root_usage")
