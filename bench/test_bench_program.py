"""The readers of the program's own spans, counters and named scopes
(``bench/spans_run.py``, ``bench/program_trace.py``,
``bench/metrics/_program.py`` and the readers that use them), on the CPU
and on a trace recorded on one TPU v5e."""
import gzip
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import program_trace as PT
from bench import run, spans_run
from bench.testing import tiny_cell
from repro.spans import Span

NEW = ("lifecycle_ms_per_step", "host_syncs_per_step", "queue_wait_p90_ms",
       "enforce_device_share")


def span(i, name, parent=-1, step=0, t0=0, t1=0, key=None):
    return Span(i, name, parent, step, t0, t1, key, None)


def record(program=None, scopes=None, window=(1.0, 2.0)):
    return spans_run.ProgramRecord(
        model={}, mix={}, chips=1, peak={}, setup_s=0.0, window=window,
        sessions=[], spans=None, trace={}, traced_steps=[], open_waits=[],
        program=program, scopes=scopes)


def test_readers_read_nothing_without_the_program():
    """The harness's own record (no tracer, no scopes) gives no value
    and raises nothing: a program without the spans reads as absent."""
    rec = run.Record(model={}, mix={}, chips=1, peak={}, setup_s=0.0,
                     window=(0.0, 1.0), sessions=[], spans=None, trace={},
                     traced_steps=[], open_waits=[])
    assert run.read_metrics(NEW, rec) == {}
    empty = {"spans": [], "counters": [], "open": []}
    assert run.read_metrics(NEW, record(empty, {"unscoped": 1.0})) == {}


def test_lifecycle_counts_outermost_spans_of_window_steps():
    ms = 1_000_000
    spans = [
        span(1, "step", step=4, t0=1000 * ms, t1=1100 * ms),   # before
        span(2, "cg.mkdir", 1, step=4, t0=1010 * ms, t1=1020 * ms),
        span(3, "step", step=5, t0=1200 * ms, t1=1300 * ms),
        span(4, "lease.open", 3, step=5, t0=1210 * ms, t1=1214 * ms),
        span(5, "cg.mkdir", 4, step=5, t0=1211 * ms, t1=1213 * ms),
        span(6, "cg.snapshot", 3, step=5, t0=1220 * ms, t1=1230 * ms),
        span(7, "kv.free_slot", 3, step=5, t0=1240 * ms, t1=1241 * ms),
        span(8, "step", step=6, t0=1400 * ms, t1=1500 * ms),
        span(9, "cg.rmdir", -1, step=6, t0=1510 * ms, t1=1512 * ms),
        span(10, "step", step=7, t0=1900 * ms, t1=2100 * ms),  # after
    ]
    counters = [(4, {"host_sync": 9}), (5, {"host_sync": 30}),
                (6, {"host_sync": 26})]
    rec = record({"spans": spans, "counters": counters, "open": []},
                 window=(1.15, 2.0))
    out = run.read_metrics(NEW[:2], rec)
    # steps 5 and 6: lease.open (4 ms, its mkdir inside it), kv (1 ms),
    # the rmdir between steps (2 ms); not the snapshot
    assert np.isclose(out["lifecycle_ms_per_step"]["value"], 7 / 2)
    assert out["host_syncs_per_step"]["value"] == 28


def test_queue_wait_counts_waits_open_at_the_close():
    s = 1_000_000_000
    spans = [span(1, "session.queued", t0=0, t1=int(1.5 * s), key="a"),
             span(2, "session.queued", t0=int(1.2 * s), t1=int(1.3 * s),
                  key="b"),
             span(3, "session.queued", t0=0, t1=int(0.5 * s), key="c"),
             span(4, "session.queued", t0=int(1.5 * s), t1=int(2.5 * s),
                  key="d")]
    open_ = [span(5, "session.queued", t0=int(1.0 * s), t1=-1, key="e"),
             span(6, "session.queued", t0=int(2.5 * s), t1=-1, key="f")]
    rec = record({"spans": spans, "counters": [], "open": open_})
    got = run.read_metrics(["queue_wait_p90_ms"], rec)
    # a 1500 and b 100 ended in the window; d (500) and e (1000) were
    # still queued at the close; c ended before it, f began after it
    want = np.percentile([1500, 100, 500, 1000], 90)
    assert np.isclose(got["queue_wait_p90_ms"]["value"], want)


def test_enforce_share_of_the_step_module():
    scopes = {"agentcgroup.gate": 0.6, "agentcgroup.charge": 0.05,
              "model.decode": 3.0, "model.sample": 0.1, "unscoped": 0.25}
    rec = record({"spans": [], "counters": [], "open": []}, scopes)
    got = run.read_metrics(["enforce_device_share"], rec)
    assert np.isclose(got["enforce_device_share"]["value"], 0.65 / 4.0 * 100)


def test_idle_gaps_go_to_the_innermost_span_covering_most():
    ops = [[(0, 10, "a"), (20, 30, "b"), (60, 100, "c")]]
    spans = [(0, 100, "bench.window"), (0, 100, "agentcgroup.step"),
             (5, 40, "agentcgroup.engine.dispatch"),
             (40, 55, "agentcgroup.engine.daemon"),
             (41, 52, "agentcgroup.daemon.snapshot"),
             (0, 100, "bench.step")]
    got = dict(PT.idle_program_spans(ops, spans))
    # gap 10-20 lies in the dispatch, 30-60 mostly under the daemon's
    # snapshot (11 of 30: less than half) and the daemon (15: half)
    assert np.isclose(got["agentcgroup.engine.dispatch"], 10e-9)
    assert np.isclose(got["agentcgroup.engine.daemon"], 30e-9)
    total, under = PT.idle_under_phases(ops, spans)
    assert np.isclose(total, 40e-9) and np.isclose(under, 40e-9)


@pytest.fixture(scope="module")
def tiny_engine():
    _, m, mix, _, _ = tiny_cell()
    return run.build_engine(m, mix, 3)


def test_step_scopes_map_the_gate_select_and_the_charge(tiny_engine):
    eng = tiny_engine
    args, _ = eng._inputs()
    scopes = PT.hlo_scopes(spans_run.step_hlo(eng._step, args,
                                              {"mode": "inkernel"}))
    # the rollback select, one fusion per decode-state leaf (K and V)
    gate = [n for n, sc in scopes.items()
            if n.startswith("broadcast_select_fusion")
            and sc == "agentcgroup.gate"]
    assert len(gate) == len(jax.tree.leaves(eng.caches.state))
    by_scope = {}
    for n, sc in scopes.items():
        by_scope.setdefault(sc, []).append(n)
    assert set(by_scope) == {"agentcgroup.charge", "agentcgroup.gate",
                             "model.decode", "model.sample"}
    assert any("scatter" in n or "while" in n
               for n in by_scope["agentcgroup.charge"])


def test_program_readers_on_the_tiny_cell():
    out = spans_run.run_program_cell("tiny.fleet", 2 ** 31 + 29, 1.5, True,
                                     loaded=tiny_cell(), require_tpu=False)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    for name in ("lifecycle_ms_per_step", "host_syncs_per_step",
                 "queue_wait_p90_ms", "host_ms_per_step"):
        assert np.isfinite(m[name]) and m[name] > 0, name
    # 4 readbacks and the 21 reads of the daemon's snapshot, at least
    assert m["host_syncs_per_step"] >= 25
    assert out["host_syncs_per_traced_step"] >= 25
    assert out["window_compilations"] == 0
    # the facade is recorded as under bench/run.py, and the control plane
    # matches its reference
    assert out["checks_hold"], out["checks"]
    assert out["checks"]["grant_mismatches"]["value"] == 0
    # every phase span runs once a step
    assert all(out["n_per_step"][k] == 1 for k in out["self_ms_per_step"]
               if k.startswith("engine."))
    phases = {k for k in out["self_ms_per_step"] if k.startswith("engine.")}
    assert phases == {"engine.flush", "engine.inputs", "engine.dispatch",
                      "engine.readback", "engine.bookkeeping",
                      "engine.daemon"}


# ------------------------------------------- a trace recorded on the chip

DATA = Path(__file__).resolve().parent / "testdata"
# two engine steps of minicpm-2b.swe-fleet traced by
# ``bench/spans_run.py --trace 1 --trace-steps 2 --keep``, and the compiled
# step's HLO text from the same run
TRACE = DATA / "minicpm-2b.swe-fleet.2steps.spans.xplane.pb.gz"
HLO = DATA / "minicpm-2b.swe-fleet.step.hlo.txt.gz"


@pytest.fixture(scope="module")
def recorded():
    ops, modules, spans, transfers = PT.load(TRACE)
    scopes = PT.hlo_scopes(gzip.decompress(HLO.read_bytes()).decode())
    return ops, modules, spans, transfers, scopes


def test_recorded_idle_time_falls_under_phase_spans(recorded):
    ops, _, spans, _, _ = recorded
    idle = PT.idle_program_spans(ops, spans)
    assert [n for n, _ in idle] == [
        "agentcgroup.cg.rmdir", "agentcgroup.cg.snapshot",
        "agentcgroup.engine.inputs", "agentcgroup.lease.close",
        "agentcgroup.engine.dispatch"]
    assert np.isclose(idle[0][1], 0.064480588)
    total, under = PT.idle_under_phases(ops, spans)
    assert np.isclose(total, 0.103586746) and under == total
    # the window's idle time: that inside the steps, and the 85 us by
    # which three of those gaps run on past a step's edge
    assert np.isclose(sum(v for _, v in idle), 0.103671417)


def test_recorded_step_time_by_scope(recorded):
    ops, modules, spans, _, scopes = recorded
    assert len(modules[0]) == 2            # the two traced steps
    assert scopes["broadcast_select_fusion.1"] == "agentcgroup.gate"
    sec = PT.step_scope_seconds(ops, modules, scopes, spans)
    assert set(sec) == {"model.decode", "model.sample", "agentcgroup.charge",
                        "agentcgroup.gate", "unscoped"}
    total = sum(sec.values())
    assert sec["unscoped"] < 1e-4 * total
    assert np.isclose(sec["agentcgroup.gate"], 0.013138134)
    rec = record({"spans": [], "counters": [], "open": []}, sec)
    share = run.read_metrics(["enforce_device_share"], rec)
    assert np.isclose(share["enforce_device_share"]["value"], 17.0661261)


def test_recorded_transfers_lie_in_counting_spans(recorded):
    """Each device-to-host transfer of the two steps happens inside a
    program span; the program's ``host_sync`` counter read 29.0 a step in
    the same run, 58 in all."""
    _, _, spans, transfers, _ = recorded
    prog = [x for x in spans if x[2].startswith(PT.PROGRAM_PREFIX)
            and x[2] != PT.STEP_ROOT]
    where = {}
    for s, e, _ in transfers:
        inner = min((x for x in prog if x[0] <= s and e <= x[1]),
                    key=lambda x: x[1] - x[0])[2]
        where[inner] = where.get(inner, 0) + 1
    assert PT.transfers_in_window(transfers, spans) == 58
    # the snapshot's reads, the readbacks, the lifecycle reads of a session
    # end and, inside the dispatch, the harness's own two reads
    assert where == {"agentcgroup.cg.snapshot": 40,
                     "agentcgroup.engine.readback": 6,
                     "agentcgroup.engine.dispatch": 4,
                     "agentcgroup.cg.rmdir": 6,
                     "agentcgroup.lease.close": 2}
