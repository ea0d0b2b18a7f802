"""The closed-loop fleet and the harness, end to end on the CPU at the
reduced preset's widths (float32, two layers)."""
import json
import os
import subprocess
import sys
from pathlib import Path

from repro.serving.session import SState

from bench import run
from bench.testing import tiny_cell

ROOT = Path(__file__).resolve().parent.parent
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_fleet_replaces_sessions_and_collects_samples(monkeypatch):
    seen = {}
    real = run.Record

    def keep(**kw):
        seen["rec"] = real(**kw)
        return seen["rec"]
    monkeypatch.setattr(run, "Record", keep)
    cell = tiny_cell()
    out = run.run_cell("tiny.fleet", 2 ** 31 + 17, 1.5, False, loaded=cell,
                       require_tpu=False)
    rec = seen["rec"]
    agents = cell[2]["agents"]
    ended = [s for s in rec.sessions if s.state is SState.DONE]
    assert out["attempted"] == len(rec.sessions) > agents
    assert len(ended) >= 1
    # every ended task was followed by its agent's next task
    for a in range(agents):
        mine = [s.sid for s in rec.sessions if s.agent == a]
        assert mine == [f"a{a}.{k}" for k in range(len(mine))]
    assert any(s.turns for s in rec.sessions)
    assert any(s.gaps for s in rec.sessions)
    assert out["correct"] is True, out["checks"]
    assert list(out)[:5] == CONTRACT_KEYS and list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"tokens_per_s", "token_gap_p95_ms",
                                   "turn_ttft_p90_ms",
                                   "high_token_gap_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    line = json.loads(json.dumps(out))
    assert line["checks"]["window_compilations"]["value"] == 0


def test_reply_tokens_follow_the_phases():
    cell = tiny_cell()
    seen = {}
    real = run.Record

    def keep(**kw):
        seen["rec"] = real(**kw)
        return seen["rec"]
    run.Record = keep
    try:
        run.run_cell("tiny.fleet", 5, 1.0, False, loaded=cell,
                     require_tpu=False)
    finally:
        run.Record = real
    for s in seen["rec"].sessions:
        if s.state is SState.DONE and s.finished:
            # one reply per phase: the first token after the feed, then
            # the phase's generated tokens
            assert len(s.served) == sum(p.gen_tokens + 1 for p in s.phases)
            assert len(s.turns) == len(s.phases)
            assert len(s.ctx) == s.length


def test_run_without_a_tpu_exits_nonzero_and_prints_no_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "minicpm-2b.swe-fleet",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr
