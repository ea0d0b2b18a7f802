"""The benchmark's copy of the traffic arithmetic, pinned to its own seeded
outputs."""
import json
from pathlib import Path

import numpy as np

from bench import fleet, tracegen

BENCH = Path(__file__).resolve().parent


def test_spike_corpus_reproduces_the_papers_peak_to_average():
    corpus = tracegen.generate_spike_corpus(8, seed=0)
    assert abs(corpus[-1].peak_to_avg - 15.4) < 1e-9
    assert max(t.peak_to_avg for t in corpus) == corpus[-1].peak_to_avg


def test_generate_task_is_pinned():
    tr = tracegen.generate_task("x", "haiku", seed=0)
    assert len(tr.tool_calls) == 59
    assert tr.baseline_mb == 184.50876265312073
    assert tr.peak_mb == 624.8768354475793
    calls = sorted(tr.tool_calls, key=lambda c: c.t_start_s)
    assert [c.category for c in calls[:3]] == ["read", "read", "read"]
    assert calls[0].peak_mb == 5.318200451227879


def test_swe_fleet_corpus_is_fixed_by_the_mix_file():
    mix = json.loads((BENCH / "traffic" / "swe-fleet.json").read_text())
    corpus = fleet.task_corpus(mix)
    assert [len(t) for t in corpus[0]] == [48, 48, 48, 32]
    assert corpus[0][0][:2] == [(32, 5, "read"), (32, 4, "read")]
    appended = [p[1] for a in corpus for t in a for p in t]
    assert len(appended) == 1468
    assert np.percentile(appended, 50) == 5.0
    assert np.percentile(appended, 90) == 79.0


def test_toolcall_churn_corpus_is_fixed_by_the_mix_file():
    mix = json.loads((BENCH / "traffic" / "toolcall-churn.json").read_text())
    corpus = fleet.task_corpus(mix)
    lens = [128 + sum(g + 1 + n for g, n, _ in t) for a in corpus for t in a]
    appended = [p[1] for a in corpus for t in a for p in t]
    assert len(appended) == 1728
    assert max(lens) == 8836
    assert round(float(np.mean(lens)), 6) == 1486.388889


def test_agents_start_spread_over_the_warm_up():
    mix = {"agents": 8, "start_spread_steps": 512,
           "engine": {"max_slots": 8}}
    assert fleet.start_steps(mix) == [0, 64, 128, 192, 256, 320, 384, 448]
    mix = {"agents": 6, "start_spread_steps": 512, "engine": {"max_slots": 4}}
    assert fleet.start_steps(mix) == [0, 128, 256, 384, 384, 384]
    assert fleet.start_steps({"agents": 3, "engine": {"max_slots": 2}}) \
        == [0, 0, 0]


def test_every_seed_serves_the_same_task_lists():
    mix = json.loads((BENCH / "traffic" / "swe-fleet.json").read_text())

    class Eng:
        pool_capacity = 768

        class ecfg:
            page_tokens = 16
    a = fleet.Fleet(Eng(), mix, 0, 1000)
    b = fleet.Fleet(Eng(), mix, 2 ** 31 + 3, 1000)
    assert a.lists == b.lists
    assert a._session(0).prompt != b._session(0).prompt
    # agents start at steps spread over the warm-up, the last one as the
    # window opens
    assert a.starts == b.starts
    assert a.starts[-1] == mix["warmup_steps"]
