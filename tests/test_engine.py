"""Serving-engine integration: the three controller modes on a real
(reduced) model — survival, in-step hard guarantee, freeze context
preservation, feedback adaptation, intent hints."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import domains as D
from repro.models import model as M
from repro.models.schema import init_params
from repro.perf import DEFAULT_PERF, replace as perf_replace
from repro.serving.engine import Engine, EngineConfig
from repro.serving.session import Phase, Session, SState

PERF = perf_replace(DEFAULT_PERF, scan_chunk=32)


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(reduced(get_config("llama3.2-3b")),
                              dtype="float32")
    params = init_params(M.param_schema(cfg), jax.random.PRNGKey(0),
                         cfg.dtype)
    return cfg, params


def sessions():
    hi = Session(sid="hi", tenant="t", priority=D.HIGH,
                 prompt=list(range(2, 34)),
                 phases=[Phase(8, 96, "test"), Phase(8, 64, "git"),
                         Phase(12, 0)])
    lo1 = Session(sid="lo1", tenant="t", priority=D.LOW,
                  prompt=list(range(2, 26)),
                  phases=[Phase(8, 160, "test"), Phase(8, 96, "test"),
                          Phase(8, 0)])
    lo2 = Session(sid="lo2", tenant="t", priority=D.LOW,
                  prompt=list(range(2, 26)),
                  phases=[Phase(8, 160, "test"), Phase(8, 96, "test"),
                          Phase(8, 0)])
    return [hi, lo1, lo2]


COMMON = dict(max_slots=4, s_max=384, pool_pages=40, page_tokens=16)


def run_mode(model, mode, **kw):
    cfg, params = model
    ecfg = EngineConfig(**COMMON, mode=mode, **kw)
    eng = Engine(cfg, params, perf=PERF, ecfg=ecfg, seed=0)
    for s in sessions():
        eng.submit(s)
    eng.run(6000)
    return eng


def test_inkernel_full_survival_and_hard_guarantee(model):
    eng = run_mode(model, "inkernel", use_freeze=True,
                   session_high={"lo1": 12, "lo2": 12})
    r = eng.report()
    assert r["survival"] == 1.0
    assert r["overshoot_pages"] == 0          # in-step charge cannot overshoot
    assert r["throttle_triggers"] > 0


def test_userspace_lags(model):
    base = run_mode(model, "userspace", use_freeze=False,
                    use_tool_domains=False, use_intent=False,
                    session_high={"lo1": 12, "lo2": 12})
    ink = run_mode(model, "inkernel", use_freeze=True,
                   session_high={"lo1": 12, "lo2": 12})
    # the stale-gate path throttles strictly later/less than in-step
    assert base.report()["throttle_triggers"] < ink.report()["throttle_triggers"]


def test_nolimit_overshoots_pool(model):
    eng = run_mode(model, "nolimit", use_freeze=False,
                   use_tool_domains=False, use_intent=False)
    assert eng.report()["overshoot_pages"] > 0


def test_freeze_preserves_context(model):
    eng = run_mode(model, "inkernel", use_freeze=True,
                   session_high={"lo1": 12, "lo2": 12})
    frozen = [s for s in eng.sessions.values() if s.n_freezes > 0]
    assert eng.metrics.n_freezes >= 1 and frozen
    for s in frozen:                          # full context length reached
        assert s.state is SState.DONE
        want = len(s.prompt) + sum(p.gen_tokens + p.append_tokens
                                   for p in s.phases)
        assert s.length == want


def test_session_completion_lengths(model):
    eng = run_mode(model, "inkernel", use_freeze=True,
                   session_high={"lo1": 12, "lo2": 12})
    for s in eng.sessions.values():
        want = len(s.prompt) + sum(p.gen_tokens + p.append_tokens
                                   for p in s.phases)
        assert s.length == want, (s.sid, s.length, want)


def test_freezer_records_deterministic(model, monkeypatch):
    """Two identical runs produce identical freezer records — the
    TL003 regression: offload records carry the step clock, never wall
    time, so freeze/thaw state is replay-deterministic."""
    from repro.core.freezer import FrozenStore

    def capture(records):
        orig = FrozenStore.freeze

        def freeze(self, sid, tree, *, pages, meta=None, now=0.0):
            records.append((sid, pages, dict(meta or {}), float(now)))
            return orig(self, sid, tree, pages=pages, meta=meta, now=now)

        return freeze

    runs = []
    for _ in range(2):
        records = []
        monkeypatch.setattr(FrozenStore, "freeze", capture(records))
        run_mode(model, "inkernel", use_freeze=True,
                 session_high={"lo1": 12, "lo2": 12})
        monkeypatch.undo()
        runs.append(records)
    assert runs[0], "scenario no longer freezes anything"
    assert runs[0] == runs[1]
    for _sid, _pages, _meta, now in runs[0]:
        assert now == int(now) >= 0      # a step number, not an epoch time


def test_feedback_shrinks_append(model):
    """Against a tiny pool, sessions reconstruct strategy (shorter tool
    results) after feedback instead of being evicted."""
    cfg, params = model
    # pool of 20 pages = 320 tokens: the full workload (424 tokens) does
    # NOT fit, but a feedback-shrunk one does — eviction would be a bug
    ecfg = EngineConfig(max_slots=2, s_max=384, pool_pages=20,
                        page_tokens=16, mode="inkernel", use_freeze=False,
                        feedback_patience_steps=20,
                        evict_patience_steps=2000)
    eng = Engine(cfg, params, perf=PERF, ecfg=ecfg, seed=0)
    big = Session(sid="big", tenant="t", priority=D.NORMAL,
                  prompt=list(range(2, 18)),
                  phases=[Phase(4, 400, "test"), Phase(4, 0)])
    eng.submit(big)
    eng.run(6000)
    assert big.state is SState.DONE
    assert len(big.feedbacks) >= 1
    want_full = 16 + 4 + 400 + 4
    assert big.length < want_full             # scope was reduced


def test_domain_accounting_clean_at_end(model):
    eng = run_mode(model, "inkernel", use_freeze=True,
                   session_high={"lo1": 12, "lo2": 12})
    assert eng.cg.usage("/") == 0


def test_async_backend_bitexact_with_device(model):
    """The async lifecycle daemon's acceptance claim: wrapping the
    device backend and deferring all lifecycle ops to step-boundary
    epochs reproduces the synchronous run bit-exactly — every metric in
    the report, same seed, same workload — while the jitted enforcement
    path never blocks on lifecycle work."""
    dev = run_mode(model, "inkernel", use_freeze=True,
                   session_high={"lo1": 12, "lo2": 12})
    asy = run_mode(model, "inkernel", backend="async", use_freeze=True,
                   session_high={"lo1": 12, "lo2": 12})
    assert asy.report() == dev.report()
    assert asy.report()["survival"] == 1.0
    assert asy.cg.usage("/") == 0
    from repro.core.daemon import AsyncDaemonBackend
    assert isinstance(asy.cg.backend, AsyncDaemonBackend)
    assert asy.cg.backend.epoch > 0       # lifecycle really ran in epochs
    asy.close()
    assert not asy.cg.backend._thread.is_alive()


def test_engine_survives_poisoned_daemon(model):
    """Robustness: when the async lifecycle daemon is poisoned mid-run
    (wedge/timeout), the next step rebuilds the backend from the last
    step-boundary snapshot and the run completes — same workload, full
    survival, clean accounting."""
    cfg, params = model
    ecfg = EngineConfig(**COMMON, mode="inkernel", backend="async",
                        use_freeze=True,
                        session_high={"lo1": 12, "lo2": 12})
    eng = Engine(cfg, params, perf=PERF, ecfg=ecfg, seed=0)
    for s in sessions():
        eng.submit(s)
    for _ in range(40):
        eng.step()
    eng.cg.backend._wedged = True            # poison between steps
    eng.run(6000)
    r = eng.report()
    assert eng.metrics.n_rebuilds == 1
    assert r["survival"] == 1.0
    assert r["overshoot_pages"] == 0
    assert eng.cg.usage("/") == 0
    for s in eng.sessions.values():
        want = len(s.prompt) + sum(p.gen_tokens + p.append_tokens
                                   for p in s.phases)
        assert s.length == want, (s.sid, s.length, want)
    eng.close()


def test_sharded_backend_serves_multitenant(model):
    """Same workload on the ShardedTableBackend: in-step enforcement now
    runs per device group under shard_map, but the guarantees (survival,
    zero pool overshoot, clean accounting) are backend-invariant."""
    eng = run_mode(model, "inkernel", backend="sharded", use_freeze=True,
                   session_high={"lo1": 12, "lo2": 12})
    r = eng.report()
    assert r["survival"] == 1.0
    assert r["overshoot_pages"] == 0
    assert r["throttle_triggers"] > 0
    assert eng.cg.usage("/") == 0
    # every tenant subtree was placed on a device group
    assert "/t" in eng.cg.backend.placement()


def test_adaptive_observation_is_non_perturbing(model):
    """``EngineConfig(adaptive=...)`` with thresholds the run can never
    cross (avg10 <= 1.0 < high_frac) polls pressure every step but takes
    no action — and reading pressure must not perturb a single decision:
    the report is bit-identical to the ``adaptive=None`` run."""
    from repro.core.adaptive import AdaptiveConfig
    base = run_mode(model, "inkernel", use_freeze=True,
                    session_high={"lo1": 12, "lo2": 12})
    watched = run_mode(model, "inkernel", use_freeze=True,
                       session_high={"lo1": 12, "lo2": 12},
                       adaptive=AdaptiveConfig(high_frac=2.0))
    assert base._adaptive is None and watched._adaptive is not None
    assert watched._adaptive.events == []
    assert watched.report() == base.report()


def test_adaptive_retuner_relieves_live_engine(model):
    """The closed loop on the live engine: watching the throttled LOW
    session domains with a hair-trigger threshold must produce bump
    events on the engine's step clock, and the run still completes with
    clean accounting."""
    from repro.core.adaptive import AdaptiveConfig
    eng = run_mode(model, "inkernel", use_freeze=True,
                   session_high={"lo1": 12, "lo2": 12},
                   adaptive=AdaptiveConfig(high_frac=0.01, low_frac=0.0,
                                           cooldown_ms=50.0,
                                           watch=("/t/lo1", "/t/lo2")))
    r = eng.report()
    assert r["survival"] == 1.0
    assert eng.cg.usage("/") == 0
    bumps = [e for e in eng._adaptive.events if e.action == "bump_high"]
    assert bumps, "pressure never produced a bump on the live engine"
    for e in bumps:
        assert e.new > e.old
        assert e.t_ms == int(e.t_ms)          # engine step clock, not ms


def _short_sessions(n):
    return [Session(sid=f"s{i}", tenant=f"t{i}",
                    priority=D.HIGH if i == 0 else D.LOW,
                    prompt=list(range(2, 18)),
                    phases=[Phase(8, 16, "python"), Phase(8, 0)])
            for i in range(n)]


def test_serve_entry_point_full_width_by_default_reduced_on_request():
    """``launch.serve`` serves the published widths unless ``--reduced``
    asks for the CPU preset; ``run`` takes the caller's sessions and
    reports each session's outcome."""
    from repro.launch import serve
    assert serve.parser().parse_args([]).reduced is False
    cfg, _ = serve.build_model("llama3.2-3b", reduced_preset=True, seed=0)
    assert cfg.dtype == "float32" and cfg.d_model < 3072
    args = serve.parser().parse_args(
        ["--reduced", "--slots", "4", "--s-max", "128", "--pool-pages", "64",
         "--max-steps", "2000"])
    r = serve.run(args, _short_sessions(3))
    assert r["survival"] == 1.0 and r["nonfinite_logit_steps"] == 0
    for sid, s in r["sessions"].items():
        assert s == {"state": "done", "length": 16 + 8 + 16 + 8,
                     "generated": 16}, sid


_SHARDED_SERVE_4DEV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from jax.sharding import NamedSharding
from repro.core import domains as D
from repro.launch.serve import build_model
from repro.perf import DEFAULT_PERF
from repro.serving.engine import Engine, EngineConfig
from repro.serving.session import Phase, Session

cfg, params = build_model("llama3.2-3b", reduced_preset=True, seed=0)

def serve(**kw):
    eng = Engine(cfg, params, perf=DEFAULT_PERF, seed=0,
                 ecfg=EngineConfig(max_slots=4, s_max=128, pool_pages=64,
                                   page_tokens=16, **kw))
    for i in range(4):
        eng.submit(Session(sid=f"s{i}", tenant=f"t{i}",
                           priority=D.HIGH if i == 0 else D.LOW,
                           prompt=list(range(2, 18)),
                           phases=[Phase(8, 16, "python"), Phase(8, 0)]))
    eng.run(2000)
    return eng

one = serve()
four = serve(backend="sharded", n_shards=4)
for leaf in jax.tree.leaves(four.params):
    sh = leaf.sharding
    assert isinstance(sh, NamedSharding) and len(sh.device_set) == 4, sh
    assert sh.is_fully_replicated, sh
for leaf in jax.tree.leaves(four.caches.state):     # split by slot
    assert leaf.sharding.spec == (None, "shard"), leaf.sharding
    assert len(leaf.sharding.device_set) == 4
assert sorted(set(four.cg.backend.placement().values())) == [0, 1, 2, 3]
assert four.report() == one.report(), (four.report(), one.report())
assert [s.out_tokens for s in four.sessions.values()] == \
    [s.out_tokens for s in one.sessions.values()]
print("SHARDED-SERVE OK")
"""


def test_sharded_engine_places_weights_on_control_mesh():
    """On a four-device control mesh the engine replicates the weights
    and splits the KV cache by slot over the mesh (one jitted step takes
    them with the tenant-sharded control state) and serves exactly what
    one device serves (subprocess: the fake device count is fixed at
    jax init)."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _SHARDED_SERVE_4DEV],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0 and "SHARDED-SERVE OK" in out.stdout, \
        out.stderr[-3000:]
