"""Paper §6: "Enforcement overhead is negligible: P50 latency increases
by 0.3%".  Ours: wall-clock engine-step times with the in-step
controller ON vs OFF (accounting-only), uncontended (huge pool, no
throttles fire), same model/sessions/seed.

``--quick`` runs a short smoke (CI): fewer timed steps plus a hard
ceiling on the enforcement overhead, so a change to the program
dispatch path (core/progs.py) cannot silently regress step latency.
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.core import domains as D
from repro.models import model as M
from repro.models.schema import init_params
from repro.perf import DEFAULT_PERF, replace as perf_replace
from repro.serving.engine import Engine, EngineConfig
from repro.serving.session import Phase, Session


def _sessions():
    return [Session(sid=f"s{i}", tenant="t",
                    priority=D.HIGH if i == 0 else D.LOW,
                    prompt=list(range(2, 34)),
                    phases=[Phase(16, 64, "test"), Phase(16, 0)])
            for i in range(3)]


def _run(cfg, params, mode: str, steps: int = 400,
         tool_domains: bool = False, backend: str = "device"):
    ecfg = EngineConfig(max_slots=4, s_max=512, pool_pages=4096,
                        page_tokens=16, mode=mode, use_freeze=False,
                        use_tool_domains=tool_domains,
                        use_intent=tool_domains, backend=backend)
    eng = Engine(cfg, params, perf=perf_replace(DEFAULT_PERF, scan_chunk=32),
                 ecfg=ecfg, seed=0)
    for s in _sessions():
        eng.submit(s)
    # warm the jit
    for _ in range(5):
        eng.step()
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        eng.step()
        times.append(time.perf_counter() - t0)
    eng.close()
    return np.array(times) * 1e3


def run(steps: int = 400, quick: bool = False, backend: str = "device"):
    cfg = dataclasses.replace(reduced(get_config("llama3.2-3b")),
                              dtype="float32")
    params = init_params(M.param_schema(cfg), jax.random.PRNGKey(0),
                         cfg.dtype)
    off = _run(cfg, params, "nolimit", steps=steps)
    core = _run(cfg, params, "inkernel", steps=steps)     # in-step charge only
    full = _run(cfg, params, "inkernel", steps=steps, tool_domains=True)
    p = lambda a, q: float(np.percentile(a, q))
    print("\n== in-step enforcement overhead (paper: P50 +0.3%) ==")
    print(f"engine step P50: accounting-only {p(off,50):.2f} ms | "
          f"+in-step enforcement {p(core,50):.2f} ms "
          f"({(p(core,50)/p(off,50)-1)*100:+.1f}%) | "
          f"+tool-domains/intent (host daemon) {p(full,50):.2f} ms "
          f"({(p(full,50)/p(off,50)-1)*100:+.1f}%)")
    print("   (the in-kernel analogue is the middle column; host-side "
          "domain lifecycle is the paper's user-space daemon work)")
    out = {"p50_off": p(off, 50), "p50_core": p(core, 50),
           "p50_full": p(full, 50)}
    if backend == "async":
        # the async lifecycle daemon: same in-step enforcement, but all
        # lifecycle ops queued to the daemon thread and applied in
        # step-boundary epochs — the wrapper may not add measurable
        # per-step latency to the enforcement path
        acore = _run(cfg, params, "inkernel", steps=steps, backend="async")
        ratio_async = p(acore, 50) / p(core, 50)
        print(f"async lifecycle daemon: P50 {p(acore,50):.2f} ms "
              f"({(ratio_async-1)*100:+.1f}% vs synchronous in-step)")
        out["p50_async"] = p(acore, 50)
        if quick:
            assert ratio_async < 1.25, \
                f"async wrapper P50 ratio {ratio_async:.2f} >= 1.25"
            print(f"async-wrapper smoke OK (ratio {ratio_async:.2f} < 1.25)")
    if quick:
        # smoke ceiling: in-step program dispatch may not blow up the
        # step (generous bound — CI machines are noisy; the point is to
        # catch an accidental host sync / retrace in the dispatch path)
        ratio = p(core, 50) / p(off, 50)
        assert ratio < 2.0, f"in-step enforcement P50 ratio {ratio:.2f} >= 2"
        print(f"quick-mode smoke OK (ratio {ratio:.2f} < 2.0)")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: few steps + overhead ceiling assert")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--backend", default="device",
                    choices=["device", "async"],
                    help="async: also time the async-daemon wrapper and "
                         "(with --quick) assert it adds no measurable "
                         "per-step enforcement latency")
    args = ap.parse_args()
    run(steps=args.steps or (60 if args.quick else 400), quick=args.quick,
        backend=args.backend)
