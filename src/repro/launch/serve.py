"""Multi-tenant serving driver: agent sessions under AgentCgroup control.

Builds the registry model at its published widths and its own dtype
(``--reduced`` selects the small float32 preset the CPU rehearsal and
the tests use), with random weights from ``--seed``; derives agent
sessions from §3-calibrated traces (or takes a caller's session list);
and runs the continuous-batching engine in one of the controller modes:

  inkernel   — AgentCgroup: in-step enforcement + tool-call domains +
               intent hints + freeze/thaw + feedback  (the paper's system)
  userspace  — poll/react daemon gating (responsiveness baseline)
  nolimit    — accounting only (no isolation baseline)

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
      --mode inkernel --sessions 4 --pool-pages 48            # on a TPU
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve --reduced
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Optional

import jax

from repro import compat
from repro.configs import get_config, reduced
from repro.core import domains as D
from repro.models import model as M
from repro.models.schema import init_params
from repro.perf import DEFAULT_PERF, replace as perf_replace
from repro.serving.engine import Engine, EngineConfig
from repro.serving.session import session_from_trace
from repro.traces.generator import generate_task


def default_sessions(n: int, seed: int = 0) -> list:
    """1 HIGH-priority session + (n-1) LOW sessions from generated traces."""
    out = []
    for i in range(n):
        trace = generate_task(f"agent-{i}", "glm" if i % 2 else "haiku",
                              seed=seed * 1000 + i, scale=0.6)
        out.append(session_from_trace(
            sid=f"s{i}", tenant="tenant0", trace=trace,
            priority=D.HIGH if i == 0 else D.LOW,
            tokens_per_mb=0.2, gen_per_call=16, max_phases=6))
    return out


def build_model(arch: str, *, reduced_preset: bool, seed: int):
    """(config, random weights from ``seed``): the published widths in
    the config's dtype, or the reduced float32 preset."""
    cfg = get_config(arch)
    if reduced_preset:
        cfg = dataclasses.replace(reduced(cfg), dtype="float32")
    params = init_params(M.param_schema(cfg), jax.random.PRNGKey(seed),
                         cfg.dtype)
    return cfg, params


def run(args, sessions: Optional[list] = None) -> dict:
    """Serve ``sessions`` (default: ``default_sessions``) and return the
    engine report plus per-session outcomes under ``"sessions"``."""
    cfg, params = build_model(args.arch, reduced_preset=args.reduced,
                              seed=args.seed)
    perf = perf_replace(DEFAULT_PERF, scan_chunk=32)
    ecfg = EngineConfig(
        max_slots=args.slots, s_max=args.s_max, pool_pages=args.pool_pages,
        page_tokens=args.page_tokens, mode=args.mode,
        use_freeze=(args.mode == "inkernel"),
        use_tool_domains=(args.mode == "inkernel"),
        use_intent=(args.mode == "inkernel"),
        session_high=json.loads(args.session_high) if args.session_high else None,
        backend=args.backend, n_shards=args.n_shards,
    )
    eng = Engine(cfg, params, perf=perf, ecfg=ecfg, seed=args.seed)
    del params          # the engine holds the (possibly re-placed) weights
    if sessions is None:
        sessions = default_sessions(args.sessions, seed=args.seed)
    for s in sessions:
        eng.submit(s)
    try:
        eng.run(args.max_steps)
    finally:
        eng.close()
    report = eng.report()
    report["sessions"] = {
        s.sid: {"state": s.state.value, "length": s.length,
                "generated": len(s.out_tokens)}
        for s in eng.sessions.values()}
    print(json.dumps(report, indent=1), flush=True)
    return report


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the small float32 preset (CPU rehearsal)")
    ap.add_argument("--mode", default="inkernel",
                    choices=["inkernel", "userspace", "nolimit"])
    ap.add_argument("--sessions", type=int, default=4)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--s-max", type=int, default=512)
    ap.add_argument("--pool-pages", type=int, default=48)
    ap.add_argument("--page-tokens", type=int, default=16)
    ap.add_argument("--session-high", default=None,
                    help='JSON dict sid->pages, e.g. {"s1": 12}')
    ap.add_argument("--backend", default="device",
                    choices=["device", "sharded", "async"])
    ap.add_argument("--n-shards", type=int, default=None,
                    help="sharded backend: devices in the control mesh")
    ap.add_argument("--max-steps", type=int, default=8000)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main() -> int:
    args = parser().parse_args()
    compat.enable_compile_cache()
    run(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
