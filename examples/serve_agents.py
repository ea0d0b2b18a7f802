"""End-to-end driver: multi-tenant agent serving with batched requests.

Serves the reduced float32 preset (a CPU-sized demonstration; the
full-width model is ``repro.launch.serve`` on a TPU) to agent sessions
derived from paper-calibrated traces (each tool call's result floods the
context, the KV-page analogue of the paper's §3 memory bursts), under
all three controller modes, and prints a Fig-8-style comparison.

Run: PYTHONPATH=src python examples/serve_agents.py [--sessions 5]
"""
import argparse

from repro.core import domains as D
from repro.launch.serve import build_model
from repro.perf import DEFAULT_PERF, replace as perf_replace
from repro.serving.engine import Engine, EngineConfig
from repro.serving.session import session_from_trace
from repro.traces.generator import generate_task


def make_sessions(n: int, seed: int):
    out = []
    for i in range(n):
        trace = generate_task(f"agent-{i}", "glm" if i % 2 else "haiku",
                              seed=seed * 131 + i, scale=0.5)
        out.append(session_from_trace(
            sid=f"s{i}", tenant=f"tenant{i % 2}", trace=trace,
            priority=D.HIGH if i == 0 else D.LOW,
            tokens_per_mb=0.6, gen_per_call=12, max_phases=5))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--sessions", type=int, default=5)
    ap.add_argument("--pool-pages", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg, params = build_model(args.arch, reduced_preset=True, seed=args.seed)
    perf = perf_replace(DEFAULT_PERF, scan_chunk=32)
    common = dict(max_slots=4, s_max=512, pool_pages=args.pool_pages,
                  page_tokens=16)
    modes = {
        "nolimit": dict(mode="nolimit", use_freeze=False,
                        use_tool_domains=False, use_intent=False),
        "userspace": dict(mode="userspace", use_freeze=False,
                          use_tool_domains=False, use_intent=False),
        "agentcgroup": dict(mode="inkernel", use_freeze=True),
    }
    print(f"serving {args.sessions} agent sessions on {args.arch} "
          f"(reduced), pool={args.pool_pages} KV pages\n")
    print(f"{'mode':12s} {'done':>5s} {'evict':>5s} {'overshoot':>9s} "
          f"{'throttles':>9s} {'freezes':>7s} {'feedbacks':>9s} "
          f"{'steps':>6s}")
    for name, kw in modes.items():
        eng = Engine(cfg, params, perf=perf,
                     ecfg=EngineConfig(**common, **kw), seed=args.seed)
        for s in make_sessions(args.sessions, args.seed):
            eng.submit(s)
        eng.run(12000)
        r = eng.report()
        print(f"{name:12s} {r['completed']:5d} {r['evicted']:5d} "
              f"{r['overshoot_pages']:9d} {r['throttle_triggers']:9d} "
              f"{r['freezes']:7d} {r['feedbacks']:9d} {r['steps']:6d}")
    print("\nAgentCgroup: everyone finishes, the pool is never "
          "overshot, and bursts are absorbed by throttle/freeze/feedback "
          "instead of evictions.")


if __name__ == "__main__":
    main()
