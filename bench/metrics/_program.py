"""Shared arithmetic of the readers of the program's own spans and
counters (``repro/spans.py``), which a run passes as ``rec.program``:
the tracer's drain, on the host's ``perf_counter`` clock."""
from __future__ import annotations

LIFECYCLE = ("cg.", "lease.", "kv.")
NOT_LIFECYCLE = ("cg.snapshot", "cg.flush")


def program(rec):
    """The drained spans and counters, or None where the run had no
    tracer on."""
    p = getattr(rec, "program", None)
    return p if p and p["spans"] else None


def window_steps(rec, prog) -> set:
    """Numbers of the engine steps that ran wholly inside the window."""
    lo, hi = (t * 1e9 for t in rec.window)
    return {s.step for s in prog["spans"]
            if s.name == "step" and s.t0_ns >= lo and s.t1_ns <= hi}


def is_lifecycle(name: str) -> bool:
    return name.startswith(LIFECYCLE) and name not in NOT_LIFECYCLE


def self_ms(prog, steps: set) -> dict:
    """Span name -> its time less its children's, summed over the given
    steps, in ms."""
    spans = [s for s in prog["spans"] if s.step in steps and s.key is None]
    out = {}
    for s in spans:
        out[s.name] = out.get(s.name, 0.0) + (s.t1_ns - s.t0_ns) * 1e-6
    by_id = {s.id: s for s in spans}
    for s in spans:
        p = by_id.get(s.parent)
        if p is not None:
            out[p.name] -= (s.t1_ns - s.t0_ns) * 1e-6
    return out
