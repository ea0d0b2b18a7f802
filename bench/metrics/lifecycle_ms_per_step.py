"""Host time of the control plane's lifecycle work per window step: the
program's outermost lifecycle spans (``cg.*`` but the step-boundary
``cg.snapshot`` and ``cg.flush``, ``lease.*``, ``kv.*``), summed over
the window's steps and divided by their number."""
from bench.metrics._program import is_lifecycle, program, window_steps

UNIT = "ms"


def read(rec):
    prog = program(rec)
    if prog is None:
        return None
    steps = window_steps(rec, prog)
    if not steps:
        return None
    spans = [s for s in prog["spans"] if s.key is None]
    by_id = {s.id: s for s in spans}

    def outermost(s):
        p = by_id.get(s.parent)
        while p is not None:
            if is_lifecycle(p.name):
                return False
            p = by_id.get(p.parent)
        return True
    total = sum(s.t1_ns - s.t0_ns for s in spans
                if s.step in steps and is_lifecycle(s.name) and outermost(s))
    return total * 1e-6 / len(steps)
