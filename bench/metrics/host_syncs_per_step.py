"""Device-to-host reads per window step: the program's ``host_sync``
counter (every read on the served path goes through one counting
helper, ``repro.spans.to_host``), over the window's steps."""
from bench.metrics._program import program, window_steps

UNIT = "count"


def read(rec):
    prog = program(rec)
    if prog is None:
        return None
    steps = window_steps(rec, prog)
    if not steps:
        return None
    return sum(c.get("host_sync", 0) for step, c in prog["counters"]
               if step in steps) / len(steps)
