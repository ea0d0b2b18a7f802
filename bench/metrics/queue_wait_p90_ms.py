"""90th percentile of the admission wait: the program's
``session.queued`` spans (``Engine.submit`` to admission) that ended in
the window, and every session still queued at the window's close with
its wait so far (as ``turn_ttft_p90_ms`` counts open waits)."""
import numpy as np

from bench.metrics._program import program

UNIT = "ms"


def read(rec):
    prog = program(rec)
    if prog is None:
        return None
    lo, hi = (t * 1e9 for t in rec.window)
    waits = []
    for s in prog["spans"] + prog["open"]:
        if s.name != "session.queued" or s.t0_ns >= hi:
            continue
        if s.t1_ns < 0 or s.t1_ns > hi:
            waits.append(hi - s.t0_ns)           # still queued at the close
        elif s.t1_ns >= lo:
            waits.append(s.t1_ns - s.t0_ns)
    if not waits:
        return None
    return float(np.percentile(waits, 90)) * 1e-6
