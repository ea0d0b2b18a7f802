"""90th percentile, over the turns whose first reply token came in the
window, of the time from the turn's input being ready (task submitted, or
tool result appended) to that first reply token; a turn still waiting at
the window's close counts with its wait so far."""
from bench.metrics._common import in_window, open_waits, pct_ms

UNIT = "ms"


def read(rec):
    return pct_ms([v for s in rec.sessions for v in in_window(rec, s.turns)]
                  + open_waits(rec, "turn"), 90)
