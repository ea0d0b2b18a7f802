"""95th percentile of every gap between consecutive reply tokens of one
reply, over all sessions, whose later token came in the window; a session
mid-reply at the window's close counts its open gap."""
from bench.metrics._common import gaps, pct_ms

UNIT = "ms"


def read(rec):
    return pct_ms(gaps(rec), 95)
