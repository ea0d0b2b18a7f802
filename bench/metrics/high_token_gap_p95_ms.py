"""``token_gap_p95_ms`` over the HIGH-priority agents' sessions only."""
from bench.metrics._common import gaps, pct_ms

UNIT = "ms"


def read(rec):
    return pct_ms(gaps(rec, high_only=True), 95)
