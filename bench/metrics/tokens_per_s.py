"""Reply tokens of every session served inside the window, over the
window's seconds (host clock)."""
UNIT = "tokens/s"


def read(rec):
    lo, hi = rec.window
    n = sum(lo <= t <= hi for s in rec.sessions
            for t in [t for t, _ in s.gaps] + [t for t, _ in s.turns])
    return n / (hi - lo)
