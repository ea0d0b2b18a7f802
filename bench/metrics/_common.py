"""Shared arithmetic of the metric readers: samples inside the window."""
from __future__ import annotations

import numpy as np

from repro.core import domains as D


def in_window(rec, pairs):
    lo, hi = rec.window
    return [v for t, v in pairs if lo <= t <= hi]


def gaps(rec, high_only=False):
    """Gaps between reply tokens that ended in the window, and the open
    gap of every session still waiting for its next reply token at the
    window's close."""
    done = [g for s in rec.sessions
            if not high_only or s.priority == D.HIGH
            for g in in_window(rec, s.gaps)]
    return done + open_waits(rec, "gap", high_only)


def open_waits(rec, kind, high_only=False):
    return [w for prio, k, w in rec.open_waits
            if k == kind and (not high_only or prio == D.HIGH)]


def pct_ms(xs, q):
    return float(np.percentile(xs, q)) * 1e3 if xs else None
