"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-op
device time and idle gaps labelled by the host span they fell in.

Device planes are those named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per executed operation. Host spans are the benchmark's own
``TraceAnnotation`` events (names starting ``bench.``) on the host plane,
on the same clock. The window is the ``bench.window`` span.
"""
from __future__ import annotations

import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
# control flow whose events enclose the operations of its body
ENCLOSING = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``%copy.102 = bf16[...] copy(...)`` -> ``copy.102``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _union(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def load(path):
    """(device op events per device, host spans): events are
    (start_ns, end_ns, name)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, spans = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs += [(e.start_ns, e.end_ns, op_name(e.name))
                            for e in line.events]
            devices.append(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.end_ns, e.name) for e in line.events
                          if e.name.startswith("bench.")]
    return devices, spans


def reduce(devices, spans, top: int = 10) -> dict:
    """busy_s and window_s (busy averaged over devices), per-op seconds
    averaged over devices (control-flow ops left out: their bodies' ops
    are counted), and idle seconds by the host span that covered them most
    (the innermost on a tie), for device 0."""
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not devices or not any(devices):
        return {}
    if win:
        lo, hi = win[0]
    else:
        lo = min(s for d in devices for s, _, _ in d)
        hi = max(e for d in devices for _, e, _ in d)
    busy, ops = [], defaultdict(float)
    for evs in devices:
        u = _union(_clip([(s, e) for s, e, _ in evs], lo, hi))
        busy.append(sum(e - s for s, e in u))
        for s, e, n in evs:
            if e > lo and s < hi and not n.startswith(ENCLOSING):
                ops[n] += (min(e, hi) - max(s, lo)) / len(devices)
    u = _union(_clip([(s, e) for s, e, _ in devices[0]], lo, hi))
    gaps, cur = [], lo
    for s, e in u:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    inner = [(s, e, n) for s, e, n in spans if n != WINDOW_SPAN]
    idle = defaultdict(float)
    for gs, ge in gaps:
        best, key = "outside bench spans", (0, 0)
        for s, e, n in inner:
            ov = min(e, ge) - max(s, gs)
            if ov > 0 and (ov, -(e - s)) > key:
                best, key = n, (ov, -(e - s))
        idle[best] += ge - gs
    ns = 1e-9
    return {
        "busy_s": sum(busy) / len(busy) * ns,
        "window_s": (hi - lo) * ns,
        "op_s": {n: v * ns for n, v in ops.items()},
        "device_ops": [[n, v * ns] for n, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, v * ns] for n, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
    }
