"""Read a profiler trace by what the program was doing: its own host spans
(``agentcgroup.*`` ``TraceAnnotation``s) and the named scopes of its
jitted step (``jax.named_scope``, carried into the compiled HLO's
``op_name`` metadata).

The trace does not expose an operation's metadata, so the scopes come
from the compiled step's HLO text, which maps each instruction name (the
trace's operation names, such as ``broadcast_select_fusion.1``) to its
``op_name``. An operation belongs to the step when it runs inside one of
the ``XLA Modules`` line's ``jit_step_fn(...)`` intervals.
"""
from __future__ import annotations

import gzip
import re
from collections import defaultdict
from pathlib import Path

from bench.xplane import (DEVICE_PLANE, ENCLOSING, OPS_LINE, WINDOW_SPAN,
                          _clip, _union, op_name)

MODULES_LINE = "XLA Modules"
STEP_MODULE = "jit_step_fn("
PROGRAM_PREFIX = "agentcgroup."
STEP_ROOT = "agentcgroup.step"
TRANSFER = "tpu::System::TransferFromDevice"
# the step's named scopes, in the order they are looked for in an op_name
SCOPES = ("agentcgroup.schedule", "agentcgroup.charge", "agentcgroup.gate",
          "model.decode", "model.sample")
ENFORCE = ("agentcgroup.schedule", "agentcgroup.charge", "agentcgroup.gate")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def load(path):
    """(device op events, step module intervals, host spans, host
    transfers from the device) of an ``.xplane.pb`` file (or its
    ``.gz``): ops and intervals per device, events as (start_ns, end_ns,
    name); host spans are the program's (``agentcgroup.``) and the
    benchmark's (``bench.``)."""
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        pd = ProfileData.from_serialized_xspace(
            gzip.decompress(Path(path).read_bytes()))
    else:
        pd = ProfileData.from_file(str(path))
    ops, modules, spans, transfers = [], [], [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs += [(e.start_ns, e.end_ns, op_name(e.name))
                            for e in line.events]
                elif line.name == MODULES_LINE:
                    mods += [(e.start_ns, e.end_ns, e.name)
                             for e in line.events
                             if e.name.startswith(STEP_MODULE)]
            ops.append(evs)
            modules.append(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((PROGRAM_PREFIX, "bench.")):
                        spans.append((e.start_ns, e.end_ns, e.name))
                    elif e.name == TRANSFER:
                        transfers.append((e.start_ns, e.end_ns, e.name))
    return ops, modules, spans, transfers


def hlo_scopes(hlo_text: str) -> dict:
    """Instruction name -> the first of ``SCOPES`` in its ``op_name``
    metadata, for every instruction of an HLO module's text that names
    one (a fusion carries the metadata of its root)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        meta = _OP_NAME.search(line)
        if meta is None:
            continue
        parts = meta.group(1).split("/")
        for scope in SCOPES:
            if scope in parts:
                out[m.group(1)] = scope
                break
    return out


def _window(spans):
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    return win[0] if win else None


def step_scope_seconds(ops, modules, scopes: dict, spans=()) -> dict:
    """Device seconds of the step module's operations in the window, by
    scope (``unscoped`` where the HLO names none), averaged over
    devices. Control-flow operations are left out: their bodies' ops
    are counted."""
    win = _window(spans)
    out = defaultdict(float)
    for evs, mods in zip(ops, modules):
        mods = sorted((s, e) for s, e, _ in mods)
        if win is not None:
            mods = _clip(mods, *win)
        for s, e, n in evs:
            if n.startswith(ENCLOSING):
                continue
            for ms, me in mods:
                if s >= ms and e <= me:
                    out[scopes.get(n, "unscoped")] += (e - s) * 1e-9
                    break
    return {k: v / max(len(ops), 1) for k, v in out.items()}


def _gaps(ops, spans) -> list:
    """Device 0's idle intervals in the window."""
    win = _window(spans)
    lo, hi = win if win else (min(s for s, _, _ in ops[0]),
                              max(e for _, e, _ in ops[0]))
    gaps, cur = [], lo
    for s, e in _union(_clip([(s, e) for s, e, _ in ops[0]], lo, hi)):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def _attribute(gs, ge, cands) -> str:
    """The innermost span that covers most of [gs, ge): the shortest
    covering at least half of it, else the one covering the most."""
    best, most, inner = "outside program spans", 0, None
    for s, e, n in cands:
        ov = min(e, ge) - max(s, gs)
        if ov <= 0:
            continue
        if ov > most:
            best, most = n, ov
        if 2 * ov >= ge - gs and (inner is None or e - s < inner[0]):
            inner = (e - s, n)
    return inner[1] if inner is not None else best


def _attributed_gaps(ops, spans):
    """(gap start, gap end, program span it falls under) for device 0's
    idle gaps in the window, swept in time order over the spans."""
    prog = sorted(x for x in spans if x[2].startswith(PROGRAM_PREFIX))
    active, i = [], 0
    for gs, ge in _gaps(ops, spans):
        while i < len(prog) and prog[i][0] < ge:
            active.append(prog[i])
            i += 1
        active = [x for x in active if x[1] > gs]
        yield gs, ge, _attribute(gs, ge, active)


def idle_program_spans(ops, spans, top: int = 12) -> list:
    """Device 0's idle gaps in the window, each under the innermost
    program span that covers most of it: [[name, seconds], ...],
    largest first."""
    if not ops or not ops[0]:
        return []
    idle = defaultdict(float)
    for gs, ge, name in _attributed_gaps(ops, spans):
        idle[name] += (ge - gs) * 1e-9
    return [[n, v] for n, v in
            sorted(idle.items(), key=lambda kv: -kv[1])[:top]]


def idle_under_phases(ops, spans) -> tuple:
    """(device 0's idle seconds inside ``bench.step`` spans, of which in
    gaps put under a program span below the step root)."""
    if not ops or not ops[0]:
        return 0.0, 0.0
    steps = _union([(s, e) for s, e, n in spans if n == "bench.step"])
    total = under = 0.0
    for gs, ge, name in _attributed_gaps(ops, spans):
        inside = sum(e - s for s, e in _clip(steps, gs, ge)) * 1e-9
        total += inside
        if name not in (STEP_ROOT, "outside program spans"):
            under += inside
    return total, under


def transfers_in_window(transfers, spans) -> int:
    win = _window(spans)
    if win is None:
        return len(transfers)
    return sum(1 for s, _, _ in transfers if win[0] <= s < win[1])
