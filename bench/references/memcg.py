"""Plain reference of the control plane: memcg-style hierarchical charging.

Domains form a tree under the root, whose ``memory.max`` and ``memory.high``
are the pool. A charge of ``a`` pages to a domain is denied when the domain
or an ancestor is frozen, is inside a throttle window, or would pass its
``memory.max``; otherwise every domain on the chain is charged. A granted
charge that leaves a domain on the chain over its ``memory.high`` opens a
throttle window on the charged domain: the graduated allocator delay of the
paper (section 5), ``min(MAX, BASE * (1 + GAIN * overage))`` ms, a tenth of
it for HIGH priority, none where every over-high domain on the chain is at
or under its ``memory.low``; the engine's clock is one step per ``STEP_MS``.
Uncharges, lifecycle moves (a thaw's re-charge, a tool call's residual
passed up on close) and removals clamp at zero. Freeze and thaw act on a
whole subtree.

``replay`` is fed what a run recorded (``bench/check.py``
``record_control``): the lifecycle operations the engine issued, each
in-step batch of charge requests with the grants the program gave, and the
state the engine's daemon read at every step boundary. It keeps its own
tree, decides every request itself, and counts each grant, usage, throttle
window and freeze flag of the program that differs. It imports nothing of
the program.
"""
from __future__ import annotations

import math

import numpy as np

UNLIMITED = (1 << 31) - 1
HIGH = 2
BASE_DELAY_MS, MAX_DELAY_MS = np.float32(10.0), np.float32(2000.0)
OVERAGE_GAIN, HIGH_DISCOUNT = np.float32(10.0), np.float32(0.1)
STEP_MS = np.float32(10.0)


class Domain:
    __slots__ = ("parent", "high", "max", "low", "priority", "usage",
                 "frozen", "throttle_until")

    def __init__(self, parent, high, max_, low, priority):
        self.parent = parent
        self.high, self.max, self.low = high, max_, low
        self.priority = priority
        self.usage = 0
        self.frozen = False
        self.throttle_until = 0

    def chain(self):
        d = self
        while d is not None:
            yield d
            d = d.parent


def in_scope(scope: str, path: str) -> bool:
    return scope == "/" or path == scope or path.startswith(scope + "/")


class Tree:
    def __init__(self, capacity: int, expect=None):
        self.dom = {"/": Domain(None, capacity, capacity, 0, 1)}
        self.path_of = {0: "/"}
        # the spec (priority, memory.low) the benchmark states for a
        # domain, where it states one: it overrides what the engine asked
        self.expect = expect or {}
        self.either = {}        # path -> the two windows a rounding allows
        self.count = {"steps": 0, "requests": 0, "denied": 0,
                      "throttled": 0, "grants_differing": 0,
                      "state_differing": 0}

    def _get(self, p):
        """The domain at a path or handle; one the reference does not have
        is a difference."""
        d = self.dom.get(p if isinstance(p, str) else
                         self.path_of.get(int(p), ""))
        self.count["state_differing"] += d is None
        return d

    def _add(self, d, n):
        for c in (d.chain() if d is not None else ()):
            c.usage = max(0, c.usage + n)

    def mkdir(self, path, handle, high, max_, low, priority):
        if path in self.expect and self.expect[path] != (priority, low):
            self.count["state_differing"] += 1
            priority, low = self.expect[path]
        parent = self.dom[path.rsplit("/", 1)[0] or "/"]
        self.dom[path] = Domain(parent, high, max_, low, priority)
        self.path_of[handle] = path

    def rmdir(self, path, transfer):
        d = self._get(path)
        if d is None:
            return
        del self.dom[path]
        self.path_of = {h: p for h, p in self.path_of.items() if p != path}
        residual = d.usage
        if residual:
            self._add(d, -residual)
            if transfer:
                self._add(d.parent, residual)

    def uncharge(self, path, n):
        self._add(self._get(path), -n)

    def charge_unchecked(self, path, n):
        self._add(self._get(path), n)

    def freeze(self, path, flag=True):
        for p, d in self.dom.items():
            if in_scope(path, p):
                d.frozen = flag

    def write(self, path, file, value):
        if file == "cgroup.freeze":
            self.freeze(path, bool(int(value)))
        elif file in ("memory.high", "memory.max", "memory.low"):
            d = self._get(path)
            if d is not None:
                setattr(d, file.split(".")[1], int(value))

    def _charge(self, d, path, a, step):
        chain = list(d.chain())
        if (any(c.frozen for c in chain)
                or any(c.throttle_until > step for c in chain)
                or any(c.usage + a > c.max for c in chain)):
            return False
        for c in chain:
            c.usage += a
        over = [(c.usage - c.high, c.high, c.usage <= c.low) for c in chain
                if c.high < UNLIMITED and c.usage > c.high]
        if not over:
            return True
        frac = max(np.float32(o) / np.float32(max(h, 1)) for o, h, _ in over)
        delay = np.float32(0.0)
        if not all(prot for _, _, prot in over):
            delay = min(MAX_DELAY_MS,
                        BASE_DELAY_MS * (np.float32(1.0) + OVERAGE_GAIN * frac))
            if d.priority == HIGH:
                delay = delay * HIGH_DISCOUNT
        q = np.float32(delay) / STEP_MS
        old = d.throttle_until
        d.throttle_until = max(old, step + math.ceil(q))
        self.count["throttled"] += 1
        if abs(q - round(q)) < 1e-3:
            # a quotient this close to a whole number may round to either
            # side of it on the device: the next boundary shows which
            self.either[path] = {max(old, step + round(q)),
                                 max(old, step + round(q) + 1)}
        return True

    def step(self, step, dom, amt, granted):
        self.count["steps"] += 1
        for h, a, g in zip(dom.tolist(), amt.tolist(), granted.tolist()):
            ok = False
            if h >= 0:
                d = self._get(h)
                self.count["requests"] += 1
                ok = d is not None and self._charge(d, self.path_of[h], a,
                                                    step)
                self.count["denied"] += not ok
            self.count["grants_differing"] += ok != bool(g)

    def boundary(self, index, usage, throttle_until, frozen):
        """The state the engine's daemon read after a step, against the
        reference's."""
        paths = set(index)
        self.count["state_differing"] += len(paths ^ set(self.dom))
        for p in paths & set(self.dom):
            d, i = self.dom[p], index[p]
            tu = int(throttle_until[i])
            if tu in self.either.get(p, ()):
                d.throttle_until = tu
            self.count["state_differing"] += (
                (int(usage[i]) != d.usage) + (tu != d.throttle_until)
                + (bool(frozen[i]) != d.frozen))
        self.either.clear()


def replay(ops: list, capacity: int, expect=None) -> dict:
    """Counts of a recorded run replayed through the reference."""
    t = Tree(capacity, expect)
    for op in ops:
        kind, args = op[0], op[1:]
        if kind == "step":
            step, dom, amt, granted = args
            t.step(int(step), np.asarray(dom), np.asarray(amt),
                   np.asarray(granted))
        elif kind == "boundary":
            t.boundary(*args)
        elif kind == "freeze":
            t.freeze(args[0], True)
        elif kind == "thaw":
            t.freeze(args[0], False)
        else:
            getattr(t, kind)(*args)
    return t.count
