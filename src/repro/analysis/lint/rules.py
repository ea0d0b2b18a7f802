"""The tracelint rules: the repo's load-bearing invariants, statically.

Each rule encodes an invariant the conformance kit certifies
dynamically (tests/test_cgroup.py parity, hypothesis fuzz) — here it is
checked the way the kernel verifier checks an eBPF program: from the
text alone, before anything runs.  See the package docstring for the
rule table and ``tests/test_lint.py`` for one seeded-violation /
clean-twin fixture pair per rule.
"""
from __future__ import annotations

import ast
import builtins
from typing import Optional

from repro.analysis.lint.core import (FileContext, Finding, Rule,
                                      is_static_test, qualname)

_BUILTINS = frozenset(dir(builtins))

# program classes: the memcg_bpf_ops analogues whose hooks are traced
# by every backend (core/progs.py) — subclasses anywhere inherit the
# trace-purity obligation
PROGRAM_BASES = frozenset({
    "PolicyProgram", "GraduatedThrottleProgram", "TokenBucketProgram",
    "WeightedFairProgram",
})
TRACED_HOOKS = frozenset({"on_charge", "on_over_high", "on_gate",
                          "on_schedule"})
# module-level decision entry points in the decision-path modules —
# the functions all six backend kinds trace verbatim.  Python-time
# registry dispatch helpers (``_single_prog``, the branch factories)
# are deliberately NOT roots — their length checks run at trace time,
# never on traced values.
TRACED_FUNCS = frozenset({
    "charge_decision", "schedule_decision", "charge_batch", "slot_gate",
    "uncharge_batch", "_chain_view", "_ancestor_chain",
    "charge_stall_event", "sched_stall_events",
    "_decision_one", "gate_decision", "schedule_weight",
    "saturating_count",
})


def _is_program_class(node: ast.ClassDef) -> bool:
    if node.name in PROGRAM_BASES:
        return True
    for base in node.bases:
        q = qualname(base)
        if q is not None and q.split(".")[-1] in PROGRAM_BASES:
            return True
    return any(isinstance(m, ast.FunctionDef) and m.name in TRACED_HOOKS
               for m in node.body)


class TracePurity(Rule):
    """TL001: no python control flow, host casts, numpy, or host syncs
    inside traced decision scopes.  A python ``if`` on a traced value
    does not error — it silently *forks the trace* on the tracer's
    boolean, and host replay / jitted engine / shard_map stop running
    the same decision path.  The eBPF verifier rejects unverifiable
    branches for the same reason."""

    id = "TL001"
    name = "trace-purity"
    description = ("python if/while/assert, .item()/float()/int() casts, "
                   "np.* calls and host syncs in traced decision scopes")

    CASTS = frozenset({"float", "int", "bool", "complex"})
    HOST_SYNCS = frozenset({"block_until_ready", "device_get"})

    def applies(self, ctx: FileContext) -> bool:
        return ctx.is_decision_module or any(
            isinstance(n, ast.ClassDef) and _is_program_class(n)
            for n in ast.walk(ctx.tree))

    # ------------------------------------------------------ traced scopes

    def _traced_roots(self, ctx: FileContext) -> list:
        roots = []
        for node in ctx.tree.body:
            if (isinstance(node, ast.FunctionDef)
                    and ctx.is_decision_module
                    and node.name in TRACED_FUNCS):
                roots.append(node)
            elif isinstance(node, ast.ClassDef) and _is_program_class(node):
                for m in node.body:
                    if (isinstance(m, ast.FunctionDef)
                            and m.name in (TRACED_HOOKS | {"delay_ms"})):
                        roots.append(m)
        return roots

    def check(self, ctx: FileContext) -> list:
        out = []
        for root in self._traced_roots(ctx):
            scope = (f"{root.name}" if isinstance(root, ast.FunctionDef)
                     else "<traced>")
            for node in ast.walk(root):
                out.extend(self._check_node(ctx, node, scope))
        if ctx.is_decision_module:
            # host syncs are module-wide poison in decision modules:
            # even outside a traced scope they mean the decision path
            # depends on a device round trip
            for node in ast.walk(ctx.tree):
                if (isinstance(node, ast.Attribute)
                        and node.attr in self.HOST_SYNCS):
                    out.append(ctx.finding(
                        self.id, node,
                        f"host sync '{node.attr}' in decision-path module"))
        return out

    def _check_node(self, ctx, node, scope) -> list:
        out = []
        if isinstance(node, (ast.If, ast.While)):
            if not is_static_test(node.test):
                kw = "if" if isinstance(node, ast.If) else "while"
                out.append(ctx.finding(
                    self.id, node,
                    f"python '{kw}' on a potentially-traced value in "
                    f"traced scope '{scope}' (use jnp.where/lax.cond — "
                    "a python branch forks the one decision path)"))
        elif isinstance(node, ast.IfExp):
            if not is_static_test(node.test):
                out.append(ctx.finding(
                    self.id, node,
                    f"python conditional expression in traced scope "
                    f"'{scope}' (use jnp.where)"))
        elif isinstance(node, ast.Assert):
            out.append(ctx.finding(
                self.id, node,
                f"python 'assert' in traced scope '{scope}' (asserts on "
                "traced values sync or silently vanish under jit; use "
                "checkify or move the check host-side)"))
        elif isinstance(node, ast.Call):
            q = qualname(node.func)
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item"):
                out.append(ctx.finding(
                    self.id, node,
                    f".item() host sync in traced scope '{scope}'"))
            elif (q in self.CASTS
                  and node.args
                  and not all(isinstance(a, ast.Constant)
                              for a in node.args)):
                out.append(ctx.finding(
                    self.id, node,
                    f"{q}() cast in traced scope '{scope}' forces a host "
                    "sync on traced values (use jnp dtypes/astype)"))
            elif q is not None and q.split(".")[0] in ("np", "numpy"):
                out.append(ctx.finding(
                    self.id, node,
                    f"numpy call '{q}' in traced scope '{scope}' "
                    "(silently syncs traced arrays to host; use jnp)"))
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in self.HOST_SYNCS):
                out.append(ctx.finding(
                    self.id, node,
                    f"host sync '{node.func.attr}' in traced scope "
                    f"'{scope}'"))
        return out


class RetraceHazards(Rule):
    """TL002: python scalars closed over inside jitted callables.  A
    closed-over ``float(cfg.x)`` is baked into the trace as a constant:
    every new value is a new jit cache entry (cache explosion) and a
    'retune' that should be a param-table write silently recompiles —
    breaking the zero-retrace contract ``update_params`` promises.
    Retunable values belong in the program param table (state), not the
    closure."""

    id = "TL002"
    name = "retrace-hazard"
    description = ("non-param-table python scalars (or loop variables) "
                   "closed over inside jit-compiled callables")

    JIT_NAMES = frozenset({"jax.jit", "jit"})

    def applies(self, ctx: FileContext) -> bool:
        return ctx.in_dirs(("core",)) or ctx.is_decision_module

    def check(self, ctx: FileContext) -> list:
        out = []
        self._walk(ctx, ctx.tree, [], out)
        return out

    def _walk(self, ctx, node, stack, out) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.Call) and \
                    qualname(child.func) in self.JIT_NAMES and stack:
                self._check_jit_call(ctx, child, stack, out)
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                self._walk(ctx, child, stack + [child], out)
            else:
                self._walk(ctx, child, stack, out)

    def _check_jit_call(self, ctx, call, stack, out) -> None:
        if not call.args:
            return
        target = call.args[0]
        fn = None
        if isinstance(target, ast.Lambda):
            fn = target
        elif isinstance(target, ast.Name):
            # a local def referenced by name; module-level defs have no
            # enclosing python frame to close over
            for scope in reversed(stack):
                for n in ast.walk(scope):
                    if (isinstance(n, ast.FunctionDef)
                            and n.name == target.id):
                        fn = n
                        break
                if fn is not None:
                    break
        if fn is None:
            return
        for name in sorted(_free_names(fn)):
            verdict = _closure_binding_hazard(name, stack)
            if verdict is not None:
                out.append(ctx.finding(
                    self.id, call,
                    f"jitted callable closes over '{name}' ({verdict}); "
                    "pass it as an argument or move it into the program "
                    "param table so retunes stay zero-retrace"))


def _free_names(fn) -> set:
    """Names loaded in ``fn`` but bound neither locally nor as params
    (builtins excluded) — the closure surface."""
    bound, loads = set(), set()
    args = fn.args
    for a in (args.posonlyargs + args.args + args.kwonlyargs):
        bound.add(a.arg)
    for a in (args.vararg, args.kwarg):
        if a is not None:
            bound.add(a.arg)
    for n in ast.walk(fn):
        if isinstance(n, ast.Name):
            (bound if isinstance(n.ctx, (ast.Store, ast.Del))
             else loads).add(n.id)
        elif isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.ClassDef)):
            if n is not fn:
                bound.add(n.name)
        elif isinstance(n, ast.Lambda) and n is not fn:
            la = n.args
            for a in (la.posonlyargs + la.args + la.kwonlyargs):
                bound.add(a.arg)
        elif isinstance(n, (ast.Import, ast.ImportFrom)):
            for alias in n.names:
                bound.add((alias.asname or alias.name).split(".")[0])
    return loads - bound - _BUILTINS


def _scalar_like(node) -> bool:
    if isinstance(node, ast.Constant):
        return isinstance(node.value, (int, float, bool, complex))
    if isinstance(node, ast.Call):
        return qualname(node.func) in ("int", "float", "bool", "len")
    if isinstance(node, ast.BinOp):
        return _scalar_like(node.left) or _scalar_like(node.right)
    if isinstance(node, ast.UnaryOp):
        return _scalar_like(node.operand)
    return False


def _closure_binding_hazard(name, stack) -> Optional[str]:
    """How ``name`` is bound in the enclosing function scopes, innermost
    first; returns a hazard description or None when the binding looks
    safe (an object reference like ``prog = self.prog``, whose identity
    IS the compiled code) or is module-global."""
    for scope in reversed(stack):
        if isinstance(scope, ast.Lambda):
            continue
        for n in ast.walk(scope):
            if isinstance(n, ast.For):
                targets = [t.id for t in ast.walk(n.target)
                           if isinstance(t, ast.Name)]
                if name in targets:
                    return ("bound as a loop variable — one jit cache "
                            "entry per iteration")
            elif isinstance(n, ast.Assign):
                targets = [t.id for t in n.targets
                           if isinstance(t, ast.Name)]
                if name in targets and _scalar_like(n.value):
                    return "a python scalar baked in as a trace constant"
            elif isinstance(n, ast.AnnAssign):
                if (isinstance(n.target, ast.Name) and n.target.id == name
                        and n.value is not None
                        and _scalar_like(n.value)):
                    return "a python scalar baked in as a trace constant"
    return None


class ReplayDeterminism(Rule):
    """TL003: no wall clocks or unseeded entropy on the record/replay
    path.  ``fig8_replay`` has been bit-identical since PR 2 — one
    ``time.time()`` stamped into a state record breaks snapshot
    stability and replay equality probabilistically, which no parity
    test catches until it flakes.  ``time.monotonic``/``time.sleep``
    stay legal: they shape wall-clock behaviour (timeouts, injected
    delays), never recorded state.

    The ``launch``/``benchmarks`` allowlist is for *measurement*, not a
    license for wall clocks in recorded state: benchmark timing code
    must still use ``time.perf_counter()`` (monotonic, highest
    resolution) rather than ``time.time()``, which steps under NTP slew
    and makes latency numbers irreproducible."""

    id = "TL003"
    name = "replay-determinism"
    description = ("time.time/datetime.now/os.urandom/stdlib random/"
                   "unseeded np.random in core/, traces/, testing/")

    SCOPE_DIRS = ("core", "traces", "testing")
    ALLOW_DIRS = ("launch", "benchmarks")
    DATETIME_FNS = frozenset({"now", "utcnow", "today"})
    NP_RANDOM_OK = frozenset({"default_rng", "SeedSequence", "Generator",
                              "PCG64", "Philox"})

    def applies(self, ctx: FileContext) -> bool:
        return (ctx.in_dirs(self.SCOPE_DIRS)
                and not ctx.in_dirs(self.ALLOW_DIRS))

    def check(self, ctx: FileContext) -> list:
        out = []
        # `from time import time` / `from random import ...` defeat the
        # attribute checks below — ban the import form itself
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom):
                if node.module == "time" and any(
                        a.name == "time" for a in node.names):
                    out.append(ctx.finding(
                        self.id, node,
                        "'from time import time' — wall clock on the "
                        "replay path (use the facade/step clock)"))
                if node.module == "random":
                    out.append(ctx.finding(
                        self.id, node,
                        "'from random import ...' — unseeded global RNG "
                        "on the replay path (use np.random.default_rng"
                        "(seed))"))
            q = qualname(node) if isinstance(node, ast.Attribute) else None
            if q == "time.time":
                out.append(ctx.finding(
                    self.id, node,
                    "time.time() — wall clock stamped on the replay path "
                    "(use the facade/step clock passed by the caller)"))
            elif q in ("os.urandom",):
                out.append(ctx.finding(
                    self.id, node,
                    "os.urandom — entropy on the replay path"))
            elif (q is not None and q.startswith("datetime.")
                  and q.split(".")[-1] in self.DATETIME_FNS):
                out.append(ctx.finding(
                    self.id, node,
                    f"{q}() — wall clock on the replay path"))
            elif (q is not None and q.startswith("random.")
                  and q.count(".") == 1):
                fn = q.split(".")[-1]
                if fn != "Random":
                    out.append(ctx.finding(
                        self.id, node,
                        f"stdlib {q} — process-global RNG on the replay "
                        "path (use np.random.default_rng(seed))"))
            if isinstance(node, ast.Call):
                fq = qualname(node.func)
                if fq in ("np.random.default_rng",
                          "numpy.random.default_rng"):
                    if not node.args and not node.keywords:
                        out.append(ctx.finding(
                            self.id, node,
                            "np.random.default_rng() without a seed — "
                            "entropy on the replay path"))
                elif (fq is not None
                      and (fq.startswith("np.random.")
                           or fq.startswith("numpy.random."))
                      and fq.split(".")[-1] not in self.NP_RANDOM_OK):
                    out.append(ctx.finding(
                        self.id, node,
                        f"legacy global-state '{fq}' on the replay path "
                        "(use a seeded np.random.default_rng)"))
        return out


class LockDiscipline(Rule):
    """TL004: inner-backend access outside the apply lock.  The async
    daemon's correctness argument is 'readers observe whole epochs':
    every ``self.inner`` touch outside ``with self._apply_lock`` (or a
    callable run under it via ``_observe``) can see a batch
    mid-application — the race the epoch tag exists to prevent."""

    id = "TL004"
    name = "lock-discipline"
    description = ("inner-backend attribute access outside a "
                   "'with self._apply_lock' block (async daemon classes)")

    MODULES = ("core/daemon.py", "core/faults.py")
    INNER_NAMES = ("inner", "_inner")
    EXEMPT_METHODS = frozenset({"__init__", "_observe"})

    def applies(self, ctx: FileContext) -> bool:
        return ctx.endswith(self.MODULES)

    def check(self, ctx: FileContext) -> list:
        out = []
        for node in ctx.tree.body:
            if isinstance(node, ast.ClassDef):
                out.extend(self._check_class(ctx, node))
        return out

    def _check_class(self, ctx, cls) -> list:
        init = next((m for m in cls.body if isinstance(m, ast.FunctionDef)
                     and m.name == "__init__"), None)
        if init is None:
            return []
        assigned = {n.attr for n in ast.walk(init)
                    if isinstance(n, ast.Attribute)
                    and isinstance(n.ctx, ast.Store)
                    and isinstance(n.value, ast.Name)
                    and n.value.id == "self"}
        # lock discipline only binds classes that HAVE the lock: a
        # synchronous single-writer wrapper (FaultyBackend) has no
        # epochs to protect
        if "_apply_lock" not in assigned:
            return []
        inner = next((n for n in self.INNER_NAMES if n in assigned), None)
        if inner is None:
            return []
        out = []
        for m in cls.body:
            if (isinstance(m, ast.FunctionDef)
                    and m.name not in self.EXEMPT_METHODS):
                out.extend(self._check_method(ctx, m, inner))
        return out

    def _observe_callables(self, method) -> set:
        """Callables executed under the lock by ``self._observe``:
        lambda/def arguments plus local defs passed by name."""
        passed = set()
        for n in ast.walk(method):
            if (isinstance(n, ast.Call)
                    and qualname(n.func) == "self._observe"):
                for a in n.args:
                    if isinstance(a, (ast.Lambda, ast.FunctionDef)):
                        passed.add(id(a))
                    elif isinstance(a, ast.Name):
                        passed.add(a.id)
        locked = set()
        for n in ast.walk(method):
            if isinstance(n, ast.Lambda) and id(n) in passed:
                locked.add(n)
            elif (isinstance(n, ast.FunctionDef)
                  and (id(n) in passed or n.name in passed)):
                locked.add(n)
        return locked

    def _check_method(self, ctx, method, inner) -> list:
        locked_fns = self._observe_callables(method)
        out = []

        def is_lock_with(stmt) -> bool:
            return isinstance(stmt, ast.With) and any(
                qualname(item.context_expr) == "self._apply_lock"
                for item in stmt.items)

        def visit(node, locked):
            if node in locked_fns:
                locked = True
            if is_lock_with(node):
                locked = True
            if (not locked and isinstance(node, ast.Attribute)
                    and node.attr == inner
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "self"
                    and isinstance(node.ctx, ast.Load)):
                out.append(ctx.finding(
                    self.id, node,
                    f"self.{inner} accessed outside 'with "
                    "self._apply_lock' — a reader here can observe an "
                    "epoch mid-application (route it through "
                    "self._observe)"))
            for child in ast.iter_child_nodes(node):
                visit(child, locked)

        visit(method, False)
        return out


class ProtocolDrift(Rule):
    """TL005: backend classes vs the ``Backend`` protocol, statically.
    Conformance certifies the ops a scenario happens to exercise; a
    missing method or drifted signature on a rarely-hit op (kill during
    rmdir races) surfaces only in production.  This diff is total."""

    id = "TL005"
    name = "protocol-drift"
    description = ("backend classes missing protocol methods, carrying "
                   "signature mismatches, or growing unsanctioned surface")
    project_wide = True

    PROTOCOL_CLASS = "Backend"
    # sanctioned extensions beyond the protocol (each is documented on
    # the class that carries it); anything else is drift until either
    # added here deliberately or promoted into the protocol
    EXTENSIONS = frozenset({
        "device_view", "restore", "flush", "barrier", "close",
        "throttle_delay_ms", "reconcile", "unwedge", "placement",
        "offload_fault",
    })

    def check_project(self, ctxs) -> list:
        proto = None
        for ctx in ctxs:
            for node in ast.walk(ctx.tree):
                if (isinstance(node, ast.ClassDef)
                        and node.name == self.PROTOCOL_CLASS
                        and any((qualname(b) or "").endswith("Protocol")
                                for b in node.bases)):
                    proto = node
                    break
            if proto is not None:
                break
        if proto is None:
            return []
        methods = {m.name: _sig(m) for m in proto.body
                   if isinstance(m, ast.FunctionDef)
                   and not m.name.startswith("_")}
        attrs = {s.target.id for s in proto.body
                 if isinstance(s, ast.AnnAssign)
                 and isinstance(s.target, ast.Name)}
        out = []
        for ctx in ctxs:
            for node in ast.walk(ctx.tree):
                if (isinstance(node, ast.ClassDef)
                        and node.name.endswith("Backend")
                        and node.name != self.PROTOCOL_CLASS
                        and not _is_exception(node)):
                    out.extend(self._check_backend(ctx, node, methods,
                                                   attrs))
        return out

    def _check_backend(self, ctx, cls, methods, attrs) -> list:
        defined = {m.name: m for m in cls.body
                   if isinstance(m, ast.FunctionDef)}
        has_getattr = "__getattr__" in defined
        out = []
        for name, want in sorted(methods.items()):
            if name not in defined:
                if not has_getattr:
                    out.append(ctx.finding(
                        self.id, cls,
                        f"{cls.name} is missing Backend method "
                        f"'{name}{_fmt(want)}'"))
                continue
            got = _sig(defined[name])
            if got is not None and want is not None and got != want:
                out.append(ctx.finding(
                    self.id, defined[name],
                    f"{cls.name}.{name}{_fmt(got)} drifts from the "
                    f"Backend protocol {_fmt(want)}"))
        for name, m in sorted(defined.items()):
            if (name.startswith("_") or name in methods
                    or name in self.EXTENSIONS
                    or _is_property(m)):
                continue
            out.append(ctx.finding(
                self.id, m,
                f"{cls.name}.{name} is not in the Backend protocol nor "
                "the sanctioned extension list (promote it or rename it "
                "to a private helper)"))
        if not has_getattr:
            init = defined.get("__init__")
            assigned = set()
            if init is not None:
                assigned = {n.attr for n in ast.walk(init)
                            if isinstance(n, ast.Attribute)
                            and isinstance(n.ctx, ast.Store)
                            and isinstance(n.value, ast.Name)
                            and n.value.id == "self"}
            props = {m.name for m in cls.body
                     if isinstance(m, ast.FunctionDef) and _is_property(m)}
            class_assigns = {t.id for s in cls.body
                             if isinstance(s, ast.Assign)
                             for t in s.targets
                             if isinstance(t, ast.Name)}
            for a in sorted(attrs):
                if a not in assigned | props | class_assigns:
                    out.append(ctx.finding(
                        self.id, cls,
                        f"{cls.name} does not provide Backend attribute "
                        f"'{a}'"))
        return out


def _sig(fn) -> Optional[tuple]:
    a = fn.args
    if a.vararg is not None or a.kwarg is not None:
        return None                    # dynamic signature: can't compare
    names = tuple(x.arg for x in (a.posonlyargs + a.args))
    return names[1:] if names and names[0] in ("self", "cls") else names


def _fmt(sig) -> str:
    return "(...)" if sig is None else f"({', '.join(sig)})"


def _is_exception(cls) -> bool:
    return any((qualname(b) or "").endswith(("Error", "Exception"))
               for b in cls.bases)


def _is_property(fn) -> bool:
    for d in fn.decorator_list:
        q = qualname(d)
        if q == "property" or (q is not None and q.endswith(".setter")):
            return True
    return False


class PytreeStability(Rule):
    """TL006: conditionally-created dict keys in control-state builders.
    jit caches key on pytree *structure*: a dict that sometimes carries
    a key and sometimes doesn't retraces on every structure flip — and
    snapshot/restore across the flip silently drops state.  Keys must
    exist unconditionally (use a neutral value instead of absence)."""

    id = "TL006"
    name = "pytree-stability"
    description = ("dict keys created under a conditional in functions "
                   "building control-state pytrees")

    def applies(self, ctx: FileContext) -> bool:
        return ctx.in_dirs(("core",))

    def check(self, ctx: FileContext) -> list:
        out = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._check_fn(ctx, node, out)
        return out

    def _check_fn(self, ctx, fn, out) -> None:
        tracked: dict = {}

        def literal_keys(value) -> Optional[set]:
            if isinstance(value, ast.Dict):
                keys = set()
                for k in value.keys:
                    if (isinstance(k, ast.Constant)
                            and isinstance(k.value, str)):
                        keys.add(k.value)
                    else:
                        return None     # **spread / computed key: opaque
                return keys
            if (isinstance(value, ast.Call)
                    and qualname(value.func) == "dict"
                    and not value.args):
                return {kw.arg for kw in value.keywords
                        if kw.arg is not None}
            return None

        def visit(stmts, depth) -> None:
            for s in stmts:
                if isinstance(s, ast.Assign) and len(s.targets) == 1:
                    t = s.targets[0]
                    if isinstance(t, ast.Name):
                        keys = literal_keys(s.value)
                        if keys is not None and depth == 0:
                            tracked[t.id] = keys
                        else:
                            tracked.pop(t.id, None)
                    elif (isinstance(t, ast.Subscript)
                          and isinstance(t.value, ast.Name)
                          and t.value.id in tracked
                          and isinstance(t.slice, ast.Constant)
                          and isinstance(t.slice.value, str)):
                        key = t.slice.value
                        if key in tracked[t.value.id]:
                            pass
                        elif depth > 0:
                            out.append(ctx.finding(
                                self.id, s,
                                f"dict key '{key}' created conditionally "
                                f"on '{t.value.id}' — pytree structure "
                                "now depends on runtime state (create "
                                "the key unconditionally with a neutral "
                                "value)"))
                        else:
                            tracked[t.value.id].add(key)
                for child, extra in _nested_blocks(s):
                    visit(child, depth + extra)

        visit(fn.body, 0)

    # note: nested function defs inside `fn` get their own _check_fn
    # pass via ast.walk in check(), so we skip them here


def _nested_blocks(stmt):
    """(body, conditional-depth-delta) pairs for compound statements.
    ``for``/``with`` bodies are not conditional structure-wise (the same
    keys are set each iteration); ``if``/``while``/``try`` are."""
    if isinstance(stmt, ast.If):
        return [(stmt.body, 1), (stmt.orelse, 1)]
    if isinstance(stmt, ast.While):
        return [(stmt.body, 1), (stmt.orelse, 1)]
    if isinstance(stmt, ast.Try):
        blocks = [(stmt.body, 1), (stmt.orelse, 1), (stmt.finalbody, 0)]
        blocks.extend((h.body, 1) for h in stmt.handlers)
        return blocks
    if isinstance(stmt, (ast.For, ast.AsyncFor)):
        return [(stmt.body, 0), (stmt.orelse, 1)]
    if isinstance(stmt, (ast.With, ast.AsyncWith)):
        return [(stmt.body, 0)]
    return []


ALL_RULES = (TracePurity(), RetraceHazards(), ReplayDeterminism(),
             LockDiscipline(), ProtocolDrift(), PytreeStability())


def rules_by_id() -> dict:
    return {r.id: r for r in ALL_RULES}
