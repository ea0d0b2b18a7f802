"""The comparison that decides ``correct``.

Served model: once the window has closed and the program's state is freed,
a sample drawn from the seed of the sessions the run finished, the longest
among them, is run through the plain reference (``bench/references``) over
each session's own context. For every reply token the program served
(greedy), the gap by which the reference's logit of that token lies below the
reference's best is read; the widest gap is held to the configuration's
limit. Beside it the guarantees, which no speed change may trade: no page
over the pool (counted by the harness from the sessions' own lengths), no
non-finite logits in any step, and no session lost (each one ended DONE
after all its phases or at the cache's end, EVICTED, or is still in flight).

Control plane: every lifecycle operation the engine issued on its control
plane, every in-step batch of charge requests with the program's grants,
and the state the engine's daemon read at each step boundary, from the
engine's creation to the end of the run, are recorded and replayed through
the plain memcg reference (``bench/references/memcg.py``); each grant, and
each domain's usage, throttle window and freeze flag, has to be the
reference's, and each session domain has to carry the priority and
``memory.low`` the mix states for its agent.
"""
from __future__ import annotations

import importlib

import jax.numpy as jnp
import numpy as np

from repro.core.cgroup import DomainSpec
from repro.serving.session import SState


def sample_sessions(sessions: list, seed: int, max_sessions: int,
                    min_tokens: int) -> list:
    """The longest finished session, then others drawn from the seed
    (finished ones first) until ``min_tokens`` reply tokens or
    ``max_sessions`` sessions."""
    rng = np.random.default_rng([seed, 7])
    done = [s for s in sessions if s.state is SState.DONE and s.served]
    live = [s for s in sessions if s.state is not SState.DONE and s.served]
    done.sort(key=lambda s: (-len(s.ctx), s.sid))
    pick = done[:1]
    rest = ([done[1:][i] for i in rng.permutation(len(done) - 1)]
            if len(done) > 1 else [])
    rest += [live[i] for i in rng.permutation(len(live))]
    for s in rest:
        if (len(pick) >= max_sessions
                or sum(len(p.served) for p in pick) >= min_tokens):
            break
        pick.append(s)
    return pick


def reference_module(m: dict):
    return importlib.import_module(f"bench.references.{m['reference']}")


def logit_gaps(m: dict, seed: int, picked: list, served_dtype, pad_to: int,
               controls=()) -> dict:
    """Per reply token, the reference's best logit minus its logit of the
    served token; with ``controls``, the same for the token each
    lower-precision control puts first at the same positions."""
    ref = reference_module(m)
    seqs = [(np.asarray(s.ctx, np.int32),
             np.asarray([p for p, _ in s.served], np.int32)) for s in picked]
    toks = jnp.asarray(np.concatenate(
        [np.asarray([t for _, t in s.served], np.int32) for s in picked]))
    lg = ref.logits_at(m, seed, seqs, served_dtype, pad_to=pad_to)
    best = lg.max(-1)
    out = {"program": np.asarray(best - jnp.take_along_axis(
        lg, toks[:, None], -1)[:, 0])}
    for name in controls:
        cl = ref.logits_at(m, seed, seqs, served_dtype,
                           quant=ref.CONTROLS[name], pad_to=pad_to)
        ct = cl.argmax(-1)
        del cl
        out[name] = np.asarray(best - jnp.take_along_axis(
            lg, ct[:, None], -1)[:, 0])
    return out


def guarantees(fleet, eng_metrics, s_max: int) -> dict:
    lost = 0
    for s in fleet.sessions():
        if s.state is SState.DONE:
            lost += not (s.finished or s.length >= s_max - 1)
        elif s.state not in (SState.EVICTED, SState.RUNNING, SState.FROZEN,
                             SState.WAITING):
            lost += 1
    return {"pool_overshoot_pages": (max(fleet.pool_overshoot, 0), 0),
            "nonfinite_logit_steps": (eng_metrics["nonfinite_logit_steps"], 0),
            "lost_sessions": (lost, 0)}


def verdict(checks: dict) -> bool:
    """Every number within its limit; a number that could not be read
    (no session to compare) or a limit not yet set fails."""
    return all(v is not None and lim is not None and v <= lim
               for v, lim in checks.values())


def record_control(eng) -> list:
    """Wrap the engine's control-plane facade on the instance so that every
    lifecycle operation and every step-boundary read is appended, in order,
    to the returned list (``run.instrument`` appends the in-step charges)."""
    ops, cg = [], eng.cg
    real = {n: getattr(cg, n) for n in (
        "mkdir", "rmdir", "uncharge", "charge_unchecked", "freeze", "thaw",
        "write", "snapshot")}

    def mkdir(path, spec=None, **kw):
        handle = real["mkdir"](path, spec, **kw)
        sp = spec if spec is not None else DomainSpec(**kw)
        ops.append(("mkdir", path, handle, sp.high, sp.max, sp.low,
                    sp.priority))
        return handle

    def rmdir(path, *, transfer_residual=True):
        out = real["rmdir"](path, transfer_residual=transfer_residual)
        ops.append(("rmdir", path, transfer_residual))
        return out

    def simple(name):
        def op(*args):
            out = real[name](*args)
            ops.append((name,) + args)
            return out
        return op

    def snapshot():
        snap = real["snapshot"]()
        ops.append(("boundary", snap["index"], snap["usage"],
                    snap["throttle_until"], snap["frozen"]))
        return snap

    cg.mkdir, cg.rmdir, cg.snapshot = mkdir, rmdir, snapshot
    for name in ("uncharge", "charge_unchecked", "freeze", "thaw", "write"):
        setattr(cg, name, simple(name))
    return ops


def control_plane(ops: list, fleet, ecfg) -> tuple:
    """(checks, counts) of the recorded control plane against the plain
    reference. The reference models the in-step enforcement on one
    device table; other modes and backends are not compared."""
    if ecfg.mode != "inkernel" or ecfg.backend != "device":
        return {}, {}
    ref = importlib.import_module("bench.references.memcg")
    counts = ref.replay(ops, fleet.capacity, fleet.spec_of)
    return ({"grant_mismatches": (counts["grants_differing"], 0),
             "domain_state_mismatches": (counts["state_differing"], 0)},
            counts)
