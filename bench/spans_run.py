"""Run one cell of BENCHMARK.json with the program's tracer on, and print
what its spans, counters and named scopes say.

A stopgap beside ``bench/run.py``: delete it once ``run.py`` builds the
engine with ``tracer=Tracer(on=trace)``, gives ``Record`` the tracer's
``program`` and the step's ``scopes``, and adds ``idle_program_spans``
to the result line's ``breakdown`` (``PERF.md`` section 7).

    python3 bench/spans_run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> [--tracer <0|1>] [--trace-steps <n>] [--keep <dir>]

Set-up, the window and the traced steps are those of ``bench/run.py``
(its functions, in the same order), with the engine's tracer
(``repro/spans.py``) on from the engine's creation. The last line of
standard output is one JSON object:

- ``metrics``: the readers of the program's spans and counters
  (``lifecycle_ms_per_step``, ``host_syncs_per_step``,
  ``queue_wait_p90_ms``) and ``host_ms_per_step``; with ``--trace 1``
  also ``enforce_device_share``, from the step module's traced device
  time by named scope (``bench/program_trace.py``);
- ``self_ms_per_step``: every span's own time (less its children's) per
  window step; ``n_per_step``, how many of each closed in a window step
  (the cost of one ``cg.mkdir`` is the ratio of the two); and
  ``spans_per_step``;
- with ``--trace 1``: ``scopes_s`` (the step's device seconds by scope),
  ``idle_program_spans`` (the traced window's device idle time by the
  innermost program span over it), ``idle_in_steps_s`` (idle inside
  ``bench.step`` spans, and the part of it under a phase span), and
  ``transfers_per_step`` beside ``host_syncs_per_traced_step``.

``--tracer 0`` leaves the tracer off (the ``host_ms_per_step`` of the two
settings is the tracer's cost). ``--trace-steps`` traces that many steps
instead of the mix's ``trace_steps``. ``--keep <dir>`` keeps the profiler
trace and writes the compiled step's HLO text there. The host work of
the window is that of ``bench/run.py``: the facade is wrapped by
``check.record_control`` there as here. ``checks`` holds the control
plane against its reference and the guarantees; the served tokens are
not checked (``bench/run.py`` does that).
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run as R  # noqa: E402

PROGRAM_METRICS = ("lifecycle_ms_per_step", "host_syncs_per_step",
                   "queue_wait_p90_ms", "host_ms_per_step")
SCOPE_METRICS = ("enforce_device_share",)


@dataclasses.dataclass
class ProgramRecord(R.Record):
    program: dict = None        # the tracer's drain
    scopes: dict = None         # step device seconds by named scope


def step_hlo(jitted, args, kw) -> str:
    """The compiled step's HLO text, for arguments of the shapes of
    ``args`` (the persistent compile cache serves it)."""
    import jax

    def spec(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        return x
    return jitted.lower(*jax.tree.map(spec, args), **kw).compile().as_text()


def run_program_cell(workload: str, seed: int, seconds: float, trace: bool,
                     *, tracer: bool = True, loaded=None,
                     require_tpu: bool = True, keep=None,
                     trace_steps=None) -> dict:
    import jax
    from bench import check
    from bench import program_trace as PT
    from bench.fleet import Fleet
    from repro.spans import Tracer

    cell, m, mix, _, _ = loaded or R.load_cell(workload)
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell["chips"]):
        raise R.NoChip(f"cell {workload} needs {cell['chips']} TPU chip(s); "
                       f"JAX found {len(devices)} {devices[0].platform!r} "
                       f"device(s)")
    if require_tpu:
        jax.config.update("jax_compilation_cache_dir",
                          os.environ.get("JAX_COMPILATION_CACHE_DIR")
                          or str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = R.CompileCounter()
    tr = Tracer(on=tracer)
    eng = R.build_engine(m, mix, seed, tracer=tr)
    jitted = eng._step
    fleet = Fleet(eng, mix, seed, eng.cfg.padded_vocab)
    spans = R.Spans(trace)
    control = check.record_control(eng)
    R.instrument(eng, fleet, spans, control)
    last = {}
    timed = eng._step

    def keep_args(*a, **kw):
        last["call"] = (a, kw)
        return timed(*a, **kw)
    eng._step = keep_args
    R.warm_lifecycle(eng)
    fleet.start()
    for _ in range(mix["warmup_steps"]):
        R.fleet_step(fleet, spans)
    setup_s = time.perf_counter() - T0
    lowered = counter.lowerings
    spans.on = True
    with spans.span("window"):
        start = time.perf_counter()
        while time.perf_counter() - start < seconds:
            R.fleet_step(fleet, spans)
        end = time.perf_counter()
    spans.on = False
    window_compiles = counter.lowerings - lowered
    R.log(f"window {end - start!r} s, {spans.n_steps} steps, compilations "
          f"in the window: {window_compiles}")

    out = {"tracer": tracer, "setup_s": setup_s, "steps": spans.n_steps,
           "window_compilations": window_compiles}
    scopes = None
    if trace:
        tdir = keep or tempfile.mkdtemp(prefix="bench_trace_")
        spans.on = True
        jax.profiler.start_trace(tdir)
        with spans.span("window"):
            t_lo = time.perf_counter()
            n = trace_steps or mix["trace_steps"]
            for _ in range(n):
                R.fleet_step(fleet, spans)
            t_hi = time.perf_counter()
        jax.profiler.stop_trace()
        spans.on = False
        a, kw = last["call"]
        hlo = step_hlo(jitted, a, kw)
        path = sorted(glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True))[-1]
        ops, modules, hspans, transfers = PT.load(path)
        scopes = PT.step_scope_seconds(ops, modules, PT.hlo_scopes(hlo),
                                       hspans)
        idle_in, idle_under = PT.idle_under_phases(ops, hspans)
        out.update(
            scopes_s=scopes,
            idle_program_spans=PT.idle_program_spans(ops, hspans),
            idle_in_steps_s=[idle_in, idle_under],
            transfers_per_step=PT.transfers_in_window(transfers, hspans) / n)
        if keep:
            (Path(keep) / "step.hlo.txt").write_text(hlo)
        else:
            shutil.rmtree(tdir, ignore_errors=True)
    prog = tr.drain()
    sessions = fleet.sessions()
    checks = check.guarantees(fleet, eng.report(), eng.ecfg.s_max)
    checks["window_compilations"] = (window_compiles, 0)
    ecfg = eng.ecfg
    fleet.eng = None
    del eng
    checks.update(check.control_plane(control, fleet, ecfg)[0])
    out["checks_hold"] = check.verdict(checks)
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    rec = ProgramRecord(
        model=m, mix=mix, chips=cell["chips"], peak={}, setup_s=setup_s,
        window=(start, end), sessions=sessions, spans=spans, trace={},
        traced_steps=[], open_waits=[], program=prog, scopes=scopes)
    names = PROGRAM_METRICS + (SCOPE_METRICS if trace else ())
    out["metrics"] = R.read_metrics(names, rec)
    if tracer:
        from bench.metrics._program import self_ms, window_steps
        steps = window_steps(rec, prog)
        out["self_ms_per_step"] = {
            k: v / len(steps) for k, v in sorted(self_ms(prog, steps).items())}
        closed = collections.Counter(
            s.name for s in prog["spans"] if s.step in steps)
        out["n_per_step"] = {k: v / len(steps)
                             for k, v in sorted(closed.items())}
        out["spans_per_step"] = sum(closed.values()) / len(steps)
        if trace:
            traced = {s.step for s in prog["spans"] if s.name == "step"
                      and s.t0_ns >= t_lo * 1e9 and s.t1_ns <= t_hi * 1e9}
            out["host_syncs_per_traced_step"] = sum(
                c.get("host_sync", 0) for st, c in prog["counters"]
                if st in traced) / max(len(traced), 1)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tracer", type=int, choices=(0, 1), default=1)
    ap.add_argument("--trace-steps", type=int, default=None)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    try:
        out = run_program_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), tracer=bool(args.tracer),
                               keep=args.keep, trace_steps=args.trace_steps)
    except R.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
