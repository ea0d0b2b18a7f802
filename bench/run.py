"""Run one cell of BENCHMARK.json once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``, built by
the model family it names, ``bench/models/<family>.py``) and a mix
(``bench/traffic/<traffic>.json``); its metrics are read by
``bench/metrics/<metric>.py``. Set-up (weights from the seed on the device,
the engine, the compiled step, ``warmup_steps`` engine steps of the fleet)
is ``setup_s``; then the fleet is measured for ``--seconds``. With
``--trace 1`` a further ``trace_steps`` steps run under the profiler and the
per-layer metrics are reported instead of the end-to-end ones. Then the
engine is freed, and the served tokens and the control plane's recorded
decisions are checked against the plain references (``bench/check.py``). The last line of standard output is one
JSON object; without a TPU, or with fewer chips than the cell needs, the run
exits 2 and prints none.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
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


class NoChip(RuntimeError):
    pass


def load_cell(workload: str, bench_file: Path = ROOT / "BENCHMARK.json"):
    """(cell entry, model dict, mix dict, end-to-end metric names,
    per-layer metric names) for one workload of BENCHMARK.json."""
    spec = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    model = json.loads((ROOT / conf["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())

    def mine(ms):
        return [x["name"] for x in ms
                if workload in x.get("workloads", [workload])]
    return cell, model, mix, mine(spec["end_to_end"]), mine(spec["per_layer"])


def build_engine(m: dict, mix: dict, seed: int, **kw):
    """The engine for configuration ``m`` under mix ``mix``: its config,
    weights (made from ``seed`` on the device) and engine settings come
    from the configuration's family (``bench/models``)."""
    import jax.numpy as jnp
    from bench.models import family
    from repro.serving.engine import Engine, EngineConfig
    fam = family(m)
    cfg = fam.model_config(m)
    params = fam.program_params(m, seed, jnp.dtype(cfg.dtype))
    return Engine(cfg, params, perf=fam.perf(m),
                  ecfg=EngineConfig(**mix["engine"]), seed=seed, **kw)


class Spans:
    """Host spans around the engine's layers, summed over the window, and
    the per-step inputs the engine passed to its jitted step."""

    def __init__(self, trace: bool):
        import jax
        self._ann = jax.profiler.TraceAnnotation if trace else None
        self.on = False
        self.n_steps = 0
        self.total = {"step": 0.0, "jit_call": 0.0, "daemon": 0.0}
        self.steps = []          # (lengths, granted) per window step

    def span(self, name):
        import contextlib
        return (self._ann(f"bench.{name}") if self._ann
                else contextlib.nullcontext())

    def add(self, name, dt):
        if self.on:
            self.total[name] += dt


def instrument(eng, fleet, spans: Spans, control: list) -> None:
    """Wrap the engine's jitted step and daemon on the instance: the jitted
    call's span ends when its outputs are ready, which stamps the tokens it
    produced; each step's charge requests and grants are appended to
    ``control`` (read once the run is over)."""
    import jax
    import numpy as np
    step, daemon = eng._step, eng._daemon

    def jit_call(*a, **kw):
        with spans.span("jit_call"):
            t0 = time.perf_counter()
            out = jax.block_until_ready(step(*a, **kw))
            t1 = time.perf_counter()
        fleet.clock.t_ready = t1
        spans.add("jit_call", t1 - t0)
        control.append(("step", a[8], a[5], a[6], out[3]))
        if spans.on:
            spans.steps.append((np.asarray(a[4]), np.asarray(out[3])))
        return out

    def daemon_call():
        with spans.span("daemon"):
            t0 = time.perf_counter()
            daemon()
        spans.add("daemon", time.perf_counter() - t0)

    eng._step = jit_call
    eng._daemon = daemon_call


def warm_lifecycle(eng) -> None:
    """Run every lifecycle path the engine's daemon and step can take
    (session and tool-call domains, charge, uncharge, feedback, freeze,
    thaw, slot offload and recycle) once on a throwaway domain and slot,
    so that the eager programs they dispatch are compiled in set-up and
    not inside the window."""
    from repro.core import domains as D
    from repro.core.cgroup import DomainSpec
    from repro.core.intent import Hint
    cg, caches = eng.cg, eng.caches
    sess = "/bench-warm/s"
    cg.mkdir("/bench-warm")
    for prio, low in ((D.LOW, 0), (D.HIGH, eng.ecfg.pool_pages)):
        cg.mkdir(sess, DomainSpec(priority=prio, low=low, high=D.UNLIMITED))
        lease = cg.intent.declare("tool", Hint.LOW, parent=sess,
                                  priority=prio, high=4)
        h = cg.handle(lease.path)
        cg.charge_unchecked(h, 2)
        cg.intent.feedback(sess, "throttled", peak=2,
                           limit=int(cg.read(cg.path_of(h), "memory.high")))
        cg.uncharge(h, 1)
        lease.close()
        cg.uncharge(sess, 1)
        cg.freeze(sess)
        cg.thaw(sess)
        cg.charge_unchecked(sess, 1)
        cg.uncharge(sess, 1)
        cg.rmdir(sess, transfer_residual=False)
    cg.rmdir("/bench-warm", transfer_residual=False)
    cg.flush()
    slot = caches.alloc_slot()
    caches.freeze_slot("bench-warm", slot, pages=0)
    caches.free_slot(caches.thaw_slot("bench-warm")[0])
    import jax
    jax.block_until_ready(caches.state)


def fleet_step(fleet, spans: Spans) -> None:
    with spans.span("step"):
        t0 = time.perf_counter()
        fleet.eng.step()
        spans.add("step", time.perf_counter() - t0)
    with spans.span("fleet"):
        fleet.step_after()
    if spans.on:
        spans.n_steps += 1


class CompileCounter:
    """Counts lowerings to MLIR (each is a compilation or a persistent
    cache read), the persistent cache's hits and misses, and the seconds
    spent compiling or loading compiled programs."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.lowerings, self.compile_s, self.hits, self.misses = 0, 0.0, 0, 0
        self._lower = dispatch.JAXPR_TO_MLIR_MODULE_EVENT
        self._compile = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event == self._lower:
            self.lowerings += 1
        elif event == self._compile:
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def read_metrics(names, rec) -> dict:
    out = {}
    for name in names:
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        v = mod.read(rec)
        if v is not None:
            out[name] = {"value": v, "unit": mod.UNIT}
    return out


@dataclasses.dataclass
class Record:
    """What a run measured, for the metric readers."""
    model: dict
    mix: dict
    chips: int
    peak: dict
    setup_s: float
    window: tuple               # (start, end) on the host clock
    sessions: list
    spans: Spans
    trace: dict                 # xplane reduction; empty without --trace
    traced_steps: list          # (lengths, granted) of the traced steps
    open_waits: list            # (priority, "gap" | "turn", s) at the close


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             loaded=None, require_tpu: bool = True, controls=()) -> dict:
    """One run of a cell; the result line as a dict. ``controls`` names
    lower-precision controls (``bench/references``) whose widest gaps are
    read beside the program's, under ``readings`` (calibration only)."""
    import jax
    import jax.numpy as jnp
    from bench import check
    from bench.fleet import Fleet
    from bench.peaks import peaks

    cell, m, mix, e2e, per_layer = loaded or load_cell(workload)
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell["chips"]):
        raise NoChip(f"cell {workload} needs {cell['chips']} TPU chip(s); "
                     f"JAX found {len(devices)} {devices[0].platform!r} "
                     f"device(s) ({devices[0].device_kind})")
    if require_tpu:
        jax.config.update("jax_compilation_cache_dir",
                          os.environ.get("JAX_COMPILATION_CACHE_DIR")
                          or str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()
    peak = peaks(devices[0].device_kind) if require_tpu else {}
    t_w = time.perf_counter()
    eng = build_engine(m, mix, seed)
    dtype = jnp.dtype(eng.cfg.dtype)
    fleet = Fleet(eng, mix, seed, eng.cfg.padded_vocab)
    spans = Spans(trace)
    control = check.record_control(eng)
    instrument(eng, fleet, spans, control)
    warm_lifecycle(eng)
    fleet.start()
    for _ in range(mix["warmup_steps"]):
        fleet_step(fleet, spans)
    setup_s = time.perf_counter() - T0
    log(f"set-up {setup_s!r} s (weights, engine and warm-up "
        f"{time.perf_counter() - t_w!r} s; {counter.lowerings} lowerings, "
        f"compile cache {counter.hits} "
        f"hits / {counter.misses} misses, compiling or loading "
        f"{counter.compile_s!r} s)")

    lowered = counter.lowerings
    spans.on = True
    with spans.span("window"):
        start = time.perf_counter()
        while time.perf_counter() - start < seconds:
            fleet_step(fleet, spans)
        end = time.perf_counter()
    spans.on = False
    open_waits = fleet.open_waits(end)
    window_compiles = counter.lowerings - lowered
    log(f"window {end - start!r} s, {spans.n_steps} steps, compilations in "
        f"the window: {window_compiles}")

    red, traced = {}, []
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        n0 = len(spans.steps)
        spans.on = True
        jax.profiler.start_trace(tdir)
        with spans.span("window"):
            for _ in range(mix["trace_steps"]):
                fleet_step(fleet, spans)
        jax.profiler.stop_trace()
        spans.on = False
        traced = spans.steps[n0:]
        spans.steps = spans.steps[:n0]
        from bench import xplane
        path = sorted(glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True))[-1]
        red = xplane.reduce(*xplane.load(path))
        shutil.rmtree(tdir, ignore_errors=True)

    device = device_info(devices[:cell["chips"]] if require_tpu else devices[:1])
    em = eng.report()
    log("engine: " + json.dumps({k: em[k] for k in (
        "steps", "completed", "evicted", "throttle_triggers", "freezes",
        "thaws", "feedbacks", "overshoot_pages", "peak_pool_pages",
        "nonfinite_logit_steps")}))
    sessions = fleet.sessions()
    ecfg = eng.ecfg
    s_max = ecfg.s_max
    fleet.eng = None
    del eng
    gc.collect()

    rec = Record(model=m, mix=mix, chips=cell["chips"], peak=peak,
                 setup_s=setup_s, window=(start, end), sessions=sessions,
                 spans=spans, trace=red, traced_steps=traced,
                 open_waits=open_waits)
    metrics = read_metrics(per_layer if trace else e2e, rec)

    t_c = time.perf_counter()
    picked = check.sample_sessions(sessions, seed, mix["check"]["max_sessions"],
                                   mix["check"]["min_served_tokens"])
    checks = check.guarantees(fleet, em, s_max)
    checks["window_compilations"] = (window_compiles, 0)
    ctl_checks, ctl = check.control_plane(control, fleet, ecfg)
    checks.update(ctl_checks)
    del control
    if ctl:
        log("control plane against the reference: " + json.dumps(ctl))
    if picked:
        gaps = check.logit_gaps(m, seed, picked, dtype, s_max, controls)
        readings = {k: float(v.max()) for k, v in gaps.items()}
        checks["logit_gap"] = (readings["program"],
                               m["check"]["logit_gap_limit"])
        served = sum(len(s.served) for s in picked)
    else:
        checks["logit_gap"] = (None, m["check"]["logit_gap_limit"])
        served = 0
    correct = check.verdict(checks)
    log(f"reference over {len(picked)} sessions, {served} served tokens, "
        f"{time.perf_counter() - t_c!r} s")
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()

    attempted = fleet.submitted
    failed = sum(s.state.value == "evicted" for s in sessions)
    if trace:
        device["busy_s"] = red.get("busy_s", 0.0)
        device["window_s"] = red.get("window_s", 0.0)
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace and red:
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    if controls and picked:
        out["readings"] = readings
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
