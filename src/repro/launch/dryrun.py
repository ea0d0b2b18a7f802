import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# the production meshes are 512 fake host devices: pin this process and
# the cell subprocesses it starts (they inherit the environment) to the
# CPU, so none of them takes an attached TPU
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ both MUST precede any jax import: jax locks them on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each applicable cell this lowers the real step function — train_step
(forward+backward+AdamW), prefill_step, or serve_step (one token against
a seq_len KV cache) — against ShapeDtypeStruct inputs carrying the
production NamedShardings (no allocation), compiles it for the 256-chip
single-pod mesh and the 512-chip two-pod mesh, and records:

  * compiled.memory_analysis()  — proves the cell fits per-device HBM;
  * compiled.cost_analysis()    — XLA's per-device FLOPs/bytes (while
    bodies counted once — see analysis/hlo.py);
  * trip-count-corrected FLOPs / bytes / collective bytes from the
    optimized HLO text (analysis/hlo.analyze);
  * the three roofline terms + dominant bottleneck (analysis/roofline).

Usage:
  python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --out results/dryrun   # orchestrates
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.analysis import hlo as hlo_mod
from repro.analysis.roofline import roofline_from_costs
from repro.configs import SHAPES, cell_applicability, get_config, ARCH_IDS
from repro.launch.mesh import (POD_CHIPS, PRODUCTION_KIND,
                               make_production_mesh, peaks, rules_for)
from repro.models import model as M
from repro.models.schema import Leaf, shape_structs, tree_map_schema
from repro.perf import DEFAULT_PERF, PerfConfig
from repro.sharding_ctx import activation_rules
from repro.training.optimizer import OptConfig
from repro.training.train_step import make_train_step


def _opt_schema(param_sch):
    f32 = lambda l: Leaf(l.shape, l.spec, init="zeros", dtype="float32")
    return {"m": tree_map_schema(f32, param_sch),
            "v": tree_map_schema(f32, param_sch),
            "count": Leaf((), init="zeros", dtype="int32")}


# per-arch production perf defaults for TRAIN cells: the giant-MoE /
# MLA configs cannot afford remat-saving their head-expansion dots
# (120 GiB of stacked saved activations) and use deeper grad
# accumulation; everything else uses the standard dots policy.
TRAIN_PERF_OVERRIDES = {
    "deepseek-v2-236b": dict(remat="full", microbatches=8),
    "llama4-maverick-400b-a17b": dict(remat="full", microbatches=4),
    "jamba-v0.1-52b": dict(remat="full", microbatches=2),
    "pixtral-12b": dict(microbatches=4),
    "internlm2-20b": dict(microbatches=4),
    "phi3-medium-14b": dict(microbatches=4),
    "xlstm-350m": dict(remat="full"),
}


def build_cell(arch: str, shape_name: str, mesh, perf: PerfConfig):
    """Returns (fn, arg_structs) for one cell, or raises."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mode = "train" if shape.kind == "train" else "serve"
    rules = rules_for(mesh, mode=mode, shape=shape)
    psch = M.param_schema(cfg)
    params = shape_structs(psch, cfg.dtype, mesh, rules)
    batch_leaves = M.batch_spec_leaves(cfg, shape)
    batch = {k: shape_structs(l, cfg.dtype, mesh, rules)
             for k, l in batch_leaves.items()}

    if shape.kind == "train":
        opt = shape_structs(_opt_schema(psch), "float32", mesh, rules)
        if perf.microbatches == 1:
            # baseline: 2 microbatches (64k tokens/device at train_4k on
            # the single pod does not fit HBM without grad accumulation)
            ov = {"microbatches": 2, **TRAIN_PERF_OVERRIDES.get(arch, {})}
            perf = dataclasses.replace(perf, **ov)
        step_fn = make_train_step(cfg, perf, OptConfig())
        step = jax.ShapeDtypeStruct((), jnp.int32)
        # params/opt are donated (aliased to the outputs), as the real
        # training driver does — memory_analysis must reflect that
        return (step_fn, (params, opt, batch, step), rules, (0, 1))

    if shape.kind == "prefill":
        def prefill_step(params, batch):
            logits, _ = M.forward(cfg, params, batch, perf=perf)
            return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return prefill_step, (params, batch), rules, ()

    # decode: one new token against a seq_len cache (cache donated)
    ssch = M.decode_state_schema(cfg, shape.global_batch, shape.seq_len)
    state = shape_structs(ssch, cfg.dtype, mesh, rules)

    def serve_step(params, state, batch):
        return M.serve_step(cfg, params, state, batch["tokens"],
                            batch["lengths"], perf=perf)
    return serve_step, (params, state, batch), rules, (1,)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             perf: PerfConfig = DEFAULT_PERF) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = cell_applicability(cfg, shape)
    mesh_name = "multi" if multi_pod else "single"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "applicable": ok}
    if not ok:
        rec["skip_reason"] = reason
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    fn, args, rules, donate = build_cell(arch, shape_name, mesh, perf)
    with mesh:
        with activation_rules(rules, mesh=mesh):
            lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    txt = compiled.as_text()
    parsed = hlo_mod.analyze(txt, pod_size=POD_CHIPS)
    per_dev_bytes = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                     + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    rec.update({
        "n_chips": n_chips,
        "t_lower_s": round(t_lower, 2),
        "t_compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "per_device_bytes": per_dev_bytes,
            "fits_hbm": bool(per_dev_bytes
                             <= peaks(PRODUCTION_KIND)["hbm_bytes"]),
        },
        "cost_analysis": {"flops": ca.get("flops", 0.0),
                          "bytes": ca.get("bytes accessed", 0.0)},
        "hlo": parsed,
    })
    rec["roofline"] = roofline_from_costs(cfg, shape, parsed, n_chips=n_chips,
                                         device_kind=PRODUCTION_KIND)
    return rec


# --------------------------------------------------------------- CLI driver


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true",
                    help="run every cell in subprocesses")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--moe-impl", default=None, choices=["dense", "gather"])
    ap.add_argument("--perf-json", default=None,
                    help="JSON dict of PerfConfig overrides")
    args = ap.parse_args()

    perf = DEFAULT_PERF
    if args.moe_impl:
        perf = dataclasses.replace(perf, moe_impl=args.moe_impl)
    if args.perf_json:
        perf = dataclasses.replace(perf, **json.loads(args.perf_json))

    if args.all:
        return orchestrate(args, perf)

    assert args.arch and args.shape, "--arch/--shape required without --all"
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)
    status = 0
    for mp in meshes:
        name = f"{args.arch}__{args.shape}__{'multi' if mp else 'single'}"
        try:
            rec = run_cell(args.arch, args.shape, mp, perf)
        except Exception as e:
            rec = {"arch": args.arch, "shape": args.shape,
                   "mesh": "multi" if mp else "single", "applicable": True,
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
            status = 1
        path = os.path.join(args.out, f"{name}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        summary = (rec.get("skip_reason") or rec.get("error")
                   or f"ok compile={rec.get('t_compile_s')}s "
                      f"fits={rec.get('memory', {}).get('fits_hbm')}")
        print(f"[{name}] {summary}", flush=True)
    return status


def orchestrate(args, perf: PerfConfig) -> int:
    """Run every (arch x shape x mesh) cell, each in its own subprocess
    (isolates jit caches / memory), a few at a time."""
    os.makedirs(args.out, exist_ok=True)
    cells = []
    for arch in ARCH_IDS:
        for shape_name in SHAPES:
            ok, reason = cell_applicability(get_config(arch),
                                            SHAPES[shape_name])
            if not ok:
                for mesh in ("single", "multi"):
                    path = os.path.join(
                        args.out, f"{arch}__{shape_name}__{mesh}.json")
                    with open(path, "w") as f:
                        json.dump({"arch": arch, "shape": shape_name,
                                   "mesh": mesh, "applicable": False,
                                   "skip_reason": reason}, f, indent=1)
                print(f"[{arch}/{shape_name}] SKIP: {reason}", flush=True)
                continue
            cells.append((arch, shape_name))
    procs: list = []
    failures = 0

    def reap(block: bool):
        nonlocal failures
        done = []
        for p, name in procs:
            if p.poll() is not None or block:
                rc = p.wait()
                if rc:
                    failures += 1
                    print(f"[{name}] FAILED rc={rc}", flush=True)
                done.append((p, name))
        for d in done:
            procs.remove(d)

    for arch, shape_name in cells:
        while len(procs) >= args.jobs:
            reap(False)
            time.sleep(1.0)
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape_name,
               "--mesh", args.mesh, "--out", args.out]
        if args.moe_impl:
            cmd += ["--moe-impl", args.moe_impl]
        if args.perf_json:
            cmd += ["--perf-json", args.perf_json]
        p = subprocess.Popen(cmd)
        procs.append((p, f"{arch}/{shape_name}"))
    while procs:
        reap(False)
        time.sleep(1.0)
    print(f"dry-run complete; failures={failures}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
