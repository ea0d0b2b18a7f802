"""Compile each cell's jitted engine step, and the reference's layer, for a
described TPU v5e (no chip attached) and print what they need of its HBM.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 bench/compile_check.py [cell ...]

A compile that passes here is not a chip run: it says the programs are
accepted by the chip's compiler and fit its memory one at a time.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

GIB = 2 ** 30


def used(compiled) -> float:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / GIB


def check_cell(name: str, topo) -> None:
    import types

    import numpy as np
    from jax.sharding import (AxisType, Mesh, NamedSharding,
                              PartitionSpec as P, SingleDeviceSharding)
    from repro.core import controller as C
    from repro.core.sharded import ShardedDeviceView, _stacked_state
    from repro.serving.engine import _slot_sharding
    from repro import compat
    from repro.core.cgroup import AgentCgroup, DeviceTableBackend
    from repro.models import model as M
    from repro.models.schema import tree_map_schema
    from repro.serving.engine import EngineConfig, _make_step_fn

    from bench import check, run
    from bench.models import family

    cell, m, mix, _, _ = run.load_cell(name)
    fam = family(m)
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype, sharding=one):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=sharding)
    cfg = fam.model_config(m)
    ecfg = EngineConfig(**mix["engine"])
    compat.on_tpu = lambda: True
    n_domains = 4 * ecfg.max_slots + 8
    weights = cache = one
    if ecfg.backend == "sharded":
        # the view reads only these backend fields; the real backend
        # would place its state on the mesh, which described devices
        # cannot hold
        mesh = Mesh(np.array(topo.devices[:ecfg.n_shards]), ("shard",),
                    axis_types=(AxisType.Auto,))
        progs = C.as_programs(None)
        view = ShardedDeviceView(types.SimpleNamespace(
            cfg=ecfg.ctrl, mesh=mesh, n_shards=ecfg.n_shards,
            per_shard_domains=n_domains, progs=progs, prog=progs[0]))
        weights, cache = NamedSharding(mesh, P()), _slot_sharding(mesh)
        ctrl = jax.tree.map(
            lambda x: sds(x.shape, x.dtype, NamedSharding(mesh, P("shard"))),
            jax.eval_shape(lambda: _stacked_state(
                ecfg.pool_pages, ecfg.n_shards, n_domains)))
    else:
        view = AgentCgroup(DeviceTableBackend(
            ecfg.pool_pages, n_domains=n_domains)).device_view()
        ctrl = jax.tree.map(lambda x: sds(x.shape, x.dtype), view.state)
    step = _make_step_fn(cfg, fam.perf(m), ecfg, view)
    B = ecfg.max_slots
    params = tree_map_schema(
        lambda l: sds(l.shape, l.dtype or cfg.dtype, weights),
        M.param_schema(cfg))
    state = tree_map_schema(
        lambda l: sds(l.shape, l.dtype or cfg.dtype, cache),
        M.decode_state_schema(cfg, B, ecfg.s_max))
    slots = lambda dt: sds((B,), dt, weights)  # noqa: E731
    c = step.lower(params, state, ctrl, slots("int32"), slots("int32"),
                   slots("int32"), slots("int32"), slots("bool"), 0,
                   sds((2,), "uint32", weights), mode="inkernel").compile()
    n_kernel = sum("tpu_custom_call" in ln and "flash_decode" in ln
                   for ln in c.as_text().splitlines())
    print(f"{name}: engine step {used(c)!r} GiB per chip, flash_decode calls "
          f"{n_kernel}", flush=True)
    ref = check.reference_module(m)
    x = fam.dims(m)
    S = -(-ecfg.s_max // ref.Q_BLOCK) * ref.Q_BLOCK
    w = {k: sds(shp(x), "float32") for k, (shp, _) in fam.LAYER_LEAVES.items()}
    with jax.default_matmul_precision("highest"):
        c = jax.jit(lambda h, w: ref.layer(m, h, w, ref._ident)).lower(
            sds((S, x["d"]), "float32"), w).compile()
    print(f"{name}: reference layer at {S} positions {used(c)!r} GiB",
          flush=True)


def main() -> int:
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    import json
    spec = json.loads((Path(__file__).resolve().parent.parent
                       / "BENCHMARK.json").read_text())
    for name in sys.argv[1:] or [w["name"] for w in spec["workloads"]]:
        check_cell(name, topo)
    return 0


if __name__ == "__main__":
    sys.exit(main())
