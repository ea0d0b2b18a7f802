"""Compile the served path for a described TPU v5e (no chip attached).

The TPU compiler refuses what the Pallas interpreter accepts (block
shapes Mosaic cannot tile, primitives it cannot lower, programs that do
not fit HBM), so these compiles guard the chip path on the CPU rig:
``flash_decode`` and ``paged_flash_decode`` at llama3.2-3b widths in
bf16, and the serving engine's jitted step at full width, on one chip
and on a four-chip control mesh (the sharded backend).

The topology is described only inside a module fixture: loading the TPU
compiler takes a process-wide lock, so it must never happen while a
module is imported.
"""
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import compat
from repro.configs import get_config
from repro.core import controller as C
from repro.core.cgroup import AgentCgroup, DeviceTableBackend
from repro.core.sharded import ShardedDeviceView, _stacked_state
from repro.kernels.decode_attention import (decode_attention_pallas,
                                            paged_decode_attention_pallas)
from repro.models import model as M
from repro.models.schema import tree_map_schema
from repro.perf import DEFAULT_PERF
from repro.serving.engine import EngineConfig, _make_step_fn, _slot_sharding

# llama3.2-3b serving widths: 8 slots x 2048 tokens, 24 q / 8 kv heads
B, S, H, HKV, D = 8, 2048, 24, 8, 128
PAGE, N_PAGES = 16, 1024
HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _kernel_calls(hlo: str, name: str) -> int:
    return sum(1 for line in hlo.splitlines()
               if "tpu_custom_call" in line and name in line)


def test_flash_decode_compiles_for_v5e(one_chip):
    q = _sds((B, H, D), "bfloat16", one_chip)
    kv = _sds((B, S, HKV, D), "bfloat16", one_chip)
    lengths = _sds((B,), "int32", one_chip)
    compiled = jax.jit(decode_attention_pallas).lower(
        q, kv, kv, lengths).compile()
    assert _kernel_calls(compiled.as_text(), "flash_decode") == 1


def test_paged_flash_decode_compiles_for_v5e(one_chip):
    q = _sds((B, H, D), "bfloat16", one_chip)
    pages = _sds((N_PAGES, PAGE, HKV, D), "bfloat16", one_chip)
    table = _sds((B, S // PAGE), "int32", one_chip)
    lengths = _sds((B,), "int32", one_chip)
    compiled = jax.jit(paged_decode_attention_pallas).lower(
        q, pages, pages, table, lengths).compile()
    assert _kernel_calls(compiled.as_text(), "paged_flash_decode") == 1


def _compile_step(view, ecfg, *, weights, cache, ctrl):
    """Lower + compile the engine's jitted step at full llama3.2-3b
    width from shapes placed by the given shardings."""
    cfg = get_config("llama3.2-3b")
    step_fn = _make_step_fn(cfg, DEFAULT_PERF, ecfg, view)

    def shapes(schema, sharding):
        return tree_map_schema(
            lambda l: _sds(l.shape, l.dtype or cfg.dtype, sharding), schema)

    params = shapes(M.param_schema(cfg), weights)
    dstate = shapes(M.decode_state_schema(cfg, B, S), cache)
    slots = lambda dt: _sds((B,), dt, weights)            # noqa: E731
    compiled = step_fn.lower(
        params, dstate, ctrl, slots("int32"), slots("int32"),
        slots("int32"), slots("int32"), slots("bool"), 0,
        _sds((2,), "uint32", weights), mode="inkernel").compile()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    return compiled.as_text(), used


def test_engine_step_compiles_at_full_width(one_chip, monkeypatch):
    """The engine's jitted step (in-step enforcement + decode_step) at
    the published llama3.2-3b widths in bf16: the decode attention is
    the compiled Pallas kernel, and the program fits one chip's HBM."""
    # this process sees only the CPU; steer kernel dispatch as it
    # resolves on a TPU (compiled Pallas, no interpreter)
    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    ecfg = EngineConfig(max_slots=B, s_max=S, pool_pages=N_PAGES,
                        page_tokens=PAGE)
    view = AgentCgroup(DeviceTableBackend(
        N_PAGES, n_domains=4 * B + 8)).device_view()
    ctrl = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip),
                        view.state)
    hlo, used = _compile_step(view, ecfg, weights=one_chip, cache=one_chip,
                              ctrl=ctrl)
    assert _kernel_calls(hlo, "flash_decode") >= 1
    assert used < HBM_BYTES, used


def test_sharded_engine_step_compiles_on_four_chips(topo, monkeypatch):
    """The same step on the sharded backend's four-chip control mesh:
    the Pallas decode kernel must sit inside the engine's per-slot
    ``shard_map`` (Mosaic kernels are never partitioned automatically),
    with the weights replicated and the cache split by slot."""
    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices[:4]), ("shard",),
                axis_types=(AxisType.Auto,))
    n_domains = 4 * B + 8
    progs = C.as_programs(None)
    # the view reads only these backend fields; the real backend would
    # place its state on the mesh, which described devices cannot hold
    backend = types.SimpleNamespace(
        cfg=C.ControllerConfig(), mesh=mesh, n_shards=4,
        per_shard_domains=n_domains, progs=progs, prog=progs[0])
    ecfg = EngineConfig(max_slots=B, s_max=S, pool_pages=N_PAGES,
                        page_tokens=PAGE, backend="sharded", n_shards=4)
    by_tenant = NamedSharding(mesh, P("shard"))
    ctrl = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, by_tenant),
        jax.eval_shape(lambda: _stacked_state(N_PAGES, 4, n_domains)))
    hlo, used = _compile_step(ShardedDeviceView(backend), ecfg,
                              weights=NamedSharding(mesh, P()),
                              cache=_slot_sharding(mesh), ctrl=ctrl)
    assert _kernel_calls(hlo, "flash_decode") >= 1
    assert used < HBM_BYTES, used
