"""Platform resolution under the installed JAX: the direct mesh /
shard_map APIs the call sites use, and the no-hidden-fallback rules of
``repro.compat`` (strict Pallas interpret resolution, backend errors
propagate, peak table keyed by device kind)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro import compat
from repro.kernels import ops


def test_make_auto_mesh_resolves():
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    assert mesh.axis_names == ("data",)
    sh = NamedSharding(mesh, P("data"))
    y = jax.device_put(jnp.zeros((4, 2)), sh)
    assert y.shape == (4, 2)


def test_shard_map_resolves_and_runs():
    mesh = jax.make_mesh((1,), ("s",), axis_types=(AxisType.Auto,))
    fn = jax.shard_map(lambda x: x + 1, mesh=mesh, in_specs=(P("s"),),
                       out_specs=P("s"), check_vma=False)
    out = fn(jnp.zeros((1, 3)))
    np.testing.assert_allclose(np.asarray(out), np.ones((1, 3)))


def test_pallas_interpret_resolution(monkeypatch):
    """Off the TPU the interpreter runs only under the override; without
    it a Pallas request is an error, never a quiet swap."""
    assert not compat.on_tpu()
    monkeypatch.setenv(compat.INTERPRET_ENV, "1")
    assert compat.pallas_interpret() is True
    monkeypatch.setenv(compat.INTERPRET_ENV, "0")
    with pytest.raises(RuntimeError, match=compat.INTERPRET_ENV):
        compat.pallas_interpret()


def test_explicit_pallas_impl_off_tpu_raises(monkeypatch):
    """``impl="pallas"`` is honoured or refused: off the TPU without the
    override it raises instead of running the blockwise path."""
    monkeypatch.delenv(compat.INTERPRET_ENV, raising=False)
    q = jnp.zeros((1, 2, 128), jnp.float32)
    kv = jnp.zeros((1, 16, 1, 128), jnp.float32)
    lengths = jnp.ones((1,), jnp.int32)
    with pytest.raises(RuntimeError, match="not a TPU"):
        ops.decode_attention(q, kv, kv, lengths, impl="pallas")
    # the unpinned default still resolves to the blockwise path here
    out = ops.decode_attention(q, kv, kv, lengths)
    assert out.shape == (1, 2, 128)


def test_on_tpu_propagates_backend_errors(monkeypatch):
    def broken():
        raise RuntimeError("backend failed to initialise")
    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="failed to initialise"):
        compat.on_tpu()


def test_peak_table_keyed_by_device_kind():
    from repro.launch.mesh import PRODUCTION_KIND, peaks
    row = peaks("TPU v5 lite")
    assert row["flops_bf16"] == 197e12 and row["hbm_bw"] == 819e9
    assert peaks(PRODUCTION_KIND) is row
    with pytest.raises(KeyError, match="cpu"):
        peaks("cpu")
