"""MoE dispatch equivalence: the three implementations (dense masked,
capacity-gather, shard_map all-to-all) must agree numerically when
capacity is generous (no drops) — dense is the oracle.  The a2a test
runs on a real (2,4) device mesh in a subprocess."""
import os
import subprocess
import sys

import dataclasses
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.models import moe as moe_mod
from repro.models.schema import init_params
from repro.perf import DEFAULT_PERF, replace as perf_replace


def _setup():
    cfg = dataclasses.replace(reduced(get_config("jamba-v0.1-52b")),
                              dtype="float32")
    p = init_params(moe_mod.moe_schema(cfg), jax.random.PRNGKey(0),
                    cfg.dtype)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.float32)
    return cfg, p, x


def test_gather_matches_dense_no_drops():
    cfg, p, x = _setup()
    y_dense, aux_d = moe_mod.moe_forward(
        cfg, p, x, perf=perf_replace(DEFAULT_PERF, moe_impl="dense"))
    y_gather, aux_g = moe_mod.moe_forward(
        cfg, p, x, perf=perf_replace(DEFAULT_PERF, moe_impl="gather",
                                     capacity_factor=8.0))
    np.testing.assert_allclose(np.asarray(y_gather), np.asarray(y_dense),
                               atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(float(aux_g), float(aux_d), rtol=1e-5)


def test_gather_grads_match_dense():
    cfg, p, x = _setup()

    def loss(impl):
        def f(params):
            y, aux = moe_mod.moe_forward(
                cfg, params, x,
                perf=perf_replace(DEFAULT_PERF, moe_impl=impl,
                                  capacity_factor=8.0))
            return jnp.sum(y ** 2) + aux
        return jax.grad(f)(p)

    gd, gg = loss("dense"), loss("gather")
    for a, b in zip(jax.tree.leaves(gd), jax.tree.leaves(gg)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=5e-4, rtol=5e-3)


def test_a2a_matches_dense_multidevice():
    """a2a == dense on a (2,4) mesh (subprocess with 8 fake devices)."""
    code = r"""
import dataclasses, os
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, reduced
from repro.models import moe as moe_mod
from repro.models.schema import init_params, shardings
from repro.perf import DEFAULT_PERF, replace as perf_replace
from repro.sharding_ctx import activation_rules

from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
rules = {"tp": "model", "fsdp": "data", "ep": "model", "ep2": "data",
         "act_batch": "data", "act_seq": "model", "layers": None}
cfg = dataclasses.replace(reduced(get_config("jamba-v0.1-52b")),
                          dtype="float32")
p = init_params(moe_mod.moe_schema(cfg), jax.random.PRNGKey(0), cfg.dtype)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                      jnp.float32)
y_dense, aux_d = moe_mod.moe_forward(
    cfg, p, x, perf=perf_replace(DEFAULT_PERF, moe_impl="dense"))

sh = shardings(moe_mod.moe_schema(cfg), mesh, rules)
p_sh = jax.tree.map(jax.device_put, p, sh)
from jax.sharding import NamedSharding, PartitionSpec as P
x_sh = jax.device_put(x, NamedSharding(mesh, P("data", "model", None)))
perf = perf_replace(DEFAULT_PERF, moe_impl="a2a", capacity_factor=8.0)
with mesh:
    with activation_rules(rules, mesh=mesh):
        y_a2a, aux_a = jax.jit(
            lambda pp, xx: moe_mod.moe_forward(cfg, pp, xx, perf=perf))(
            p_sh, x_sh)
np.testing.assert_allclose(np.asarray(y_a2a), np.asarray(y_dense),
                           atol=2e-4, rtol=1e-3)
# aux differs slightly by construction: a2a averages SHARD-LOCAL
# load-balance statistics (f_e, P_e per device) while dense computes
# them globally — standard per-microbatch aux behaviour
np.testing.assert_allclose(float(aux_a), float(aux_d), rtol=0.15)
print("A2A OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0 and "A2A OK" in out.stdout, out.stderr[-3000:]
