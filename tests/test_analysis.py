"""HLO cost parser: validated against cost_analysis on scan-free graphs
and against analytic counts on scanned graphs (trip-count awareness)."""
import os
import subprocess
import sys

import numpy as np


def _run(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_parser_matches_analytic_scan_flops():
    out = _run(r"""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.analysis.hlo import analyze
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
D = 128
def body(x, w):
    return jax.nn.relu(jnp.einsum("bd,df->bf", x, w)), None
def stacked(ws, x):
    return jax.lax.scan(body, x, ws)[0].sum()
ws = jax.ShapeDtypeStruct((6, D, D), jnp.float32,
                          sharding=NamedSharding(mesh, P(None, None, "model")))
xs = jax.ShapeDtypeStruct((8, D), jnp.float32,
                          sharding=NamedSharding(mesh, P("data", None)))
with mesh:
    compiled = jax.jit(stacked).lower(ws, xs).compile()
r = analyze(compiled.as_text(), pod_size=4)
analytic = 6 * 2 * 4 * 128 * 32       # per-device: 6 iters, B_loc=4, f_loc=32
assert abs(r["flops"] - analytic) / analytic < 0.01, (r["flops"], analytic)
assert r["coll_bytes_total"] > 0
print("OK", r["flops"])
""")
    assert "OK" in out


def test_parser_matches_cost_analysis_no_scan():
    out = _run(r"""
import jax, jax.numpy as jnp
from repro.analysis.hlo import analyze
def f(a, b):
    return (a @ b).sum()
a = jnp.ones((64, 128)); b = jnp.ones((128, 32))
compiled = jax.jit(f).lower(a, b).compile()
ca = compiled.cost_analysis()
r = analyze(compiled.as_text())
# dot flops identical when there is no while loop
assert abs(r["flops"] - 2 * 64 * 128 * 32) < 1e3, r["flops"]
assert abs(ca["flops"] - r["flops"]) / max(ca["flops"], 1) < 0.05
print("OK")
""")
    assert "OK" in out


def test_collective_classification_dcn():
    out = _run(r"""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.analysis.hlo import analyze
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
x = jax.ShapeDtypeStruct((8, 64), jnp.float32,
                         sharding=NamedSharding(mesh, P(("pod", "data"), None)))
def f(t):
    return t.sum()                      # all-reduce across all 8 devices
with mesh:
    compiled = jax.jit(f).lower(x).compile()
r = analyze(compiled.as_text(), pod_size=4)
# the reduction spans the pod boundary -> classified as DCN traffic
assert r["coll_bytes_total"] > 0
assert r["coll_dcn_bytes"] > 0, r
print("OK")
""")
    assert "OK" in out


def test_roofline_terms():
    from repro.analysis.roofline import model_flops, roofline_from_costs
    from repro.configs import SHAPES, get_config
    cfg = get_config("llama3.2-3b")
    parsed = {"flops": 1e13, "bytes": 1e12, "coll_bytes_total": 5e10,
              "coll_dcn_bytes": 1e10}
    r = roofline_from_costs(cfg, SHAPES["train_4k"], parsed, n_chips=256,
                            device_kind="TPU v5 lite")
    assert r["compute_s"] == 1e13 / 197e12
    assert r["memory_s"] == 1e12 / 819e9
    assert abs(r["collective_s"] - (4e10 / 50e9 + 1e10 / 25e9)) < 1e-9
    assert r["dominant"] == "memory_s"
    assert 0 < r["useful_flop_ratio"]
    mf_train = model_flops(cfg, SHAPES["train_4k"])
    mf_dec = model_flops(cfg, SHAPES["decode_32k"])
    assert mf_train / mf_dec == (3 * 4096 * 256) / 128
