"""Training substrate: convergence, microbatch equivalence, gradient
compression with error feedback, schedules, optimizer math."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config, reduced
from repro.data.pipeline import DataIterator
from repro.models import model as M
from repro.models.schema import init_params
from repro.perf import DEFAULT_PERF, replace as perf_replace
from repro.training import compression
from repro.training.optimizer import (OptConfig, adamw_update,
                                      init_opt_state, make_schedule)
from repro.training.train_step import init_train_state, make_train_step

BASE_PERF = perf_replace(DEFAULT_PERF, scan_chunk=32, remat="none")


def setup(arch="minicpm-2b", batch=4, seq=64, perf=BASE_PERF, steps=30):
    cfg = dataclasses.replace(reduced(get_config(arch)), dtype="float32")
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=seq,
                                global_batch=batch)
    params = init_params(M.param_schema(cfg), jax.random.PRNGKey(0),
                         cfg.dtype)
    opt_cfg = OptConfig(schedule=cfg.schedule, warmup_steps=3,
                        total_steps=steps, lr=1e-3)
    step = jax.jit(make_train_step(cfg, perf, opt_cfg))
    data = DataIterator(cfg, shape, seed=0, batch=batch, seq=seq)
    return cfg, params, init_train_state(cfg, params, perf), step, data


def run_steps(params, opt, step_fn, data, n):
    losses = []
    for i in range(n):
        params, opt, m = step_fn(params, opt, data.at(i), i)
        losses.append(float(m["loss"]))
    return params, losses


def test_convergence_on_learnable_data():
    cfg, params, opt, step, data = setup(steps=30)
    _, losses = run_steps(params, opt, step, data, 30)
    assert losses[0] > 5.5                    # ~ln(512) at init
    assert losses[-1] < losses[0] - 1.0       # clearly learning


def test_microbatch_grads_match_full_batch():
    cfg, params, opt, _, data = setup()
    batch = data.at(0)
    from repro.models.model import loss_fn
    g_full = jax.grad(lambda p: loss_fn(cfg, p, batch, perf=BASE_PERF)[0])(
        params)
    perf_mb = perf_replace(BASE_PERF, microbatches=2)
    step_mb = make_train_step(cfg, perf_mb, OptConfig(lr=0.0,
                                                      weight_decay=0.0,
                                                      grad_clip=1e9))
    # lr=0: params unchanged; compare the computed grad via opt moments
    opt0 = init_train_state(cfg, params, perf_mb)
    _, opt1, m = jax.jit(step_mb)(params, opt0, batch, 0)
    # m1 = (1-b1) * grad after one step
    g_mb = jax.tree.map(lambda x: x / 0.1, opt1["m"])
    for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_mb)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a, np.float32),
                                   atol=5e-3, rtol=5e-2)


def test_grad_compression_tracks_fp32():
    cfg, p0, o0, step0, data = setup(steps=25)
    _, base_losses = run_steps(p0, o0, step0, data, 25)
    perf_c = perf_replace(BASE_PERF, grad_compress=True)
    cfg2, p1, o1, step1, data1 = setup(perf=perf_c, steps=25)
    _, comp_losses = run_steps(p1, o1, step1, data1, 25)
    # error feedback keeps compressed training within a small gap
    assert abs(comp_losses[-1] - base_losses[-1]) < 0.35


def test_error_feedback_reduces_bias():
    k = jax.random.PRNGKey(3)
    g = jax.random.normal(k, (256,)) * 1e-3
    err = jnp.zeros_like(g)
    acc_plain = jnp.zeros_like(g)
    acc_fb = jnp.zeros_like(g)
    err_acc = jnp.zeros_like(g)
    for i in range(20):
        gh, _ = compression.quantize_leaf(g, jnp.zeros_like(g))
        acc_plain += gh
        gh2, err_acc = compression.quantize_leaf(g, err_acc)
        acc_fb += gh2
    true = g * 20
    assert (jnp.abs(acc_fb - true).max()
            <= jnp.abs(acc_plain - true).max() + 1e-7)


def test_schedules():
    cos = make_schedule(OptConfig(lr=1.0, warmup_steps=10, total_steps=100,
                                  schedule="cosine"))
    wsd = make_schedule(OptConfig(lr=1.0, warmup_steps=10, total_steps=100,
                                  schedule="wsd"))
    assert float(cos(0)) == 0.0
    assert abs(float(cos(10)) - 1.0) < 1e-6
    assert float(cos(100)) <= 0.11
    # WSD: flat at peak through the stable phase, then fast decay
    assert abs(float(wsd(11)) - 1.0) < 1e-5
    assert abs(float(wsd(80)) - 1.0) < 1e-5   # still stable at 80%
    assert float(wsd(100)) <= 0.11


def test_adamw_step_direction():
    p = {"w": jnp.ones((4,), jnp.float32)}
    g = {"w": jnp.ones((4,), jnp.float32)}
    st = init_opt_state(p)
    cfg = OptConfig(lr=0.1, weight_decay=0.0)
    p2, st2, gn = adamw_update(g, st, p, 0.1, cfg)
    assert float(p2["w"][0]) < 1.0            # moved against the gradient
    assert float(gn) == pytest.approx(2.0)


def test_compressed_psum_multidevice():
    """int8 all-gather all-reduce == fp32 psum (separate process with 8
    fake devices)."""
    import subprocess, sys, os
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.training.compression import compressed_psum
mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
x = jnp.linspace(-1.0, 1.0, 64).reshape(8, 8)
with mesh:
    got = jax.jit(lambda t: compressed_psum(t, mesh, "data"))(x)
want = x * 8.0
err = float(jnp.max(jnp.abs(got - want)))
assert err < 8 * 2.0 / 127, err
txt = jax.jit(lambda t: compressed_psum(t, mesh, "data")).lower(x).compile().as_text()
assert "all-gather" in txt and "s8[" in txt, "int8 payload not on the wire"
print("OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-2000:]
