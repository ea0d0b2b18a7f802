"""Checkpointing: atomicity, keep-k GC, bit-exact resume, crash-restart
via the real training driver (failure injection)."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.checkpoint.manager import CheckpointManager


def tree():
    return {"a": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4)},
            "b": [jnp.ones((2,), jnp.bfloat16), jnp.zeros((), jnp.int32)]}


def test_save_load_roundtrip(tmp_path):
    t = tree()
    p = str(tmp_path / "c.npz")
    ckpt.save(p, 7, t)
    step, t2 = ckpt.load(p, t)
    assert step == 7
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(t2)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


import jax  # noqa: E402  (used above in tree comparisons)


def test_atomic_no_partial_file(tmp_path):
    p = str(tmp_path / "c.npz")
    ckpt.save(p, 1, tree())
    # a tmp file from a 'crashed' write must not confuse the manager
    with open(str(tmp_path / "ckpt_00000009.npz.tmp.999"), "wb") as f:
        f.write(b"garbage")
    mgr = CheckpointManager(str(tmp_path), keep=3)
    assert mgr.steps() == []


def test_keep_k_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, every=1,
                            async_write=False)
    for s in range(1, 6):
        mgr.maybe_save(s, tree())
    assert mgr.steps() == [4, 5]


def test_async_writer_overlap(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=5, every=1)
    for s in range(1, 4):
        mgr.maybe_save(s, tree())
    mgr.finalize()
    assert mgr.steps() == [1, 2, 3]


def _run_driver(tmp_path, extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "repro.launch.train",
           "--arch", "llama3.2-3b", "--reduced", "--steps", "16",
           "--batch", "2", "--seq", "32", "--ckpt-every", "5",
           "--sync-ckpt",
           "--ckpt-dir", str(tmp_path / "ck"), "--log-every", "100"] + extra
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=600)


@pytest.mark.slow
def test_crash_and_restart_bit_exact(tmp_path):
    """Kill the driver mid-run; restart must resume from the last
    checkpoint and finish with the same final loss as an uninterrupted
    run (data is a pure function of step)."""
    r1 = _run_driver(tmp_path, ["--crash-at", "8"])
    assert r1.returncode == 42, r1.stderr[-1500:]
    r2 = _run_driver(tmp_path, [])
    assert r2.returncode == 0, r2.stderr[-1500:]
    rep2 = json.loads(r2.stdout.strip().splitlines()[-1])
    assert rep2["resumed_from"] == 5
    # uninterrupted reference
    r3 = _run_driver(tmp_path.parent / "ref", [])
    rep3 = json.loads(r3.stdout.strip().splitlines()[-1])
    assert abs(rep2["last_loss"] - rep3["last_loss"]) < 1e-5
