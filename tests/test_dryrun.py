"""Dry-run machinery integration: one representative cell per step kind
lowers + compiles on the production meshes (subprocess with 512 fake
devices), producing memory/cost/roofline records — the deliverable-(e)
pipeline exercised inside the test suite."""
import json
import os
import subprocess
import sys

import pytest


def _run_cells(cells, mesh):
    code = f"""
import json
from repro.launch.dryrun import run_cell
out = []
for arch, shape in {cells!r}:
    rec = run_cell(arch, shape, {mesh!r} == "multi")
    out.append(rec)
print("CELLJSON:" + json.dumps(out))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["JAX_PLATFORMS"] = "cpu"
    # run_cell is imported from dryrun, whose first lines set XLA_FLAGS
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("CELLJSON:")]
    return json.loads(line[0][len("CELLJSON:"):])


@pytest.mark.slow
def test_dryrun_cells_compile_single_pod():
    recs = _run_cells([("llama3.2-3b", "train_4k"),
                       ("llama3.2-3b", "decode_32k"),
                       ("xlstm-350m", "prefill_32k")], "single")
    for rec in recs:
        assert rec["applicable"] and "error" not in rec, rec
        assert rec["n_chips"] == 256
        r = rec["roofline"]
        assert r["compute_s"] > 0 and r["memory_s"] > 0
        assert r["dominant"] in ("compute_s", "memory_s", "collective_s")
        assert 0 < r["useful_flop_ratio"] < 2.0
        assert rec["memory"]["per_device_bytes"] > 0
    # the 3B train cell must fit a 16 GiB chip
    assert recs[0]["memory"]["fits_hbm"]


@pytest.mark.slow
def test_dryrun_multi_pod_shards_pod_axis():
    recs = _run_cells([("llama3.2-3b", "train_4k")], "multi")
    rec = recs[0]
    assert rec["n_chips"] == 512 and "error" not in rec
    # cross-pod (DCN) traffic exists: gradients sync over the pod axis
    assert rec["hlo"]["coll_dcn_bytes"] > 0
    assert rec["memory"]["fits_hbm"]


def test_dryrun_skips_are_recorded():
    from repro.configs import SHAPES, cell_applicability, get_config
    ok, reason = cell_applicability(get_config("hubert-xlarge"),
                                    SHAPES["decode_32k"])
    assert not ok and "encoder-only" in reason
    ok, reason = cell_applicability(get_config("phi3-medium-14b"),
                                    SHAPES["long_500k"])
    assert not ok and "sub-quadratic" in reason
