"""Model families (``bench/models``): the configuration names its family,
and the family module builds the program's config, weights and engine
settings and counts the FLOPs the metrics divide by.

The golden values were read from the harness before its dense code moved
into ``bench/models/dense.py`` (then ``run.model_config``,
``run.program_params`` and the ``PerfConfig`` that ``run.py`` passed): the
dense cells have to build exactly what they built then.
"""
import hashlib
import json
import os
import subprocess
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run, spans_run
from bench.models import family
from bench.testing import tiny_mix, tiny_model

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SEED = 2 ** 32 + 3
CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))


def model(name):
    if name == "tiny":
        return tiny_model()
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def dense_leaves(L, d, h, hkv, hd, f, vocab, *, tied):
    """(path, shape) of every leaf of a dense weight tree, in tree order."""
    g = "['groups'][0]"
    return ([] if tied else [("['embed']['head']", (d, vocab))]) + [
        ("['embed']['tok']", (vocab, d)),
        (f"{g}['ffn']['w_down']", (L, f, d)),
        (f"{g}['ffn']['w_gate']", (L, d, f)),
        (f"{g}['ffn']['w_up']", (L, d, f)),
        (f"{g}['ln1']['scale']", (L, d)),
        (f"{g}['ln2']['scale']", (L, d)),
        (f"{g}['mixer']['wk']", (L, d, hkv * hd)),
        (f"{g}['mixer']['wo']", (L, h * hd, d)),
        (f"{g}['mixer']['wq']", (L, d, h * hd)),
        (f"{g}['mixer']['wv']", (L, d, hkv * hd)),
        ("['out_norm']['scale']", (d,))]


GOLDEN = {
    "tiny": {
        "config": dict(name="tiny", n_layers=2, d_model=128, n_heads=4,
                       n_kv_heads=2, d_ff=256, vocab=500, head_dim=32,
                       rope_theta=10000.0, norm_eps=1e-05,
                       tie_embeddings=False, dtype="float32"),
        "leaves": dense_leaves(2, 128, 4, 2, 32, 256, 512, tied=False),
        "dtype": "float32",
        # sha256 of each leaf's bytes at SEED, first 16 hex digits
        "sha": ["caec05a36234f0c8", "6173b81dc4741ad4", "71c0d83aa2f10078",
                "0946c1afb03633f1", "6d7ef6b2a48196b2", "893a106828fbdb95",
                "893a106828fbdb95", "1b9b6ffa55af315d", "8552a0d00fa56adf",
                "423c79acfab91b13", "2c2a06bcd724e172", "02722f124d0f1736"],
    },
    "minicpm-2b": {
        "config": dict(name="minicpm-2b", n_layers=40, d_model=2304,
                       n_heads=36, n_kv_heads=36, d_ff=5760, vocab=122753,
                       head_dim=64, rope_theta=10000.0, norm_eps=1e-05,
                       tie_embeddings=True, dtype="bfloat16"),
        "leaves": dense_leaves(40, 2304, 36, 36, 64, 5760, 122880,
                               tied=True),
        "dtype": "bfloat16",
    },
    "internlm2-20b-l8": {
        "config": dict(name="internlm2-20b-l8", n_layers=8, d_model=6144,
                       n_heads=48, n_kv_heads=8, d_ff=16384, vocab=92544,
                       head_dim=128, rope_theta=1000000.0, norm_eps=1e-05,
                       tie_embeddings=False, dtype="bfloat16"),
        "leaves": dense_leaves(8, 6144, 48, 8, 128, 16384, 92672,
                               tied=False),
        "dtype": "bfloat16",
    },
}


# ------------------------------------------------ the move changed nothing

@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_program_params_as_before(name):
    m, gold = model(name), GOLDEN[name]
    fam = family(m)
    dtype = jnp.dtype(fam.model_config(m).dtype)
    if name == "tiny":
        params = fam.program_params(m, SEED, dtype)
    else:
        # shapes only: the weights of a chip cell do not fit a test run
        params = jax.eval_shape(lambda: fam.program_params(m, SEED, dtype))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    assert [(jax.tree_util.keystr(p), tuple(x.shape))
            for p, x in flat] == gold["leaves"]
    assert {str(x.dtype) for _, x in flat} == {gold["dtype"]}
    if "sha" in gold:
        assert [hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]
                for _, x in flat] == gold["sha"]


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_model_config_as_before(name):
    from repro.configs.base import ModelConfig
    m = model(name)
    assert family(m).model_config(m) == ModelConfig(
        family="dense", **GOLDEN[name]["config"])


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_engine_settings_as_before(name):
    from repro.perf import DEFAULT_PERF, replace
    m = model(name)
    assert family(m).perf(m) == replace(DEFAULT_PERF, scan_chunk=32)


# ------------------------------------------------ the family is a plug-in

STUB = "stub_family"


class Built(Exception):
    pass


@pytest.fixture
def stub(monkeypatch):
    """A family module registered as ``bench.models.stub_family``; every
    call to it is recorded. The engine is replaced by one that records
    what it was given and stops the run there."""
    from repro.serving import engine as E
    calls = []
    mod = types.ModuleType(f"bench.models.{STUB}")
    cfg = types.SimpleNamespace(dtype="float32", padded_vocab=8)
    mod.model_config = lambda m: calls.append(("model_config", m)) or cfg
    mod.program_params = lambda m, seed, dtype: calls.append(
        ("program_params", m, seed, dtype)) or "params"
    mod.perf = lambda m: calls.append(("perf", m)) or "perf"
    mod.token_flops = lambda m, attended: calls.append(
        ("token_flops", m, list(attended))) or 1e12
    mod.dims = lambda m: calls.append(("dims", m)) or {"layers": 2}
    mod.flash_decode_cost = lambda m, lengths: calls.append(
        ("flash_decode_cost", m, list(lengths))) or (0.5e9, 0.0)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)

    def engine(cfg, params, *, perf, ecfg, seed, tracer=None):
        calls.append(("Engine", cfg, params, perf, seed))
        raise Built
    monkeypatch.setattr(E, "Engine", engine)
    m = dict(tiny_model(), name="stubbed", family=STUB)
    return m, cfg, calls


@pytest.mark.parametrize("entry", ["run_cell", "run_program_cell"])
def test_each_entry_point_builds_through_the_named_family(stub, entry):
    m, cfg, calls = stub
    loaded = ({"name": "stubbed.fleet", "chips": 1}, m, tiny_mix(), [], [])
    fn = {"run_cell": run.run_cell,
          "run_program_cell": spans_run.run_program_cell}[entry]
    with pytest.raises(Built):
        fn("stubbed.fleet", SEED, 1.0, False, loaded=loaded,
           require_tpu=False)
    assert calls == [("model_config", m),
                     ("program_params", m, SEED, jnp.dtype("float32")),
                     ("perf", m), ("Engine", cfg, "params", "perf", SEED)]
    assert STUB not in (BENCH / "run.py").read_text()


def test_metric_readers_count_through_the_named_family(stub):
    m, _, calls = stub
    steps = [(np.array([3, 5]), np.array([True, False]))]
    rec = run.Record(
        model=m, mix={}, chips=1, peak={"flops_bf16": 1e12, "hbm_bw": 1e9},
        setup_s=0.0, window=(0.0, 2.0), sessions=[],
        spans=types.SimpleNamespace(steps=steps),
        trace={"op_s": {"flash_decode.3": 2.0, "fusion.1": 9.0}},
        traced_steps=steps, open_waits=[])
    got = run.read_metrics(["step_mfu", "flash_decode_roofline"], rec)
    # 1e12 FLOPs over 2 s of a 1e12 FLOP/s chip; 2 layers x 0.5 s at
    # peak over 2 s of the kernel
    assert got["step_mfu"]["value"] == 50.0
    assert got["flash_decode_roofline"]["value"] == 50.0
    assert calls == [("token_flops", m, [4]), ("dims", m),
                     ("flash_decode_cost", m, [4, 6])]


@pytest.mark.parametrize("fam, says", [
    (None, "names no model family"),
    ("no_such_family", "unknown model family 'no_such_family'"),
    ("../dense", "names no model family"),
])
def test_a_configuration_without_a_known_family_is_refused(fam, says):
    m = dict(tiny_model(), name="orphan")
    m.pop("family")
    if fam is not None:
        m["family"] = fam
    with pytest.raises(ValueError, match=says) as e:
        family(m)
    assert "'orphan'" in str(e.value)


@pytest.mark.parametrize("name", CONFIGS)
def test_every_configuration_names_its_family(name):
    m = model(name)
    assert "family" in m
    fam = family(m)
    for attr in ("model_config", "program_params", "perf", "dims",
                 "token_flops", "flash_decode_cost", "LAYER_LEAVES",
                 "global_leaves", "layer_weights", "global_weights"):
        assert hasattr(fam, attr), (name, attr)


# ------------------------------------------ the reference stays independent

def test_reference_and_family_import_nothing_of_the_program():
    code = ("import sys\n"
            "import bench.references.dense, bench.models.dense\n"
            "print(sorted(m for m in sys.modules\n"
            "             if m == 'repro' or m.startswith('repro.')))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    # ``repro`` is importable here, and still none of it was imported
    assert out.stdout.strip() == "[]"
