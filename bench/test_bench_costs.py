"""FLOP and byte functions at the cells' shapes, through each
configuration's family, against sums worked by hand."""
import json
from pathlib import Path

from bench import costs
from bench.models import family
from bench.peaks import peaks

BENCH = Path(__file__).resolve().parent


def model(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_minicpm_token_flops():
    m = model("minicpm-2b")
    # q, k, v, o are 2304 x (36 * 64) each (MHA); SwiGLU 3 x 2304 x 5760
    per_layer = 4 * 2304 * 2304 + 3 * 2304 * 5760
    assert family(m).layer_matmul_params(m) == per_layer == 61046784
    # one token attending 100 positions: 2 x (40 layers + tied head) +
    # QK and PV over 100 positions in 40 layers of 36 heads of 64
    want = 2 * (40 * per_layer + 2304 * 122880) + 4 * 40 * 36 * 64 * 100
    assert family(m).token_flops(m, [100]) == want
    assert family(m).token_flops(m, [100, 1]) == (
        want + 2 * (40 * per_layer + 2304 * 122880) + 4 * 40 * 36 * 64)


def test_internlm2_layer_params():
    m = model("internlm2-20b-l8")
    per_layer = 2 * 6144 * 48 * 128 + 2 * 6144 * 8 * 128 + 3 * 6144 * 16384
    assert family(m).layer_matmul_params(m) == per_layer == 390070272


def test_flash_decode_cost_at_the_cells_shapes():
    m = model("minicpm-2b")
    nbytes, flops = family(m).flash_decode_cost(m, [1536] * 8)
    # K and V of 8 x 1536 positions, 36 kv heads of 64, bf16; q and o
    assert nbytes == 2 * 8 * 1536 * 36 * 64 * 2 + 2 * 8 * 36 * 64 * 2
    assert flops == 4 * 36 * 64 * 8 * 1536
    t, bound = costs.roofline_seconds(nbytes, flops, peaks("TPU v5 lite"))
    assert bound == "memory" and t == nbytes / 819e9
    m = model("internlm2-20b-l8")
    nbytes, flops = family(m).flash_decode_cost(m, [1, 4096])
    assert nbytes == 2 * 4097 * 8 * 128 * 2 + 2 * 2 * 48 * 128 * 2
    assert flops == 4 * 48 * 128 * 4097


def test_unknown_device_kind_is_an_error():
    import pytest
    with pytest.raises(KeyError):
        peaks("TPU v9")
