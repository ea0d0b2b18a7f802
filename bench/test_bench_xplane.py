"""The trace reduction, on a trace recorded on one TPU v5e: two engine
steps of ``minicpm-2b.swe-fleet`` traced by ``bench/run.py --trace 1``."""
from pathlib import Path

import numpy as np

from bench import xplane
from bench.metrics import _common  # noqa: F401  (the readers' package)

TRACE = (Path(__file__).resolve().parent / "testdata"
         / "minicpm-2b.swe-fleet.2steps.xplane.pb")


def reduced():
    return xplane.reduce(*xplane.load(TRACE))


def test_device_busy_and_window():
    r = reduced()
    assert r["busy_s"] == 0.242377432
    assert r["window_s"] == 0.277159788
    assert 0 < r["busy_s"] < r["window_s"]


def test_ops_are_named_and_control_flow_is_not_double_counted():
    devices, spans = xplane.load(TRACE)
    r = xplane.reduce(devices, spans)
    assert not any(n.startswith("while") for n in r["op_s"])
    # the 40-layer scan runs flash_decode once per layer per step
    fd = [n for _, _, n in devices[0] if "flash_decode" in n]
    assert len(fd) == 2 * 40
    assert np.isclose(sum(v for n, v in r["op_s"].items()
                          if "flash_decode" in n), 0.015052136)
    assert r["device_ops"][0] == ["copy.102", 0.024326841000000002]
    # the sum of ops outside control flow covers no more than the union
    # of busy intervals plus overlap from asynchronous copies
    assert sum(r["op_s"].values()) > 0


def test_idle_gaps_are_labelled_by_host_spans():
    r = reduced()
    labels = [n for n, _ in r["idle_gaps"]]
    assert labels[0] == "bench.step"
    idle = sum(v for _, v in r["idle_gaps"])
    assert np.isclose(idle, r["window_s"] - r["busy_s"])


def test_union_and_clip():
    assert xplane._union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert xplane._clip([(0, 2), (3, 9)], 1, 4) == [(1, 2), (3, 4)]
    assert xplane.op_name("%copy.1 = bf16[2] copy(x)") == "copy.1"
