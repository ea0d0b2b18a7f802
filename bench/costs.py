"""The least time work of given operations and bytes can take at a chip's
peak. The operations and bytes themselves are each family's
(``bench/models/<family>.py``), counted from shapes alone.
"""
from __future__ import annotations


def roofline_seconds(nbytes: float, flops: float, peak: dict) -> tuple:
    """(least seconds, bound) for work of ``nbytes`` and ``flops``."""
    t_mem = nbytes / peak["hbm_bw"]
    t_cmp = flops / peak["flops_bf16"]
    return (t_mem, "memory") if t_mem >= t_cmp else (t_cmp, "compute")
