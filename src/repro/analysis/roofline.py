"""Three-term roofline from the dry-run's compiled artifact.

    compute    = HLO_FLOPs / peak_FLOP/s          (per chip)
    memory     = HLO_bytes / HBM_bw               (per chip)
    collective = ICI_bytes / ICI_bw + DCN_bytes / DCN_bw

All inputs are per-device (post-SPMD partitioning), trip-count-corrected
by analysis/hlo.py.  MODEL_FLOPS is the analytic useful compute:
  train   : 6 * N * D        (N = params, active-only for MoE; D = tokens)
  prefill : 2 * N * D
  decode  : 2 * N * B        (one token per slot)
The ratio MODEL_FLOPS / (HLO_FLOPs * chips) exposes remat/dispatch waste.
"""
from __future__ import annotations

from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.mesh import peaks


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch        # decode: 1 token/slot


def roofline_from_costs(cfg: ModelConfig, shape: ShapeConfig, parsed: dict,
                        *, n_chips: int, device_kind: str) -> dict:
    hw = peaks(device_kind)
    flops = parsed["flops"]                 # per device
    byts = parsed["bytes"]
    coll_total = parsed["coll_bytes_total"]
    dcn = parsed.get("coll_dcn_bytes", 0.0)
    ici = max(coll_total - dcn, 0.0)
    compute_s = flops / hw["flops_bf16"]
    memory_s = byts / hw["hbm_bw"]
    collective_s = ici / hw["ici_bw"] + dcn / hw["dcn_bw"]
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    hlo_global = flops * n_chips
    step_s = max(compute_s, memory_s, collective_s)
    ideal_s = mf / (n_chips * hw["flops_bf16"])
    return {
        **{k: float(v) for k, v in terms.items()},
        "dominant": dominant,
        "model_flops": mf,
        "hlo_flops_global": hlo_global,
        "useful_flop_ratio": (mf / hlo_global) if hlo_global else 0.0,
        # fraction of the compute roofline this step achieves if the
        # dominant term is the critical path (no overlap assumed)
        "roofline_fraction": (ideal_s / step_s) if step_s else 0.0,
        "step_time_bound_s": step_s,
    }


def fmt_seconds(s: float) -> str:
    if s >= 1.0:
        return f"{s:.2f}s"
    if s >= 1e-3:
        return f"{s * 1e3:.2f}ms"
    return f"{s * 1e6:.1f}us"
