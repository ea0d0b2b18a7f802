"""Production meshes + logical-axis rule resolution.

Single pod: (16, 16) = 256 chips, axes (data, model) — all ICI.
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the pod
axis crosses DCN; collectives on it are the expensive ones and the
roofline's collective term prices them at DCN bandwidth.

``rules_for`` resolves the logical axes used by parameter schemas and
activation constraints into mesh axes, per (mode, shape):
  train:   weights FSDP over data + TP over model; batch over (pod,data)
  serve:   weights TP only (replicated over data) except expert stacks;
           decode caches sequence-sharded over model (flash-decoding);
           long-context (batch=1) shards the cache over EVERY axis.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType

from repro.configs.base import ShapeConfig
from repro.models.schema import RULES

# Per-chip peaks, keyed by ``jax.Device.device_kind``.  Source: Google
# Cloud documentation, "TPU v5e" (bf16 FLOP/s, HBM capacity and
# bandwidth, 1,600 Gbit/s inter-chip interconnect = 4 links x 50 GB/s).
# ``dcn_bw`` (cross-pod) is this repo's planning assumption, not a
# published figure.
PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,       # peak bf16 FLOP/s
        "hbm_bw": 819e9,            # HBM bytes/s
        "ici_bw": 50e9,             # per-link ICI bytes/s
        "dcn_bw": 25e9,             # cross-pod bytes/s (assumption)
        "hbm_bytes": 16 * 2 ** 30,  # capacity
    },
}

# the chip the production meshes below are made of
PRODUCTION_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Peak table row for one device kind; an unknown kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak table entry for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


POD_CHIPS = 256                 # devices per pod (16 x 16)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def _batch_axes(mesh) -> tuple:
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))


def _axis_prod(mesh, axes) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in axes:
        n *= sizes[a]
    return n


def rules_for(mesh, *, mode: str, shape: Optional[ShapeConfig] = None) -> dict:
    """Logical-axis -> mesh-axis rules for one (mode, shape) cell."""
    assert mode in ("train", "serve"), mode
    rules = dict(RULES[mode])
    # sequence-parallel residual stream in training: carries + remat-saved
    # activations are sharded over the model axis between layers
    rules["act_seq"] = "model" if mode == "train" else None
    batch_axes = _batch_axes(mesh)
    nb = _axis_prod(mesh, batch_axes)
    gb = shape.global_batch if shape is not None else nb
    if gb % nb == 0 and gb >= nb:
        rules["act_batch"] = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    elif gb % 16 == 0:
        rules["act_batch"] = "data"
    else:
        rules["act_batch"] = None            # e.g. long-context batch=1
    if shape is not None and shape.kind == "decode":
        if shape.global_batch == 1:
            # long-context: the cache is the whole working set — shard its
            # sequence axis over every mesh axis
            rules["cache_seq"] = tuple(mesh.axis_names)
        else:
            rules["cache_seq"] = "model"
    else:
        rules["cache_seq"] = "model"
    return rules
