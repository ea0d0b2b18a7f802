"""KV/state cache management + page accounting for the serving engine.

Two layers:
  * ``PageAccountant`` — maps context length to page counts (the charge
    unit of the resource domains; 1 page = ``page_tokens`` tokens of KV/
    state footprint).  This is what AgentCgroup governs.
  * ``SlotCaches`` — the dense per-slot decode state (built from
    ``model.decode_state_schema``), with freeze/thaw slot offload to a
    ``FrozenStore`` (host memory) and slot recycling.

The Pallas paged-decode kernel (kernels/decode_attention.py) is the TPU
production path for the GQA cache layout; on the CPU test rig the engine
runs the dense per-slot layout with identical page-granular accounting
(see DESIGN.md §hardware-adaptation).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.freezer import FrozenStore
from repro.models import model as M
from repro.models.schema import Leaf, tree_map_schema
from repro.spans import OFF, Tracer, to_host


@dataclass(frozen=True)
class PageAccountant:
    page_tokens: int = 16

    def pages_for(self, n_tokens: int) -> int:
        return math.ceil(max(n_tokens, 1) / self.page_tokens)

    def crossing(self, length: int) -> int:
        """Pages that must be charged to append token #length (0-based)."""
        return 1 if length % self.page_tokens == 0 else 0


class SlotCaches:
    """Dense per-slot decode state with host offload."""

    def __init__(self, cfg: ModelConfig, max_slots: int, s_max: int, *,
                 tracer: Optional[Tracer] = None):
        self.cfg = cfg
        self.tracer = tracer if tracer is not None else OFF
        self.max_slots = max_slots
        self.s_max = s_max
        sch = M.decode_state_schema(cfg, max_slots, s_max)
        self.state = tree_map_schema(
            lambda l: jnp.zeros(l.shape, jnp.dtype(l.dtype or cfg.dtype)), sch)
        self._free = list(range(max_slots))
        self.store = FrozenStore()

    # ------------------------------------------------------------- slots

    def alloc_slot(self) -> Optional[int]:
        return self._free.pop(0) if self._free else None

    def free_slot(self, slot: int) -> None:
        # zero the slot's state so a recycled slot starts clean
        with self.tracer.span("kv.free_slot"):
            self.state = jax.tree.map(
                lambda x: x.at[:, slot].set(jnp.zeros_like(x[:, slot])),
                self.state)
            self._free.append(slot)

    @property
    def n_free(self) -> int:
        return len(self._free)

    # ----------------------------------------------------- freeze / thaw

    def freeze_slot(self, session_id: str, slot: int, *, pages: int,
                    meta: Optional[dict] = None, now: float = 0.0) -> None:
        """Offload one slot's state to host memory and recycle the slot."""
        with self.tracer.span("kv.freeze_slot"):
            blob = jax.tree.map(lambda x: to_host(x[:, slot]), self.state)
            self.store.freeze(session_id, blob, pages=pages, meta=meta,
                              now=now)
            self.free_slot(slot)

    def thaw_slot(self, session_id: str) -> tuple[int, dict]:
        """Restore a frozen session into a fresh slot."""
        with self.tracer.span("kv.thaw_slot"):
            slot = self.alloc_slot()
            assert slot is not None, "no free slot to thaw into"
            entry = self.store.thaw(session_id)
            self.state = jax.tree.map(
                lambda x, b: x.at[:, slot].set(jnp.asarray(b, x.dtype)),
                self.state, entry.blobs)
            return slot, entry.meta
