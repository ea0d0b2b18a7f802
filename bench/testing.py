"""A tiny float32 cell for the CPU tests: the same harness, configuration
keys and mix keys as the chip cells, at sizes a test run can hold."""
from __future__ import annotations

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def tiny_model(limit: float = 1e-3) -> dict:
    """The program's reduced float32 preset's widths, at two layers."""
    return {"name": "tiny", "family": "dense", "reference": "dense",
            "hidden_size": 128, "intermediate_size": 256,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 32, "num_hidden_layers": 2,
            "vocab_size": 500, "vocab_padded": 512, "rope_theta": 10000.0,
            "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
            "torch_dtype": "float32", "check": {"logit_gap_limit": limit}}


def tiny_mix() -> dict:
    return {"agents": 3, "tenant_of_agent": [0, 1, 0], "high_agents": [0],
            "tasks": {"kind": "generate_task", "models": ["haiku", "glm"],
                      "scale": None, "corpus_seed": 0, "tasks_per_agent": 2},
            "session": {"prompt_tokens": 16, "gen_per_call": 4,
                        "tokens_per_mb": 0.2, "max_phases": 4},
            "start_spread_steps": 32,
            # a pool that binds and three sessions with a small
            # memory.high, so that freezes, thaws, throttles and denials
            # all come in a second's run
            "engine": {"max_slots": 2, "s_max": 128, "pool_pages": 6,
                       "page_tokens": 16, "mode": "inkernel",
                       "backend": "device", "use_freeze": True,
                       "use_tool_domains": True, "use_intent": True,
                       "session_high": {"a1.0": 2, "a2.0": 3, "a0.1": 2}},
            "warmup_steps": 30, "trace_steps": 4,
            "check": {"max_sessions": 6, "min_served_tokens": 80}}


def tiny_cell(name: str = "tiny.fleet"):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {"name": name, "config": "tiny", "traffic": "tiny", "chips": 1}
    e2e = [m["name"] for m in spec["end_to_end"]]
    per = [m["name"] for m in spec["per_layer"]]
    return cell, tiny_model(), tiny_mix(), e2e, per
