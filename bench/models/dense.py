"""The dense decoder family: pre-norm RMSNorm, rotary grouped-query
attention and a SwiGLU MLP in every layer, a final RMSNorm and a tied or
untied head.

``m`` is a model dict as ``bench/configs/<name>.json`` holds it (Hugging
Face key names). Attention FLOPs count the positions each query attends:
the live length, not the cache's capacity. Every weight leaf is a pure
function of (seed, leaf name, layer) (``bench/weights.py``); the program's
weight tree and the plain reference (``bench/references/dense.py``) are
both built from the leaves below.
"""
from __future__ import annotations

import numpy as np

from bench.weights import NORMAL, SMALL, leaf, root_key


def dims(m: dict) -> dict:
    d = m["hidden_size"]
    h = m["num_attention_heads"]
    return {"d": d, "h": h, "hkv": m["num_key_value_heads"],
            "hd": m.get("head_dim") or d // h, "f": m["intermediate_size"],
            "layers": m["num_hidden_layers"], "vocab": m["vocab_padded"]}


# per-layer leaves: name -> (shape from dims, init scale or None for ones)
LAYER_LEAVES = {
    "ln1": (lambda x: (x["d"],), None),
    "wq": (lambda x: (x["d"], x["h"] * x["hd"]), NORMAL),
    "wk": (lambda x: (x["d"], x["hkv"] * x["hd"]), NORMAL),
    "wv": (lambda x: (x["d"], x["hkv"] * x["hd"]), NORMAL),
    "wo": (lambda x: (x["h"] * x["hd"], x["d"]), SMALL),
    "ln2": (lambda x: (x["d"],), None),
    "w_gate": (lambda x: (x["d"], x["f"]), NORMAL),
    "w_up": (lambda x: (x["d"], x["f"]), NORMAL),
    "w_down": (lambda x: (x["f"], x["d"]), SMALL),
}


def global_leaves(m: dict) -> dict:
    x = dims(m)
    out = {"tok": ((x["vocab"], x["d"]), NORMAL),
           "out_norm": ((x["d"],), None)}
    if not m["tie_word_embeddings"]:
        out["head"] = ((x["d"], x["vocab"]), NORMAL)
    return out


def layer_weights(m: dict, key, layer, dtype):
    """Every leaf of one layer, keyed by name."""
    x = dims(m)
    return {n: leaf(key, n, layer, shp(x), sc, dtype)
            for n, (shp, sc) in LAYER_LEAVES.items()}


def global_weights(m: dict, key, dtype):
    return {n: leaf(key, n, 0, shp, sc, dtype)
            for n, (shp, sc) in global_leaves(m).items()}


def model_config(m: dict):
    """The program's ModelConfig for a dense decoder configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=m["name"], family="dense", n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab=m["vocab_size"], head_dim=m["head_dim"],
        rope_theta=m["rope_theta"], norm_eps=m["rms_norm_eps"],
        tie_embeddings=m["tie_word_embeddings"], dtype=m["torch_dtype"])


def program_params(m: dict, seed: int, dtype):
    """The program's weight tree, made on the device in one jitted call
    from the leaves above."""
    import jax
    import jax.numpy as jnp

    def make(key):
        g = global_weights(m, key, dtype)
        per = jax.vmap(lambda l: layer_weights(m, key, l, dtype))(
            jnp.arange(m["num_hidden_layers"]))
        embed = {"tok": g["tok"]}
        if "head" in g:
            embed["head"] = g["head"]
        layer = {"ln1": {"scale": per["ln1"]},
                 "mixer": {n: per[n] for n in ("wq", "wk", "wv", "wo")},
                 "ln2": {"scale": per["ln2"]},
                 "ffn": {n: per[n] for n in ("w_gate", "w_up", "w_down")}}
        return {"embed": embed, "groups": [layer],
                "out_norm": {"scale": g["out_norm"]}}
    # the key is an argument, not a constant, so that the compiled program
    # is the same for every seed and comes from the compile cache
    return jax.block_until_ready(jax.jit(make)(root_key(seed)))


def perf(m: dict):
    """The engine's PerfConfig as the program's serving entry point
    (``repro.launch.serve``) passes it: the defaults with ``scan_chunk``
    32, which only the SSM and xLSTM blocks read."""
    from repro.perf import DEFAULT_PERF, replace
    return replace(DEFAULT_PERF, scan_chunk=32)


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies in one dense layer: q, k, v, o and the
    three SwiGLU matrices."""
    x = dims(m)
    attn = x["d"] * x["h"] * x["hd"] * 2 + x["d"] * x["hkv"] * x["hd"] * 2
    return attn + 3 * x["d"] * x["f"]


def token_flops(m: dict, attended) -> float:
    """Model FLOPs of processing tokens, one per entry of ``attended`` (the
    number of positions that token's query attends, its own included):
    2 x every matmul weight, the logits, and QK plus PV over the attended
    positions in every layer."""
    x = dims(m)
    attended = np.asarray(attended, np.float64)
    dense = 2.0 * (x["layers"] * layer_matmul_params(m) + x["d"] * x["vocab"])
    attn = 4.0 * x["layers"] * x["h"] * x["hd"] * attended
    return float(dense * attended.size + attn.sum())


def flash_decode_cost(m: dict, lengths, dtype_bytes: int = 2) -> tuple:
    """(bytes, FLOPs) one ``flash_decode`` call needs for one layer, where
    ``lengths`` are the per-slot positions attended (the kernel's
    ``lengths`` argument): K and V of those positions for every kv head,
    plus q in and o out."""
    x = dims(m)
    lengths = np.asarray(lengths, np.float64)
    kv = 2.0 * lengths.sum() * x["hkv"] * x["hd"] * dtype_bytes
    qo = 2.0 * lengths.size * x["h"] * x["hd"] * dtype_bytes
    flops = 4.0 * x["h"] * x["hd"] * lengths.sum()
    return kv + qo, flops
