"""Platform resolution: which device runs the program, how its Pallas
kernels run there, and where compiled programs are cached.

Written against the installed JAX (0.9): call sites use
``pltpu.CompilerParams``, ``jax.make_mesh(..., axis_types=...)``,
``jax.shard_map`` and ``compiled.cost_analysis()`` directly.

Nothing here falls back quietly.  A backend that fails to initialise
raises instead of reading as "no TPU", and a Pallas kernel asked for off
the TPU raises unless ``REPRO_FORCE_PALLAS_INTERPRET=1`` selects the
Pallas interpreter (the CPU test rig's override).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

INTERPRET_ENV = "REPRO_FORCE_PALLAS_INTERPRET"

# fixed, so that every run of this checkout finds the same cache: the
# path is part of the cache key
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def on_tpu() -> bool:
    """Is the default device a TPU?  Backend initialisation errors
    propagate."""
    return jax.devices()[0].platform == "tpu"


def force_interpret() -> bool:
    """The one reader of the ``REPRO_FORCE_PALLAS_INTERPRET`` knob."""
    return os.environ.get(INTERPRET_ENV, "0") == "1"


def pallas_interpret() -> bool:
    """The ``interpret=`` flag for a ``pallas_call``: compiled (Mosaic)
    on a TPU; off the TPU the Pallas interpreter, and only under the
    interpret override — otherwise the request is an error."""
    if on_tpu():
        return False
    if force_interpret():
        return True
    raise RuntimeError(
        f"a Pallas kernel was requested on {jax.devices()[0].platform!r}, "
        f"not a TPU; set {INTERPRET_ENV}=1 to run it in the Pallas "
        "interpreter")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is
    JAX's own setting and is left alone; otherwise the cache is
    ``<checkout>/.jax_cache``.  Call from ``__main__`` code only — never
    at import or from tests."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
