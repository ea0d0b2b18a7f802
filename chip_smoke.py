"""Chip smoke test: serve full-width llama3.2-3b on a TPU through the engine.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # sharded control plane on four chips

One chip, all in this one process (the chip belongs to one process):
  1. ``flash_decode``, the compiled Pallas kernel the served path
     dispatches, against ``ref.decode_attention_ref`` at the served shapes
     in bf16 (tolerance ``FLASH_DECODE_TOL``);
  2. the backend conformance kit on the ``device`` kind: every scenario
     bit-exact against the host-tree reference;
  3. ``launch.serve.run`` on llama3.2-3b at its published widths in bf16
     (random weights from seed 0): 8 slots x 2048 tokens, a KV pool the
     size of the cache (1024 pages of 16 tokens), 4 agent sessions of a
     64-token prompt and two tool phases of 32 generated tokens each.
     Every session must finish with its full length, survival 1.0,
     overshoot 0 and no NaN/Inf in any step's logits.

``--chips 4`` runs only the sharded path and what it is compared with:
the conformance kit on the ``sharded`` and ``async-sharded`` kinds over
all four devices, then the same 4 sessions served on one chip
(``backend="device"``) and on the four-device control mesh
(``backend="sharded", n_shards=4``: one tenant per device, the KV
cache split by slot, the weights replicated on every device).

Earlier lines report each phase.  The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU, or when a phase fails, the script exits non-zero and does
not print it.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ARCH = "llama3.2-3b"
HEADS, KV_HEADS, HEAD_DIM = 24, 8, 128            # llama3.2-3b attention
SLOTS, S_MAX, PAGE_TOKENS = 8, 2048, 16
POOL_PAGES = SLOTS * S_MAX // PAGE_TOKENS        # the pool is the whole cache
N_SESSIONS, PROMPT_TOKENS, GEN_TOKENS, TOOL_TOKENS = 4, 64, 32, 32
MAX_STEPS = 1000
# bf16 inputs, f32 accumulation in both kernel and reference: allow a
# few bf16 ulps (2**-8 relative) at unit scale
FLASH_DECODE_TOL = 2e-2


class SmokeFailure(RuntimeError):
    pass


def _line(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Sums JAX's backend-compile durations (cache hits are not
    compiles) so each phase can report its compile seconds."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.total = 0.0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self._event:
            self.total += duration


def smoke_sessions() -> list:
    from repro.core import domains as D
    from repro.serving.session import Phase, Session
    return [Session(sid=f"s{i}", tenant=f"t{i}",
                    priority=D.HIGH if i == 0 else D.LOW,
                    prompt=[(j % 997) + 2 for j in range(PROMPT_TOKENS)],
                    phases=[Phase(GEN_TOKENS, TOOL_TOKENS, "python"),
                            Phase(GEN_TOKENS, TOOL_TOKENS, "git")])
            for i in range(N_SESSIONS)]


def expected_length() -> int:
    return PROMPT_TOKENS + 2 * (GEN_TOKENS + TOOL_TOKENS)


def check_flash_decode(seed: int = 0) -> float:
    """The served decode attention (``ops.decode_attention`` with no
    ``impl``: the Pallas kernel on a TPU) against the reference, at the
    served shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    cache = (SLOTS, S_MAX, KV_HEADS, HEAD_DIM)
    q = jax.random.normal(keys[0], (SLOTS, HEADS, HEAD_DIM), jnp.bfloat16)
    k = jax.random.normal(keys[1], cache, jnp.bfloat16)
    v = jax.random.normal(keys[2], cache, jnp.bfloat16)
    # lengths from a single token to the full cache
    lengths = jnp.asarray(np.linspace(1, S_MAX, SLOTS).round(), jnp.int32)
    got = np.asarray(jax.jit(ops.decode_attention)(q, k, v, lengths),
                     np.float32)
    want = np.asarray(jax.jit(ref.decode_attention_ref)(q, k, v, lengths),
                      np.float32)
    if not np.isfinite(got).all():
        raise SmokeFailure("flash_decode returned NaN/Inf")
    err = float(np.max(np.abs(got - want)))
    ok = np.allclose(got, want, atol=FLASH_DECODE_TOL, rtol=FLASH_DECODE_TOL)
    _line(f"flash_decode vs reference at B={SLOTS} S={S_MAX} H={HEADS} "
          f"Hkv={KV_HEADS} d={HEAD_DIM} bf16: max abs err {err!r} "
          f"(tol atol=rtol={FLASH_DECODE_TOL}) -> {'ok' if ok else 'FAIL'}")
    if not ok:
        raise SmokeFailure(f"flash_decode off the reference by {err!r}")
    return err


def check_conformance(kinds) -> None:
    from repro.testing.conformance import (ConformanceSuite,
                                           backend_features,
                                           standard_backend_factory)
    suite = ConformanceSuite()
    for kind in kinds:
        report = suite.run(standard_backend_factory(kind),
                           features=backend_features(kind))
        ran = [r for r in report.results if not r.skipped]
        _line(f"conformance[{kind}]: {sum(r.ok for r in ran)}/{len(ran)} "
              f"scenarios bit-exact vs host-tree reference "
              f"({len(report.results) - len(ran)} feature-gated skips)")
        if not report.ok:
            raise SmokeFailure(report.summary())


def serve(backend: str = "device", n_shards=None) -> dict:
    """Serve the smoke sessions through ``launch.serve.run`` and hold
    the run to its guarantees; returns the report."""
    from repro.launch import serve as serve_mod
    argv = ["--arch", ARCH, "--slots", str(SLOTS), "--s-max", str(S_MAX),
            "--pool-pages", str(POOL_PAGES),
            "--page-tokens", str(PAGE_TOKENS), "--mode", "inkernel",
            "--backend", backend, "--max-steps", str(MAX_STEPS),
            "--seed", "0"]
    if n_shards is not None:
        argv += ["--n-shards", str(n_shards)]
    report = serve_mod.run(serve_mod.parser().parse_args(argv),
                           smoke_sessions())
    want = expected_length()
    per = {sid: (r["length"], r["generated"])
           for sid, r in sorted(report["sessions"].items())}
    _line(f"serve[{backend}{'' if n_shards is None else f' x{n_shards}'}]: "
          f"steps {report['steps']}, (length, generated tokens) per session "
          f"{per}, survival {report['survival']!r}, overshoot "
          f"{report['overshoot_pages']}, non-finite logit steps "
          f"{report['nonfinite_logit_steps']}")
    bad = [sid for sid, r in report["sessions"].items()
           if r["state"] != "done" or r["length"] != want
           or r["generated"] != 2 * GEN_TOKENS]
    if bad:
        raise SmokeFailure(f"sessions {bad} did not finish at length {want}")
    if report["survival"] != 1.0 or report["overshoot_pages"] != 0:
        raise SmokeFailure("survival below 1.0 or pool overshoot")
    if report["nonfinite_logit_steps"]:
        raise SmokeFailure("NaN/Inf in the logits")
    return report


def one_chip(clock: CompileClock) -> None:
    t0 = clock.total
    check_flash_decode()
    check_conformance(["device"])
    _line(f"compile seconds, kernel check + conformance: "
          f"{clock.total - t0!r}")
    t0 = clock.total
    serve("device")
    _line(f"compile seconds, full-width serving: {clock.total - t0!r}")


def four_chips(clock: CompileClock) -> None:
    t0 = clock.total
    check_conformance(["sharded", "async-sharded"])
    _line(f"compile seconds, sharded conformance: {clock.total - t0!r}")
    t0 = clock.total
    ref = serve("device")
    gc.collect()              # free the one-chip engine's weights and cache
    got = serve("sharded", n_shards=4)
    _line(f"compile seconds, full-width serving x2: {clock.total - t0!r}")
    if got["sessions"] != ref["sessions"] or got["steps"] != ref["steps"]:
        raise SmokeFailure("sharded serving disagrees with the one-chip run: "
                           f"{got['sessions']} vs {ref['sessions']}")
    _line("sharded x4 matches the one-chip run: steps and per-session "
          "lengths and generated tokens equal")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU attached — JAX's default device is "
              f"{dev.platform!r} ({dev.device_kind}); this check runs only "
              "on a TPU", file=sys.stderr)
        return 2
    n_dev = len(jax.devices())
    if n_dev < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {n_dev}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro import compat
    cache = compat.enable_compile_cache()
    clock = CompileClock()
    _line(f"device: {dev.platform} {dev.device_kind!r} x{n_dev}; "
          f"compile cache {cache}")
    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else one_chip)(clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    _line(f"compile seconds, total: {clock.total!r}; wall seconds "
          f"{time.perf_counter() - t0!r}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n_dev}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
