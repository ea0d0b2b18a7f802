"""``correct`` comes out false when the timed path is broken underneath,
once for each fault a served cell can have on one chip, in the model step
and in the control plane, and the lower-precision control reads above the
program. The harness's look for a chip is skipped; the rest of a run is
driven as on the chip."""
import json

import jax.numpy as jnp
import pytest

from repro.core import cgroup, progs
from repro.models import model as M
from repro.serving import engine as E

from bench import run
from bench.testing import tiny_cell


def state_unchanged(monkeypatch):
    real = M.decode_step

    def step(cfg, params, state, tokens, lengths, *, perf):
        logits, _ = real(cfg, params, state, tokens, lengths, perf=perf)
        return logits, state
    monkeypatch.setattr(M, "decode_step", step)


def half_batch_left_out(monkeypatch):
    real = M.decode_step

    def step(cfg, params, state, tokens, lengths, *, perf):
        logits, new = real(cfg, params, state, tokens, lengths, perf=perf)
        h = logits.shape[0] // 2
        return logits.at[h:].set(logits[:logits.shape[0] - h]), new
    monkeypatch.setattr(M, "decode_step", step)


def token_altered(monkeypatch):
    real = E.sample

    def sample(logits, key, *, temperature=0.0, top_k=0):
        return (real(logits, key, temperature=temperature) + 1) % logits.shape[-1]
    monkeypatch.setattr(E, "sample", sample)


def denial_dropped(monkeypatch):
    real = cgroup.DeviceView.charge

    def charge(self, state, dom, amt, step):
        state, granted, stalled = real(self, state, dom, amt, step)
        return state, granted | (dom >= 0), stalled
    monkeypatch.setattr(cgroup.DeviceView, "charge", charge)


def high_protection_dropped(monkeypatch):
    monkeypatch.setattr(E, "DomainSpec",
                        lambda **kw: cgroup.DomainSpec(**dict(kw, low=0)))


def memory_high_ignored(monkeypatch):
    monkeypatch.setattr(progs.GraduatedThrottleProgram, "on_over_high",
                        lambda self, view, req, over_frac, protected:
                        jnp.float32(0.0))


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out,
                                   token_altered])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = run.run_cell("tiny.fleet", 9, 1.0, False, loaded=tiny_cell(),
                       require_tpu=False)
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
    assert out["correct"] is False


@pytest.mark.parametrize("fault", [denial_dropped, high_protection_dropped,
                                   memory_high_ignored])
def test_broken_control_plane_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = run.run_cell("tiny.fleet", 9, 1.0, False, loaded=tiny_cell(),
                       require_tpu=False)
    assert (out["checks"]["grant_mismatches"]["value"]
            + out["checks"]["domain_state_mismatches"]["value"]) > 0
    assert out["correct"] is False


def test_sound_run_is_correct(capsys):
    out = run.run_cell("tiny.fleet", 9, 1.0, False, loaded=tiny_cell(),
                       require_tpu=False)
    assert out["correct"] is True, out["checks"]
    # the control plane compared had denials, throttles and freezes to get
    # right
    err = capsys.readouterr().err.splitlines()

    def logged(head):
        line = [x for x in err if x.startswith(f"[bench] {head}")][0]
        return json.loads(line.split(": ", 1)[1])
    counts = logged("control plane against the reference")
    assert counts["denied"] > 0 and counts["throttled"] > 0
    assert logged("engine")["freezes"] > 0


def test_lower_precision_control_reads_above_the_program(monkeypatch):
    cell = tiny_cell()
    cell[2]["check"] = {"max_sessions": 3, "min_served_tokens": 200}
    out = run.run_cell("tiny.fleet", 4, 1.5, False, loaded=cell,
                       require_tpu=False, controls=("int8", "fp8"))
    r = out["readings"]
    limit = out["checks"]["logit_gap"]["limit"]
    assert r["program"] <= limit < r["fp8"]
