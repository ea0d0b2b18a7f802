"""Model FLOPs of every token the window's steps processed (fed and
generated: each granted slot, attending its live length), per second of
the window, over chips x peak bf16 FLOP/s."""
from bench.costs import token_flops

UNIT = "%"


def read(rec):
    if not rec.spans.steps or not rec.peak:
        return None
    flops = sum(token_flops(rec.model, (lengths + 1)[granted])
                for lengths, granted in rec.spans.steps)
    lo, hi = rec.window
    return flops / (hi - lo) / (rec.chips * rec.peak["flops_bf16"]) * 100
