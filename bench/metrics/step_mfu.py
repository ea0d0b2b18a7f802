"""Model FLOPs of every token the window's steps processed (fed and
generated: each granted slot, attending its live length), per second of
the window, over chips x peak bf16 FLOP/s. The FLOPs are the model
family's (``bench/models``)."""
from bench.models import family

UNIT = "%"


def read(rec):
    if not rec.spans.steps or not rec.peak:
        return None
    token_flops = family(rec.model).token_flops
    flops = sum(token_flops(rec.model, (lengths + 1)[granted])
                for lengths, granted in rec.spans.steps)
    lo, hi = rec.window
    return flops / (hi - lo) / (rec.chips * rec.peak["flops_bf16"]) * 100
