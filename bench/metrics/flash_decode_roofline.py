"""Share of its roofline the ``flash_decode`` kernel reaches in the traced
steps: the least time the bytes and FLOPs it needs for each slot's live
length could take at peak (the model family's ``flash_decode_cost``), over
its device time in the trace. The kernel runs once per layer per step over
every slot."""
from bench.costs import roofline_seconds
from bench.models import family

UNIT = "%"
KERNEL = "flash_decode"


def read(rec):
    if not rec.trace or not rec.traced_steps or not rec.peak:
        return None
    ops = rec.trace["op_s"]
    n_dev = rec.chips
    t_kernel = sum(v for n, v in ops.items() if KERNEL in n) * n_dev
    if t_kernel <= 0:
        return None
    fam = family(rec.model)
    layers = fam.dims(rec.model)["layers"]
    ideal = 0.0
    for lengths, _ in rec.traced_steps:
        for part in lengths.reshape(n_dev, -1):
            ideal += layers * roofline_seconds(
                *fam.flash_decode_cost(rec.model, part + 1), rec.peak)[0]
    return ideal / t_kernel * 100
