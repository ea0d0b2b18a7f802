"""Device-busy time per engine step: the union of the device's operations
in the traced steps (averaged over chips), over their number."""
UNIT = "ms"


def read(rec):
    if not rec.trace or not rec.traced_steps:
        return None
    return rec.trace["busy_s"] / len(rec.traced_steps) * 1e3
