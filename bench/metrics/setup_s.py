"""Process start to the first step of the window: weights, engine,
compilation (or the compile cache), warm-up steps."""
UNIT = "s"


def read(rec):
    return rec.setup_s
