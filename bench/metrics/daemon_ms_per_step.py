"""Host time per engine step in ``Engine._daemon`` (snapshot, freeze and
thaw, admission), averaged over the window's steps."""
UNIT = "ms"


def read(rec):
    sp = rec.spans
    return sp.total["daemon"] / sp.n_steps * 1e3 if sp.n_steps else None
