"""Host time per engine step outside its jitted call: the span around
``Engine.step`` minus the span around the jitted step (ended when its
outputs are ready), averaged over the window's steps."""
UNIT = "ms"


def read(rec):
    sp = rec.spans
    if not sp.n_steps:
        return None
    return (sp.total["step"] - sp.total["jit_call"]) / sp.n_steps * 1e3
