"""Share of the jitted step's device time spent in in-step enforcement:
the traced operations of the step module whose compiled HLO
``op_name`` lies under the named scopes ``agentcgroup.schedule``,
``agentcgroup.charge`` or ``agentcgroup.gate``, over the device time of
all the module's operations (``bench/program_trace.py``)."""
from bench.program_trace import ENFORCE

UNIT = "%"


def read(rec):
    scopes = getattr(rec, "scopes", None)
    if not scopes:
        return None
    total = sum(scopes.values())
    enforce = sum(scopes.get(k, 0.0) for k in ENFORCE)
    if total <= 0 or enforce <= 0:
        return None
    return enforce / total * 100
