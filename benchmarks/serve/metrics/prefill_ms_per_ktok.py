"""Model step: device seconds of the prefill program in the traced window,
in ms per 1000 real prompt tokens of its traced calls (padding computed is
no token here)."""

from prefill_costs import PREFILL_MODULE, module_s, on_device, traced_prefill_steps

LAYER = "model step"
UNIT = "ms/ktok"
MOVES = "itl_p95_ms"


def read(run):
    if not on_device(run):
        return None
    tokens = sum(traced_prefill_steps(run).values())
    spent = module_s(run, PREFILL_MODULE)
    return 1e6 * spent / tokens if tokens and spent > 0 else None
