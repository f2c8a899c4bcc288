"""Scheduler: host time of admission in the window's admitting steps: mean
of the engine's `engine.admit` span (queue scan, page plans, the prefill's
dispatch and scatter; the prefill's device time lands in the step's
`engine.wait`)."""

from engine_spans import mean_ms, window_records

LAYER = "scheduler"
UNIT = "ms"
MOVES = "itl_p95_ms"


def read(run):
    recs = window_records(run)
    if recs is None:
        return None
    return mean_ms(r.span_s("engine.admit") for r in recs if r.admitted)
