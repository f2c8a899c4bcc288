"""Model step: mean of the engine's `engine.step` span over the window's
steps that admitted nothing and committed tokens (`decode_step_ms`'s steps,
timed inside the program)."""

from engine_spans import decode_only, mean_ms, window_records

LAYER = "model step"
UNIT = "ms"
MOVES = "itl_p95_ms"


def read(run):
    recs = window_records(run)
    if recs is None:
        return None
    return mean_ms(r.seconds for r in decode_only(recs))
