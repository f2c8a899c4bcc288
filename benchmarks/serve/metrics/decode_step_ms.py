"""Model step: total time of the window's steps that admitted nothing, over
their count (a step ends on the engine's own sync of its tokens)."""

LAYER = "model step"
UNIT = "ms"
MOVES = "itl_p95_ms"


def read(run):
    ts = [s.t1 - s.t0 for s in run.steps if s.admitted == 0 and s.emitted > 0]
    return 1e3 * sum(ts) / len(ts) if ts else None
