"""Scheduler: the engine's `decode_rows` over its `slots`, over the window's
steps with a decode dispatch (counted inside the program)."""

from engine_spans import window_records

LAYER = "scheduler"
UNIT = "frac"
MOVES = "out_tok_s"


def read(run):
    recs = [r for r in window_records(run) or () if r.decode_rows > 0]
    return sum(r.decode_rows for r in recs) / sum(r.slots for r in recs) if recs else None
