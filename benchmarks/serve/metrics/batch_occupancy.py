"""Scheduler: mean over the window's decode dispatches of active slots over
slots."""

LAYER = "scheduler"
UNIT = "frac"
MOVES = "out_tok_s"


def read(run):
    rows = [c.rows for c in run.calls if c.phase == "decode"]
    return sum(rows) / (len(rows) * run.slots) if rows else None
