"""Scheduler: the share of the prefill's computed token rows that are
padding, over the window's steps: 1 - the engine's `prefill_tokens` (real
prompt tokens) over its `prefill_padded_tokens` (sequences x padded
length).  A program that does not count padded rows gives nothing."""

from engine_spans import window_records

LAYER = "scheduler"
UNIT = "frac"
MOVES = "out_tok_s"


def read(run):
    recs = window_records(run)
    if not recs or not hasattr(recs[0], "prefill_padded_tokens"):
        return None
    padded = sum(r.prefill_padded_tokens for r in recs)
    return 1.0 - sum(r.prefill_tokens for r in recs) / padded if padded else None
