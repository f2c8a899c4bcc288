"""Scheduler: host work per decode-only step that the device waits out:
mean over the window's steps that admitted nothing and committed tokens of
the engine's `engine.step` span less its `engine.wait` spans."""

from engine_spans import decode_only, mean_ms, window_records

LAYER = "scheduler"
UNIT = "ms"
MOVES = "out_tok_s"


def read(run):
    recs = window_records(run)
    if recs is None:
        return None
    return mean_ms(r.seconds - r.span_s("engine.wait") for r in decode_only(recs))
