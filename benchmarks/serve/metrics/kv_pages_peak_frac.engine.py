"""KV manager: the peak over the window's steps of the engine's
`pages_in_use` over `pages_total`, recorded by the engine at each step's
end."""

from engine_spans import window_records

LAYER = "KV manager"
UNIT = "frac"
MOVES = "out_tok_s"


def read(run):
    fr = [r.pages_in_use / r.pages_total for r in window_records(run) or ()
          if r.pages_in_use is not None and r.pages_total]
    return max(fr) if fr else None
