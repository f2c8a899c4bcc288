"""KV manager: the peak over the window's steps of the engine's
`pages_in_use` over `pages_total`, read after each step (traced run)."""

LAYER = "KV manager"
UNIT = "frac"
MOVES = "out_tok_s"


def read(run):
    fr = [s.pages_in_use / s.pages_total for s in run.steps
          if s.pages_in_use is not None and s.pages_total]
    return max(fr) if fr else None
