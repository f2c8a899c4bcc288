"""Generated tokens committed in the window over the window's seconds."""

from readings import window_tokens

UNIT = "tokens/s"


def read(run):
    n = sum(1 for _ in window_tokens(run))
    return n / run.window_s if n and run.window_s > 0 else None
