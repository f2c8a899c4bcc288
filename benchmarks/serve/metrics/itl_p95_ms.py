"""95th percentile of every gap between consecutive tokens of a request,
both tokens in the window."""

from readings import percentile

UNIT = "ms"


def read(run):
    gaps = []
    for r in run.requests:
        ts = r.token_times
        gaps += [1e3 * (b - a) for a, b in zip(ts, ts[1:])
                 if run.in_window(a) and run.in_window(b)]
    return percentile(gaps, 95)
