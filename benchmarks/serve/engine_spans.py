"""The program's own step records over a run's window, for the metric
readers of the engine's spans and counters (`repro.serving.tracing`).

Each engine keeps one record per step, on the clock the window's bounds are
read from.  A program without that module, or a window in which no live
engine has a step, gives None: the metric is then left out."""

from __future__ import annotations


def window_records(run):
    """The step records that ended in the run's window, or None.  More than
    one engine with steps in the window is an error."""
    try:
        from repro.serving import tracing
    except ImportError:
        return None
    found = [recs for recs in (t.records(run.window_t0, run.window_t1)
                               for t in tracing.tracers()) if recs]
    if len(found) > 1:
        raise RuntimeError(f"{len(found)} engines have steps in the window; a run holds one")
    return found[0] if found else None


def decode_only(recs):
    """Steps that admitted nothing and committed tokens."""
    return [r for r in recs if not r.admitted and r.emitted > 0]


def mean_ms(seconds) -> float | None:
    seconds = list(seconds)
    return 1e3 * sum(seconds) / len(seconds) if seconds else None
