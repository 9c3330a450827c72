"""The program's own spans (``repro.utils.spans``) for the readers of the
metrics read from them.

Both sides time on ``time.perf_counter``.  Each function returns None
where the program records no spans (a program older than the span
recorder) or where the ring lost spans that may have started in the
window: it has dropped some, and its oldest starts after the window
opened.
"""


def after(run):
    """Spans that start at or after the window opens, by start."""
    try:
        from repro.utils import spans
    except ImportError:
        return None
    ring = spans.recent()
    if spans.dropped() and (not ring or ring[0].t0 > run.window_start):
        return None
    return sorted((s for s in ring if s.t0 >= run.window_start),
                  key=lambda s: s.t0)


def inside(run):
    """Spans that start in the window [start, start + seconds]."""
    got = after(run)
    if got is None:
        return None
    end = run.window_start + run.seconds
    return [s for s in got if s.t0 <= end]
