"""execute_ms.<cell>: median of the benchmark's span around each
PermanentSolver.execute call (executor, engine, device, host join)."""

import statistics


def read(run):
    spans = run.spans.durations("execute")
    return 1e3 * statistics.median(spans) if spans else None
