"""plan_ms.<cell>: median of the benchmark's span around each
PermanentSolver.plan_batch call (planner: DM/FM, row scaling, hashing)."""

import statistics


def read(run):
    spans = run.spans.durations("plan_batch")
    return 1e3 * statistics.median(spans) if spans else None
