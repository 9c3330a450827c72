"""serve.queue_wait_ms: median time from a request's scheduled arrival
to the start of the service step() that resolved it (the benchmark times
its own step() calls)."""

import statistics


def read(run):
    waits = [start - arrival for arrival, start, _, answered
             in run.requests if answered]
    return 1e3 * statistics.median(waits) if waits else None
