"""latency_p95_ms: 95th percentile (nearest rank) of admission-to-result
latency over every request due in the window, each timed from its
scheduled arrival; a shed or unanswered request counts as infinitely
late."""

import math


def read(run):
    if not run.requests:
        return None
    lat = sorted(done - arrival for arrival, _, done, _ in run.requests)
    return 1e3 * lat[math.ceil(0.95 * len(lat)) - 1]
