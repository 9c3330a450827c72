"""serve.queue_ms: median of the service's ``serve.queue`` spans (from
the submit call to the start of the dispatch that took the request,
repro.utils.spans) that start in the window, in ms."""

import statistics

import harness


def read(run):
    spans = harness.plugin("metrics", "_spans").inside(run)
    queued = [s.seconds for s in spans or () if s.name == "serve.queue"]
    return 1e3 * statistics.median(queued) if queued else None
