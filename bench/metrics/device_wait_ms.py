"""device_wait_ms.<cell>: median over the device programs of the window
of the program's span ``engine.wait`` (the host's block on its result
and the copy back, repro.utils.spans), in ms."""

import statistics

import harness


def read(run):
    spans = harness.plugin("metrics", "_spans").inside(run)
    waits = [s.seconds for s in spans or () if s.name == "engine.wait"]
    return 1e3 * statistics.median(waits) if waits else None
