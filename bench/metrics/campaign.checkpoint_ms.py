"""campaign.checkpoint_ms: median of the program's ``campaign.checkpoint``
spans (one ``JobState.save`` after a wave, repro.utils.spans) that start
in the window, in ms."""

import statistics

import harness


def read(run):
    spans = harness.plugin("metrics", "_spans").inside(run)
    saves = [s.seconds for s in spans or ()
             if s.name == "campaign.checkpoint"]
    return 1e3 * statistics.median(saves) if saves else None
