"""campaign.wave_host_ms: median over the campaign waves that start in
the window of the wave's span ``campaign.wave`` less its ``engine.wait``
(the host's block on the wave's partials), in ms: the host's own time in
a wave, its launch, bookkeeping and checkpoint (repro.utils.spans)."""

import statistics
from collections import defaultdict

import harness


def read(run):
    spans = harness.plugin("metrics", "_spans").after(run)
    if not spans:
        return None
    end = run.window_start + run.seconds
    waited = defaultdict(float)
    for s in spans:
        if s.name == "engine.wait":
            waited[s.parent] += s.seconds
    host = [s.seconds - waited[s.id] for s in spans
            if s.name == "campaign.wave" and s.t0 <= end and s.id in waited]
    return 1e3 * statistics.median(host) if host else None
