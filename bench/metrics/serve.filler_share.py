"""serve.filler_share: filler lanes over dispatched lanes, in percent,
as the service counts them where it pads: the ``lanes`` (padded batch)
and ``served`` attributes of its ``serve.dispatch`` spans in the window
(repro.utils.spans)."""

import harness


def read(run):
    spans = harness.plugin("metrics", "_spans").inside(run)
    d = [s.attrs for s in spans or () if s.name == "serve.dispatch"]
    lanes = sum(a["lanes"] for a in d)
    if not lanes:
        return None
    return 100.0 * sum(a["lanes"] - a["served"] for a in d) / lanes
