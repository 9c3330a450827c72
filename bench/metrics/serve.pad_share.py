"""serve.pad_share: filler lanes over dispatched lanes, in percent: each
dispatch of the service's log is padded up its power-of-two ladder."""


def read(run):
    lanes = run.counters.get("dispatched_lanes")
    if not lanes:
        return None
    return 100.0 * run.counters["filler_lanes"] / lanes
