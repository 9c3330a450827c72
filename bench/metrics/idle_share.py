"""idle_share.<cell>: device idle share of the traced window, in percent:
1 - (union of the intervals in which a program ran on the chip / window),
averaged over the chips used."""


def read(run):
    share = None if run.trace is None else run.trace.idle_share
    return None if share is None else 100.0 * share
