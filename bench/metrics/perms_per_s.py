"""perms_per_s: permanents completed in the window over the time from
the window's start to the end of the last call completed inside it."""


def read(run):
    if not run.calls:
        return None
    return sum(p for _, _, p in run.calls) / (run.calls[-1][1]
                                              - run.window_start)
