"""ryser_gflops.<cell>: algorithmic Ryser work (bench/workcount.py) of
everything run while the profiler traced, over the device-busy seconds of
the trace summed over the chips used: a rate per chip, in Gflop/s."""


def read(run):
    if run.trace is None or run.trace.busy_total_s <= 0:
        return None
    return run.traced_flops / run.trace.busy_total_s / 1e9
