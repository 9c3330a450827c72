"""setup_s: process start to the first timed request (imports, device,
inputs, warm-up, compile or cache load), on the host clock."""


def read(run):
    return run.setup_s
