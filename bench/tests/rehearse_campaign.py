"""The campaign cell at a tiny size on the CPU, on four virtual devices.

The cell runs only on four TPU chips at n = 31; this drives the same
``harness.run_cell`` with its configuration cut to n = 10 (and the
planner's threshold and slice geometry cut with it, so that n = 10
campaigns in 8 slices), on ``--xla_force_host_platform_device_count=4``,
once per case, all in one process:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python bench/tests/rehearse_campaign.py sound traced control ...

Cases: ``sound`` and ``traced`` (the program as it is, the profiler off
and on), and three faults planted under the harness: ``control`` (the
float32 reference of ``bench/campaign_control.py`` in ``execute``'s
place),
``slice_left_out`` (one slice's partials left out of the reduce) and
``stale_answer`` (every job answers with the previous job's value).
Prints one JSON object: each case's result line, its counters and the
device count.
"""

from __future__ import annotations

import json
import os
import sys
import time
from contextlib import ExitStack, contextmanager

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402

CELL = "dense_n31.campaign4"
TINY_CONFIG = {"n": 10, "solver": {
    "precision": "dq_acc", "campaign_checkpoint": "job.npz",
    "campaign_threshold": 1.0, "campaign_slices": 8, "campaign_lanes": 8}}
SECONDS = 1.0


@contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _control(stack: ExitStack) -> None:
    import campaign_control
    stack.enter_context(campaign_control.in_program_place())


def _slice_left_out(stack: ExitStack) -> None:
    from repro.core.resume import JobState
    reduce = JobState.reduce

    def without_slice_0(self):
        kept = self.done[0]
        self.done[0] = False
        try:
            return reduce(self)
        finally:
            self.done[0] = kept
    stack.enter_context(_patched(JobState, "reduce", without_slice_0))


def _stale_answer(stack: ExitStack) -> None:
    import rehearse
    from repro.core.solver import PermanentSolver
    stack.enter_context(_patched(PermanentSolver, "execute",
                                 rehearse.faulty_execute("stale_answer")))


PLANT = {"sound": None, "traced": None, "control": _control,
         "slice_left_out": _slice_left_out, "stale_answer": _stale_answer}


def run_case(case: str, seed: int) -> dict:
    import jax
    cell = harness.load_cell(CELL)
    cell.config = {**cell.config, **TINY_CONFIG}
    logs: list[str] = []
    with ExitStack() as stack:
        if PLANT[case] is not None:
            PLANT[case](stack)
        out = harness.run_cell(cell, seed, SECONDS, case == "traced",
                               jax.devices(), t_start=time.perf_counter(),
                               log=logs.append)
    counters = next(json.loads(s.split(": ", 1)[1]) for s in logs
                    if s.startswith("counters: "))
    return {"result": out, "counters": counters}


def main(argv=None) -> int:
    import jax
    jax.config.update("jax_enable_x64", True)
    cases = (argv if argv is not None else sys.argv[1:]) or list(PLANT)
    out = {"devices": jax.device_count()}
    for k, case in enumerate(cases):
        out[case] = run_case(case, 2 ** 31 + 101 + k)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
