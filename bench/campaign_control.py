"""The float32 control of the campaign cell (``dense_n31.campaign4``).

``bench/control.py`` puts the plain reference, in float32, in
``PermanentSolver.execute``'s place for the matrices that
``plan_batch`` saw.  A campaign job is planned with
``PermanentSolver.plan``, so while this control is in place a job's plan
is made through ``plan_batch``.  It gives the upper reading behind the
limit of the ``dense_rec`` configuration's ``max_rel_err``: at the
cell's own size (n = 31), several seeds in one process, each printing
one JSON line that has to read ``correct`` false.  No program runs on
the cell's mesh, so one chip will do:

    python3 bench/campaign_control.py --seconds 4 <seed> ...
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from contextlib import contextmanager

import run as bench_run  # puts bench/ and src/ on the path

import control  # noqa: E402
import harness  # noqa: E402

CELL = "dense_n31.campaign4"


@contextmanager
def in_program_place(device=None):
    """The control in the campaign loop's ``execute``, on ``device``
    (default: the host CPU)."""
    from repro.core.solver import PermanentSolver
    plan = PermanentSolver.plan
    with control.in_program_place(device):
        PermanentSolver.plan = lambda self, A: self.plan_batch([A])
        try:
            yield
        finally:
            PermanentSolver.plan = plan


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args(argv)

    bench_run.prepare_env()
    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.serve import enable_compile_cache
    enable_compile_cache(harness.CACHE_DIR)
    devices = jax.devices()
    cell = harness.load_cell(CELL)
    for seed in args.seeds:
        with in_program_place(devices[0]):
            out = harness.run_cell(cell, seed, args.seconds, False, devices,
                                   t_start=time.perf_counter(),
                                   log=lambda s: None)
        print(json.dumps({"cell": cell.name, "seed": seed, "path": "control",
                          "device": devices[0].device_kind,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
