"""The readings that the limit of ``correct`` is set from.

The control is the plain reference put in the program's place and
computed one precision below the configuration's: float32 (complex64)
where the program runs float64.  :func:`in_program_place` makes every
``PermanentSolver.execute`` (the service's dispatches included) return
the control's values for the matrices its plan was built from, so a run
of the harness with it in place goes through the cell's own loop, sample
and comparison, and has to come out not correct.  The benchmark's runs
never use it; the tests do at a tiny size, and on the chip at each
cell's own size, several seeds in one process:

    python3 bench/control.py --workload <cell> --seconds <s> <seed> ...

With ``--program`` the same loop reads the program itself instead (its
sound runs give the lower reading), and ``--precision <mode>`` runs the
program at another of its precisions.  Each run prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from contextlib import contextmanager, nullcontext

import numpy as np

import run as bench_run  # puts bench/ and src/ on the path

import harness  # noqa: E402
import reference  # noqa: E402

__all__ = ["in_program_place", "low_dtype"]


def low_dtype(matrix) -> type:
    """The control's precision for a matrix the program computes in
    float64."""
    return np.complex64 if np.iscomplexobj(matrix) else np.float32


@contextmanager
def in_program_place(device=None):
    """The control in ``PermanentSolver.execute``'s place, on ``device``
    (default: the host CPU)."""
    from repro.core.solver import PermanentSolver
    plan_batch, execute = PermanentSolver.plan_batch, PermanentSolver.execute
    inputs: dict[int, list] = {}

    def plan_batch_keeping_inputs(self, As):
        As = [np.asarray(A) for A in As]
        plan = plan_batch(self, As)
        inputs[id(plan)] = As
        return plan

    def execute_control(self, plan, **kw):
        if kw.get("return_report"):
            raise NotImplementedError("the control returns values only")
        mats = inputs.pop(id(plan))
        values = [reference.permanent(M, dtype=low_dtype(M), device=device)
                  for M in mats]
        return np.asarray(values, np.complex128 if plan.is_complex
                          else np.float64)

    PermanentSolver.plan_batch = plan_batch_keeping_inputs
    PermanentSolver.execute = execute_control
    try:
        yield
    finally:
        PermanentSolver.plan_batch = plan_batch
        PermanentSolver.execute = execute


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program", action="store_true",
                    help="read the program, not the control")
    ap.add_argument("--precision",
                    help="run the program at this precision mode")
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args(argv)

    bench_run.prepare_env()
    cell = harness.load_cell(args.workload)
    import jax
    jax.config.update("jax_enable_x64", True)
    try:
        devices = bench_run.require_tpu(cell.chips)
    except (bench_run.NoChip, LookupError) as e:
        print(f"control: {e}; nothing run", file=sys.stderr)
        return 2
    from repro.serve import enable_compile_cache
    enable_compile_cache(harness.CACHE_DIR)
    if args.precision:
        cell.config = {**cell.config, "solver": {
            **cell.config.get("solver", {}), "precision": args.precision}}
    path = ("program:" + cell.config.get("solver", {}).get(
        "precision", "default")) if args.program else "control"
    for seed in args.seeds:
        ctx = nullcontext() if args.program else in_program_place(devices[0])
        with ctx:
            out = harness.run_cell(cell, seed, args.seconds, False, devices,
                                   t_start=time.perf_counter(),
                                   log=lambda s: None)
        print(json.dumps({"cell": cell.name, "seed": seed, "path": path,
                          "device": devices[0].device_kind,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
