"""A benchmark run at a tiny size on the CPU, for the tests.

The command (``bench/run.py``) only runs on a TPU at the cells' own
sizes; this drives the same ``harness.run_cell`` with each cell's
configuration and traffic cut to seconds of CPU work, and plants faults
in the timed path underneath it.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402

# what each cell's tiny copy changes: configuration, traffic, seconds;
# every answer of the window is compared
TINY = {
    "boson_c20.serve": ({"n": 6, "modes": 36,
                         "service": {"max_batch": 4}},
                        {"rate_hz": 150.0, "check_sample": 1000}, 1.0),
    "boson_c20.batch": ({"n": 6, "modes": 36}, {"batch": 8,
                                                "check_sample": 1000}, 0.5),
}


def tiny_cell(name: str) -> tuple[harness.Cell, float]:
    cell = harness.load_cell(name)
    cfg, tr, seconds = TINY[name]
    cell.config = {**cell.config, **cfg}
    cell.traffic = {**cell.traffic, **tr}
    return cell, seconds


def run_tiny(name: str, seed: int = 2 ** 31 + 11, trace: bool = False,
             log=lambda s: None) -> dict:
    import jax
    jax.config.update("jax_enable_x64", True)
    cell, seconds = tiny_cell(name)
    return harness.run_cell(cell, seed, seconds, trace, jax.devices(),
                            t_start=time.perf_counter(), log=log)


def _altered(out, last):
    """An answer altered where it is produced."""
    return out * (1 + 1e-7)


def _half_batch_left_out(out, last):
    """Only the first half of the batch (rounded down) computed; the
    lanes left out read 0."""
    kept = out.copy()
    kept[len(out) // 2:] = 0
    return kept


def _stale_answer(out, last):
    """Every call after the first answers with the previous call's
    values, as a state left unchanged would."""
    return out if last is None else np.resize(last, out.shape)


FAULTS = {"altered_answer": _altered,
          "half_batch_left_out": _half_batch_left_out,
          "stale_answer": _stale_answer}


def faulty_execute(fault: str):
    """``PermanentSolver.execute`` with ``fault`` planted in its output."""
    from repro.core.solver import PermanentSolver
    execute, plant = PermanentSolver.execute, FAULTS[fault]
    last = [None]

    def broken(self, plan, **kw):
        out = plant(np.asarray(execute(self, plan, **kw)), last[0])
        last[0] = out
        return out
    return broken
