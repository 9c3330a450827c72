"""Closed loop of ``PermanentSolver.plan_batch`` + ``execute`` calls, each
on ``batch`` fresh requests; a call that ends past the close is not
counted.

Traffic keys: ``batch``, ``check_sample``.
"""

from __future__ import annotations

import time

import numpy as np

import harness
import traffic
import workcount


class Driver:

    def __init__(self, run: harness.Run, devices):
        self.run = run
        self.source = harness.request_source(run.cell.config, run.seed)
        self.batch = int(run.cell.traffic["batch"])
        self.rng = traffic.stream(run.seed, "requests")

    def setup(self) -> None:
        from repro.core.solver import PermanentSolver
        self.solver = PermanentSolver(
            harness.solver_config(self.run.cell.config))
        # the one program this traffic runs, on requests of its own draw
        warm = self.source.draw(self.batch,
                                traffic.stream(self.run.seed, "warmup"))
        self.solver.execute(self.solver.plan_batch(list(warm)))

    def window(self) -> None:
        run, spans, solver = self.run, self.run.spans, self.solver
        self.done: list[tuple[np.ndarray, np.ndarray]] = []
        t0 = time.perf_counter()
        run.window_start = t0
        end = t0 + run.seconds
        ran = 0
        while time.perf_counter() < end:
            mats = self.source.draw(self.batch, self.rng)
            t = time.perf_counter()
            with spans.span("plan_batch"):
                plan = solver.plan_batch(list(mats))
            with spans.span("execute"):
                out = solver.execute(plan)
            t_end = time.perf_counter()
            ran += 1
            if t_end > end:
                break                    # finished past the close
            run.calls.append((t, t_end, len(mats)))
            self.done.append((mats, out))
        self.ran = ran

    def record(self) -> None:
        self.run.traced_flops = self.ran * self.batch * \
            workcount.ryser_flops(self.source.n, self.source.is_complex)

    def answers(self):
        """(attempted, failed, lost, [(matrix, value)] to compare)."""
        flat = [(M, v) for mats, out in self.done for M, v in zip(mats, out)]
        pick = harness.sample(len(flat),
                              int(self.run.cell.traffic["check_sample"]),
                              self.run.seed)
        return len(flat), 0, 0, [flat[j] for j in pick]

    def release(self) -> None:
        del self.solver
