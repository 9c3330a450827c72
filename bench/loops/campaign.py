"""Closed loop of back-to-back campaign jobs: each is
``PermanentSolver.plan`` + ``execute`` of one fresh matrix, which the
planner routes to the step-space campaign by its own threshold, run on a
mesh of the cell's chips and checkpointed after every wave under the
configuration's one checkpoint path, placed in a directory of the run's
own.  A job that ends past the close is not counted.

Traffic keys: ``check_sample``.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np

import harness
import traffic
import workcount


class Driver:

    def __init__(self, run: harness.Run, devices):
        self.run = run
        self.source = harness.request_source(run.cell.config, run.seed)
        self.rng = traffic.stream(run.seed, "requests")
        self.devices = list(devices[:run.cell.chips])

    def setup(self) -> None:
        from jax.sharding import Mesh
        from repro.core.planner import ROUTE_CAMPAIGN
        from repro.core.solver import PermanentSolver
        cfg = harness.solver_config(self.run.cell.config)
        self.ckpt_dir = tempfile.mkdtemp(prefix="bench_campaign_")
        self.ckpt = os.path.join(self.ckpt_dir, cfg.campaign_checkpoint)
        self.solver = PermanentSolver(
            cfg.replace(campaign_checkpoint=self.ckpt),
            distributed_ctx=Mesh(np.array(self.devices), ("step",)))
        # the wave program, on a matrix of its own draw
        warm = self.source.draw(1, traffic.stream(self.run.seed, "warmup"))[0]
        plan = self.solver.plan(warm)
        routes = [leaf.route for leaf in plan.leaves]
        if routes != [ROUTE_CAMPAIGN]:
            raise RuntimeError(f"n={self.source.n} planned as {routes}, "
                               f"not one {ROUTE_CAMPAIGN} leaf")
        self.solver.execute(plan)
        self.before = self._campaign_counters()

    def _campaign_counters(self) -> dict:
        return dict(self.solver.stats().get("campaign", {}))

    def window(self) -> None:
        run, spans, solver = self.run, self.run.spans, self.solver
        self.done: list[tuple[np.ndarray, float]] = []
        t0 = time.perf_counter()
        run.window_start = t0
        end = t0 + run.seconds
        ran = 0
        while time.perf_counter() < end:
            A = self.source.draw(1, self.rng)[0]
            t = time.perf_counter()
            with spans.span("plan"):
                plan = solver.plan(A)
            with spans.span("execute"):
                value = np.asarray(solver.execute(plan)).item()
            t_end = time.perf_counter()
            ran += 1
            if t_end > end:
                break                    # finished past the close
            run.calls.append((t, t_end, 1))
            self.done.append((A, value))
        self.ran = ran

    def record(self) -> None:
        run = self.run
        run.traced_flops = self.ran * workcount.ryser_flops(
            self.source.n, self.source.is_complex)
        after = self._campaign_counters()
        run.counters.update(
            jobs=len(self.done), jobs_run=self.ran,
            **{f"campaign_{k}": v - self.before.get(k, 0)
               for k, v in after.items()})

    def answers(self):
        """(attempted, failed, lost, [(matrix, value)] to compare)."""
        pick = harness.sample(len(self.done),
                              int(self.run.cell.traffic["check_sample"]),
                              self.run.seed)
        return len(self.done), 0, 0, [self.done[j] for j in pick]

    def release(self) -> None:
        del self.solver
        shutil.rmtree(self.ckpt_dir, ignore_errors=True)
