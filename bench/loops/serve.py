"""Open loop into ``PermanentService``: Poisson arrivals at the traffic's
fixed ``rate_hz``, one thread that submits each request at its scheduled
arrival (its ticket backdated to it) and steps the service in between.

Traffic keys: ``rate_hz``, ``lane`` (optional), ``check_sample``.
"""

from __future__ import annotations

import math
import statistics
import time
from collections import deque

import harness
import traffic
import workcount


class Driver:

    def __init__(self, run: harness.Run, devices):
        self.run = run
        cfg, tr = run.cell.config, run.cell.traffic
        self.source = harness.request_source(cfg, run.seed)
        self.sched = traffic.arrivals(float(tr["rate_hz"]), run.seconds,
                                      traffic.stream(run.seed, "arrivals"))
        self.mats = self.source.draw(len(self.sched),
                                     traffic.stream(run.seed, "requests"))
        self.lane = tr.get("lane")

    def setup(self) -> None:
        from repro.serve import (PermanentService, ServiceConfig,
                                 quantized_batches, warmup)
        scfg = ServiceConfig(log_every_s=float("inf"),
                             **self.run.cell.config.get("service", {}))
        solver_cfg = harness.solver_config(self.run.cell.config)
        self.service = PermanentService(solver_cfg, scfg, log=None,
                                        clock=time.perf_counter)
        # warm exactly the programs this traffic dispatches: its one
        # (n, is_complex) bucket at every padded batch size
        ladder = quantized_batches(scfg.max_batch) \
            if scfg.quantize_buckets else (scfg.max_batch,)
        warmup(solver_cfg, [(self.source.n, b, self.source.is_complex)
                            for b in ladder])

    def window(self) -> None:
        run, svc, spans = self.run, self.service, self.run.spans
        sched, mats = self.sched, self.mats
        t0 = time.perf_counter()
        run.window_start = t0
        end = t0 + run.seconds
        self.tickets, self.step_start = [], {}
        self.lateness = []
        pending: deque = deque()
        self.depth: list[int] = []       # queued requests, every 0.5 s
        next_sample = t0
        i, count = 0, len(sched)
        while True:
            now = time.perf_counter()
            if now >= next_sample:
                self.depth.append(len(pending))
                next_sample += 0.5
            while i < count and t0 + sched[i] <= now:
                due = t0 + sched[i]
                tk = svc.submit(mats[i], lane=self.lane, t_submit=due)
                self.lateness.append(now - due)
                self.tickets.append(tk)
                pending.append(tk)
                i += 1
            if i >= count and now >= end:
                break
            if not self._step(pending) and i < count:
                wait = t0 + sched[i] - time.perf_counter()
                if wait > 0:
                    with spans.span("wait"):
                        time.sleep(min(wait, 1e-3))
        self.depth.append(len(pending))
        # answers due in the window are waited for (a minute at most);
        # their latency counts the wait
        limit = time.perf_counter() + 60.0
        while pending and time.perf_counter() < limit:
            self._step(pending)
        self.unanswered = len(pending)

    def _step(self, pending: deque) -> int:
        t = time.perf_counter()
        with self.run.spans.span("step"):
            served = self.service.step()
        for _ in range(len(pending)):
            tk = pending.popleft()
            if tk.done or tk.shed:
                self.step_start[tk.id] = t
            else:
                pending.append(tk)
        return served

    def record(self) -> None:
        run = self.run
        for tk, due in zip(self.tickets, self.sched):
            arrival = run.window_start + due
            run.requests.append((arrival, self.step_start.get(tk.id, math.inf),
                                 tk.t_done if tk.done else math.inf,
                                 tk.done))
        served = [s for _, s, _, _ in self.service.dispatch_log]
        dts = [dt for _, _, dt, _ in self.service.dispatch_log]
        from repro.serve import quantized_batches
        ladder = quantized_batches(self.service.scfg.max_batch)
        lanes = [next(b for b in ladder if b >= s) for s in served]
        run.counters.update(
            dispatches=len(served), dispatched_lanes=sum(lanes),
            filler_lanes=sum(lanes) - sum(served),
            shed=sum(1 for tk in self.tickets if tk.shed),
            queue_depth_max=max(self.depth),
            queue_depth_end=self.depth[-1],
            dispatch_ms_p50=1e3 * statistics.median(dts),
            dispatch_ms_max=1e3 * max(dts),
            generator_late_ms_p50=1e3 * statistics.median(self.lateness),
            generator_late_ms_max=1e3 * max(self.lateness))
        run.traced_flops = len(self.tickets) * workcount.ryser_flops(
            self.source.n, self.source.is_complex)

    def answers(self):
        """(attempted, failed, lost, [(matrix, value)] to compare)."""
        done = [(k, tk) for k, tk in enumerate(self.tickets) if tk.done]
        pick = harness.sample(len(done),
                              int(self.run.cell.traffic["check_sample"]),
                              self.run.seed)
        failed = sum(1 for tk in self.tickets if not tk.done)
        return (len(self.tickets), failed, self.unanswered,
                [(self.mats[done[j][0]], done[j][1].value) for j in pick])

    def release(self) -> None:
        del self.service
