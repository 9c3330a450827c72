"""`PermanentSolver`: the stateful plan/execute session object.

The paper's Alg. 4 is a pipeline; this module exposes it as a lifecycle
instead of a free function:

    config = SolverConfig(precision="dq_acc", backend="jnp")
    solver = PermanentSolver(config)

    plan = solver.plan(A)            # type sniff + DM/FM + routing; no
    print(plan.summary())            # device work -- inspect or serialize
    value = solver.execute(plan)     # dispatch through the backend registry

    plans = solver.plan_batch(As)    # bucketed batch plan ...
    values = solver.execute(plans)   # ... one device program per bucket

**Plan** (`plan` / `plan_batch`) is pure and deterministic: equal inputs
produce ``==`` plans, and ``plan.to_json()`` serializes every dispatch
decision (leaves, routes, buckets, cost estimate) for offline inspection.
**Execute** walks the plan through ``core.executor``'s backend registry
and the solver's content-hash :class:`~repro.core.cache.ResultCache` --
repeated post-DM/FM leaves (boson-sampling pipelines resample overlapping
submatrices) skip the device entirely; ``solver.stats()`` reports the
hit/miss and dispatch accounting.

**Queue** (`submit` / `flush` / `poll`) decouples request arrival from
batch dispatch: submitted matrices accumulate in size-keyed buckets and
are flushed through a bucketed batch plan when a bucket reaches
``config.queue_max_batch`` (size trigger) or its oldest request ages past
``config.queue_max_delay_s`` (deadline trigger, checked on ``submit``/
``poll``).  ``submit`` returns a :class:`PermanentRequest` future whose
``result()`` forces a flush if needed -- mixed traffic fills batches
instead of fragmenting them (ROADMAP: async request queue).

The legacy ``engine.permanent`` / ``engine.permanent_batch`` free
functions are thin stateless wrappers over this machinery.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Sequence

import numpy as np

from ..utils.spans import span
from .cache import ResultCache
from .executor import ExecStats, LeafTiming, execute_plan
from .planner import ExecutionPlan, PermanentReport, SolverConfig, build_plan

__all__ = ["PermanentSolver", "PermanentRequest", "SolverConfig",
           "SolverError"]


class SolverError(RuntimeError):
    """Typed failure from the solver's queue/flush machinery.

    Raised (instead of a bare ``assert``, which vanishes under
    ``python -O``) when a bucket flush fails to resolve every queued
    request -- the message names the bucket and the pending count so an
    always-on service can log and shed instead of dying opaquely.
    """


class PermanentRequest:
    """Future for one queued permanent; resolved by a solver flush."""

    def __init__(self, solver: "PermanentSolver", matrix: np.ndarray):
        self._solver = solver
        self.matrix = matrix
        self.n = matrix.shape[0]
        self.done = False
        self.value: complex | float | None = None
        self.report: PermanentReport | None = None

    def result(self) -> complex | float:
        """The permanent; flushes this request's size bucket if pending.

        Only the owning bucket is flushed -- a planning failure in an
        unrelated size bucket must not raise out of ``result()`` and
        strand a perfectly resolvable future.
        """
        if not self.done:
            self._solver._flush_bucket(self.n)
        if not self.done:
            _, reqs = self._solver._queue.get(self.n, (0.0, []))
            raise SolverError(
                f"flush of size bucket n={self.n} left "
                f"{len(reqs)} request(s) unresolved (this future among "
                f"them) -- bucket flush must resolve every queued request")
        return self.value

    def _resolve(self, value, report) -> None:
        self.value = value
        self.report = report
        self.done = True


class PermanentSolver:
    """Stateful plan/execute session: backend dispatch + cache + queue."""

    def __init__(self, config: SolverConfig | None = None, *,
                 distributed_ctx: Any | None = None,
                 clock: Callable[[], float] | None = None,
                 **overrides):
        config = config or SolverConfig()
        if overrides:
            config = config.replace(**overrides)
        self.config = config
        self.distributed_ctx = distributed_ctx
        self.cache = ResultCache(config.cache_entries) if config.cache \
            else None
        # clock precedence: explicit kwarg > SolverConfig.clock > monotonic
        # (injectable so deadline behavior is deterministic under test)
        self._clock = clock if clock is not None \
            else (config.clock or time.monotonic)  # permlint: disable=PL004  # sanctioned injectable-clock default
        # size-keyed request queue: n -> (first-enqueue time, requests)
        self._queue: dict[int, tuple[float, list[PermanentRequest]]] = {}
        self._stats = ExecStats()
        self.flushes = 0
        # optional JobState -> None callback fired after every
        # checkpointed wave of a step_sharded (campaign) leaf
        self.campaign_progress: Callable | None = None

    # -- plan ---------------------------------------------------------------

    def plan(self, A) -> ExecutionPlan:
        """Scalar plan for one matrix (per-leaf dispatch order)."""
        A = np.asarray(A)
        if A.ndim != 2 or A.shape[0] != A.shape[1]:
            raise ValueError(f"square matrix required, got {A.shape}")
        with span("solver.plan"):
            return build_plan([A], self.config, batched=False)

    def plan_batch(self, As: Sequence) -> ExecutionPlan:
        """Bucketed batch plan: same-size same-route leaves share one
        device program (vmapped locally, or batch-axis-sharded over the
        mesh when the solver holds a ``distributed_ctx`` and the backend
        is ``distributed``/``distributed_batch``)."""
        with span("solver.plan"):
            return build_plan(list(As), self.config, batched=True)

    # -- execute ------------------------------------------------------------

    def execute(self, plan: ExecutionPlan, *, return_report: bool = False):
        """Dispatch a plan; scalar plans return a Python scalar, batch
        plans a (B,) ndarray (complex128 when the plan is complex)."""
        with span("solver.execute"):
            totals, reports, stats = execute_plan(
                plan, cache=self.cache,
                distributed_ctx=self.distributed_ctx,
                campaign_progress=self.campaign_progress)
            self._merge_stats(stats)
            out = totals if plan.is_complex else np.real(totals)
            for i, r in enumerate(reports):
                r.value = complex(out[i]) if plan.is_complex \
                    else float(out[i])
        if not plan.batched and plan.num_matrices == 1:
            value = reports[0].value
            return (value, reports[0]) if return_report else value
        return (out, reports) if return_report else out

    # -- async request queue ------------------------------------------------

    def submit(self, A) -> PermanentRequest:
        """Queue one matrix; returns a future resolved at the next flush.

        Triggers an immediate flush of the matrix's size bucket when it
        reaches ``queue_max_batch``; also polls deadline triggers for
        every bucket (oldest request older than ``queue_max_delay_s``).
        """
        A = np.asarray(A)
        if A.ndim != 2 or A.shape[0] != A.shape[1]:
            raise ValueError(f"square matrix required, got {A.shape}")
        req = PermanentRequest(self, A)
        t0, reqs = self._queue.setdefault(A.shape[0],
                                          (self._clock(), []))
        reqs.append(req)
        if len(reqs) >= self.config.queue_max_batch:
            self._flush_bucket(A.shape[0])
        self.poll()
        return req

    @property
    def pending(self) -> int:
        return sum(len(reqs) for _, reqs in self._queue.values())

    def poll(self) -> int:
        """Flush every bucket whose deadline has passed; returns the
        number of requests flushed."""
        now = self._clock()
        due = [n for n, (t0, reqs) in self._queue.items()
               if reqs and now - t0 >= self.config.queue_max_delay_s]
        return sum(self._flush_bucket(n) for n in due)

    def flush(self) -> int:
        """Flush every queued bucket regardless of triggers; returns the
        number of requests flushed."""
        return sum(self._flush_bucket(n) for n in list(self._queue))

    def _flush_bucket(self, n: int) -> int:
        _, reqs = self._queue.get(n, (0.0, []))
        if not reqs:
            self._queue.pop(n, None)
            return 0
        # plan + execute BEFORE dequeuing: if either raises, the bucket
        # stays queued and the pending futures remain resolvable
        plan = self.plan_batch([r.matrix for r in reqs])
        _, reports = self.execute(plan, return_report=True)
        self._queue.pop(n, None)
        for req, report in zip(reqs, reports):
            req._resolve(report.value, report)
        self.flushes += 1
        return len(reqs)

    # -- accounting ---------------------------------------------------------

    def _merge_stats(self, s: ExecStats) -> None:
        t = self._stats
        t.device_dispatches += s.device_dispatches
        t.batched_leaves += s.batched_leaves
        t.scalar_leaves += s.scalar_leaves
        t.inline_leaves += s.inline_leaves
        t.cache_hits += s.cache_hits
        t.cache_misses += s.cache_misses
        t.downgrades.extend(s.downgrades)
        t.campaign.update(s.campaign)
        for key, lt in s.timings.items():
            t.timings.setdefault(key, LeafTiming()).merge(lt)

    def stats(self) -> dict:
        """Dispatch + cache + queue accounting for the session.

        ``leaf_timings`` aggregates the executor's per-leaf device timing
        by dispatch-site key (``dense_batch(n=12,jnp)`` -> count / leaves
        / total_s / max_s) -- the same shape ``serve.metrics`` exports in
        its snapshot schema, so benchmarks and the service log line read
        identical counters.  ``campaign`` counts the waves, slices,
        retried waves and checkpoint bytes of step_sharded leaves.
        """
        out = {"device_dispatches": self._stats.device_dispatches,
               "batched_leaves": self._stats.batched_leaves,
               "scalar_leaves": self._stats.scalar_leaves,
               "inline_leaves": self._stats.inline_leaves,
               "downgrades": list(self._stats.downgrades),
               "flushes": self.flushes,
               "pending": self.pending,
               "campaign": {k: self._stats.campaign[k] for k in (
                   "waves", "slices", "retried_waves", "checkpoint_bytes")},
               "leaf_timings": {k: t.to_json()
                                for k, t in sorted(
                                    self._stats.timings.items())}}
        out["cache"] = self.cache.stats() if self.cache else None
        return out
