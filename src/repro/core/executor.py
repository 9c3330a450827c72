"""Execute side of the plan/execute split: backend registry + dispatcher.

``engine._leaf_value``'s if/elif backend chain is replaced by strategy
objects: each :class:`Backend` knows how to run one dense/sparse leaf and
(optionally) a whole same-size bucket; ``register_backend`` adds new
strategies without touching the dispatcher (the ``jnp`` / ``pallas`` /
``distributed`` / ``distributed_batch`` / ``campaign`` strategies
register themselves at import).  ``campaign`` is special: it is never
selected by ``SolverConfig.backend`` -- the planner routes individual
oversized leaves to it (``route == "step_sharded"``) and it executes
them as checkpointed step-space waves (see :class:`CampaignBackend`).

**Batch contract.**  ``dense_batch(stack, *, precision, num_chunks, ctx)``
and ``sparse_batch(sps, *, precision, num_chunks, ctx)`` run one
same-size bucket as a single device program and return a (B,) ndarray of
values in bucket order, or ``None`` to signal "unsupported for this
bucket" -- the dispatcher then re-runs the bucket on the ``jnp``
strategy and tags the downgrade as ``{route}_batch(...,<cfg>->jnp)``
(e.g. ``distributed->jnp`` when no mesh/ctx is attached; complex stacks
are first-class on every strategy and no longer downgrade).  ``ctx`` is
the ``distributed_ctx`` threaded
through :func:`execute_plan`: a ``jax.sharding.Mesh`` or any object with
a ``.mesh`` attribute (``core.distributed.DistributedPermanent``);
non-distributed strategies ignore it.  Every strategy must also answer
:meth:`Backend.value_backend` -- the registry name of the strategy whose
numerics will actually produce a leaf's value.  The result cache stores
values under THAT name, never the configured one, so a jnp-computed
downgrade can never satisfy a genuine pallas/distributed lookup whose
kernel numerics differ at the ulp level.

:func:`execute_plan` walks an :class:`~repro.core.planner.ExecutionPlan`:

* scalar plans dispatch leaf by leaf in plan order (bit-identical to the
  legacy ``engine.permanent`` loop);
* batched plans fold n <= 2 leaves inline, consult the result cache per
  leaf, then run every multi-leaf (route, n) bucket as ONE device
  program (vmapped locally, or batch-axis-sharded over the mesh under
  ``distributed``) -- cache hits and ragged singletons never enter a
  bucket;
* every leaf result is normalized to a Python scalar before accumulation
  (both dense and sparse routes -- no 0-d array surprises downstream),
  and backend downgrades are recorded in the dispatch tags, as is the
  planner's ``qq->kahan`` complex precision downgrade
  (``precision(qq->kahan)`` on every report, mirroring ``--plan-json``).

Returns per-matrix totals plus :class:`PermanentReport`s and an
:class:`ExecStats` with device-dispatch / cache accounting.
"""

from __future__ import annotations

from collections import Counter
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from ..utils.spans import span
from . import ryser as R
from . import sparyser as S
from .cache import ResultCache
from .planner import (ROUTE_CAMPAIGN, ROUTE_DENSE, ROUTE_INLINE,
                      ROUTE_SPARSE, CampaignSpec, ExecutionPlan,
                      LeafTask, PermanentReport)

__all__ = ["Backend", "JnpBackend", "PallasBackend", "DistributedBackend",
           "DistributedBatchBackend", "CampaignBackend",
           "register_backend", "get_backend", "available_backends",
           "ExecStats", "LeafTiming", "execute_plan"]


def _ctx_mesh(ctx):
    """Extract a usable Mesh from a distributed ctx (Mesh or runner)."""
    if ctx is None:
        return None
    from jax.sharding import Mesh
    mesh = getattr(ctx, "mesh", ctx)
    return mesh if isinstance(mesh, Mesh) else None


def _scalar(v) -> complex | float:
    """Normalize any engine return (0-d jax/numpy array, numpy scalar,
    Python number) to a Python scalar so downstream ``complex(...)``
    coercions never see 0-d array surprises."""
    return np.asarray(v).item()


@dataclass
class LeafTiming:
    """Wall-clock accounting for one dispatch-site key.

    One key is one (route, n, producing-backend) device program family,
    e.g. ``dense_batch(n=12,jnp)`` or ``sparse(n=9,pallas)``; ``count``
    is device dispatches, ``leaves`` the leaf results they produced
    (a bucket dispatch serves many leaves).
    """
    count: int = 0
    leaves: int = 0
    total_s: float = 0.0
    max_s: float = 0.0

    def add(self, seconds: float, leaves: int = 1) -> None:
        self.count += 1
        self.leaves += leaves
        self.total_s += seconds
        self.max_s = max(self.max_s, seconds)

    def merge(self, other: "LeafTiming") -> None:
        self.count += other.count
        self.leaves += other.leaves
        self.total_s += other.total_s
        self.max_s = max(self.max_s, other.max_s)

    def to_json(self) -> dict:
        return {"count": self.count, "leaves": self.leaves,
                "total_s": self.total_s, "max_s": self.max_s,
                "mean_s": self.total_s / self.count if self.count else 0.0}


@dataclass
class ExecStats:
    """What one execute_plan call actually did (for tests/benchmarks)."""
    device_dispatches: int = 0       # scalar leaf calls + bucket programs
    batched_leaves: int = 0          # leaves served by bucket programs
    scalar_leaves: int = 0           # leaves served one at a time
    inline_leaves: int = 0           # n <= 2 closed forms
    cache_hits: int = 0
    cache_misses: int = 0
    downgrades: list[str] = field(default_factory=list)
    # per-dispatch-site wall-clock timing (serve/metrics.py exports these
    # through the one snapshot schema; PermanentSolver.stats() aggregates
    # them across calls as ``leaf_timings``)
    timings: dict[str, LeafTiming] = field(default_factory=dict)
    # campaign leaves: waves, slices, retried_waves, checkpoint_bytes
    campaign: Counter = field(default_factory=Counter)

    @contextmanager
    def dispatch(self, key: str, leaves: int = 1):
        """One device dispatch, timed as the span ``executor.dispatch``
        (attrs ``key``, ``leaves``); its seconds go to ``timings`` under
        the span's ``key``, which the body may correct once it knows
        which strategy served the leaves."""
        with span("executor.dispatch", key=key, leaves=leaves) as s:
            yield s
        self.timings.setdefault(s.attrs["key"], LeafTiming()).add(
            s.seconds, leaves)


# ---------------------------------------------------------------------------
# Backend strategy registry
# ---------------------------------------------------------------------------

class Backend:
    """One execution strategy for permanent leaves.

    ``dense``/``sparse`` run a single leaf and must return a Python
    scalar.  ``dense_batch``/``sparse_batch`` follow the batch contract
    in the module docstring: one bucket -> (B,) ndarray, or ``None`` to
    downgrade to ``jnp``.  ``value_backend`` names the strategy whose
    numerics actually serve a leaf -- the result-cache identity.
    ``geometry`` is the leaf's resolved kernel geometry (config override
    or tuning-table hit); only kernel-backed strategies honor it, the
    jnp engines ignore it (their numerics have no kernel geometry).
    """

    name = "?"

    def dense(self, M: np.ndarray, *, precision: str, num_chunks: int,
              geometry=None, ctx: Any | None = None) -> complex | float:
        raise NotImplementedError

    def sparse(self, sp, *, precision: str, num_chunks: int,
               geometry=None, ctx: Any | None = None) -> complex | float:
        raise NotImplementedError

    def dense_batch(self, stack: np.ndarray, *, precision: str,
                    num_chunks: int, geometry=None,
                    ctx: Any | None = None) -> np.ndarray | None:
        return None

    def sparse_batch(self, sps: list, *, precision: str, num_chunks: int,
                     geometry=None,
                     ctx: Any | None = None) -> np.ndarray | None:
        return None

    def value_backend(self, route: str, n: int, *, batched: bool,
                      ctx: Any | None = None) -> str:
        """Registry name of the strategy whose numerics produce this
        leaf's value.  Cache keys use THIS name, not the configured
        backend, so downgraded (jnp-computed) values are stored -- and
        found -- under ``jnp``.  Produced-by logic is uniform across the
        dense and sparse routes (no sparse hardcode since the SpaRyser
        kernel landed: sparse leaves are kernel-served too); strategies
        that fall back for some shapes override this accordingly."""
        return self.name


class JnpBackend(Backend):
    """Chunked / vmapped XLA engines (the default)."""

    name = "jnp"

    def dense(self, M, *, precision, num_chunks, geometry=None, ctx=None):
        return _scalar(R.perm_ryser_chunked(M, num_chunks=num_chunks,
                                            precision=precision))

    def sparse(self, sp, *, precision, num_chunks, geometry=None, ctx=None):
        return _scalar(S.perm_sparyser_chunked(sp, num_chunks=num_chunks,
                                               precision=precision))

    def dense_batch(self, stack, *, precision, num_chunks, geometry=None,
                    ctx=None):
        if np.iscomplexobj(stack):
            # the split-plane engine syncs and joins the planes itself
            return R.perm_ryser_batched(stack, num_chunks=num_chunks,
                                        precision=precision)
        return R.launch_and_wait(R.perm_ryser_batched, stack,
                                 num_chunks=num_chunks, precision=precision)

    def sparse_batch(self, sps, *, precision, num_chunks, geometry=None,
                     ctx=None):
        return S.perm_sparyser_batched(sps, num_chunks=num_chunks,
                                       precision=precision)


class PallasBackend(JnpBackend):
    """TPU kernel (interpret-mode on CPU); real OR complex, n >= 4.

    Dense AND sparse leaves run the kernels (sparse: the padded-CCS
    SpaRyser kernels in ``kernels.ryser_sparse``, same batch grid and
    window schedule); complex leaves run the split re/im plane variants.
    Only tiny matrices fall back to the jnp engines -- scalar dense falls
    back silently (legacy contract), scalar sparse and every batch with a
    ``pallas->jnp`` downgrade tag emitted by the dispatcher.
    """

    name = "pallas"

    @staticmethod
    def _kernel_ok(n: int) -> bool:
        return n >= 4

    def _supported(self, M_or_stack) -> bool:
        return self._kernel_ok(M_or_stack.shape[-1])

    def dense(self, M, *, precision, num_chunks, geometry=None, ctx=None):
        if self._supported(M):
            from ..kernels import ops as K
            return _scalar(K.permanent_pallas(M, precision=precision,
                                              geometry=geometry))
        return super().dense(M, precision=precision, num_chunks=num_chunks)

    def sparse(self, sp, *, precision, num_chunks, geometry=None, ctx=None):
        if self._kernel_ok(sp.n):
            from ..kernels import ops as K
            return _scalar(K.permanent_pallas_sparse(sp, precision=precision,
                                                     geometry=geometry))
        return super().sparse(sp, precision=precision,
                              num_chunks=num_chunks)

    def dense_batch(self, stack, *, precision, num_chunks, geometry=None,
                    ctx=None):
        if self._supported(stack):
            from ..kernels import ops as K
            return R.launch_and_wait(K.permanent_pallas_batched, stack,
                                     precision=precision, geometry=geometry)
        return None                  # dispatcher falls back + tags downgrade

    def sparse_batch(self, sps, *, precision, num_chunks, geometry=None,
                     ctx=None):
        if self._kernel_ok(sps[0].n):
            from ..kernels import ops as K
            return R.launch_and_wait(K.permanent_pallas_sparse_batched, sps,
                                     precision=precision, geometry=geometry)
        return None                  # tiny bucket: jnp fallback, tagged

    def value_backend(self, route, n, *, batched, ctx=None):
        if self._kernel_ok(n):       # dense and sparse kernels alike
            return self.name
        return "jnp"                 # tiny-n fallback to the jnp engines


class DistributedBatchBackend(JnpBackend):
    """Batch-axis sharding over ``core.distributed``'s mesh (ROADMAP:
    batch sharding over the device mesh).

    ``dense_batch``/``sparse_batch`` shard a same-size bucket's leading
    axis over the mesh -- matrices replicated per shard (each device owns
    whole matrices, no psum), ragged tails padded to the device count and
    masked on the host; complex buckets shard their split (re, im)
    planes through the same shard_map bodies.  Needs a mesh through
    ``ctx``; without one every bucket downgrades to ``jnp`` with a tag.
    Scalar leaves (ragged singletons) use the plain jnp engines -- a
    one-matrix bucket has nothing to shard.
    """

    name = "distributed_batch"

    def dense_batch(self, stack, *, precision, num_chunks, geometry=None,
                    ctx=None):
        mesh = _ctx_mesh(ctx)
        if mesh is None:
            return None              # no mesh attached: tagged jnp downgrade
        from . import distributed as Dm
        return Dm.batch_permanents_on_mesh(stack, mesh, precision=precision,
                                           num_chunks=num_chunks)

    def sparse_batch(self, sps, *, precision, num_chunks, geometry=None,
                     ctx=None):
        mesh = _ctx_mesh(ctx)
        if mesh is None:
            return None
        from . import distributed as Dm
        return Dm.sparse_batch_permanents_on_mesh(
            sps, mesh, precision=precision, num_chunks=num_chunks)

    def value_backend(self, route, n, *, batched, ctx=None):
        if batched and _ctx_mesh(ctx) is not None:
            return self.name
        return "jnp"


class DistributedBackend(JnpBackend):
    """Mesh-wide shard_map (core.distributed).

    Scalar dense leaves split the Gray-step space over the mesh (the
    paper's Sec. 6.3 shape, for the occasional huge matrix); batched
    plans delegate whole buckets to the ``distributed_batch`` strategy
    (data parallelism over matrices).  Needs a ctx passed through
    ``execute_plan(..., distributed_ctx=...)`` -- either a
    ``DistributedPermanent`` runner or a bare ``jax.sharding.Mesh``;
    without one it behaves like ``jnp`` (legacy contract), batched with a
    ``distributed->jnp`` downgrade tag.
    """

    name = "distributed"

    def dense(self, M, *, precision, num_chunks, geometry=None, ctx=None):
        if ctx is not None:
            # a DistributedPermanent runner computes at ITS OWN precision
            # (ctx.permanent takes none) -- only honor it when that agrees
            # with the plan, else the value would be reported and cached
            # under a precision it was never computed at
            if hasattr(ctx, "permanent") and \
                    getattr(ctx, "precision", precision) == precision:
                return _scalar(ctx.permanent(M))
            from . import distributed as Dm
            return _scalar(Dm.permanent_on_mesh(M, _ctx_mesh(ctx),
                                                precision=precision))
        return super().dense(M, precision=precision, num_chunks=num_chunks)

    def dense_batch(self, stack, *, precision, num_chunks, geometry=None,
                    ctx=None):
        return get_backend("distributed_batch").dense_batch(
            stack, precision=precision, num_chunks=num_chunks,
            geometry=geometry, ctx=ctx)

    def sparse_batch(self, sps, *, precision, num_chunks, geometry=None,
                     ctx=None):
        return get_backend("distributed_batch").sparse_batch(
            sps, precision=precision, num_chunks=num_chunks,
            geometry=geometry, ctx=ctx)

    def value_backend(self, route, n, *, batched, ctx=None):
        if batched:
            return get_backend("distributed_batch").value_backend(
                route, n, batched=batched, ctx=ctx)
        if route == ROUTE_DENSE and ctx is not None:
            return self.name
        return "jnp"


class CampaignBackend(Backend):
    """Checkpointed step-space waves for ROUTE_CAMPAIGN leaves.

    Not selected through ``SolverConfig.backend`` -- the planner routes a
    leaf here when its step-cost estimate crosses
    ``campaign_threshold``, and the :class:`CampaignSpec` it records
    (slice geometry + wave-body backend + precision) fully determines the
    numerics.  Execution is ``core.distributed.run_campaign``: waves of
    :func:`~repro.core.distributed.slice_sums_on_mesh` over the ctx mesh
    (or a flat 1D mesh over every visible device when no ctx is
    attached), twofloat partials checkpointed to
    ``SolverConfig.campaign_checkpoint`` after each wave, fixed-order
    final reduce.  A ``campaign_max_waves`` budget that expires with
    slices pending raises :class:`~repro.core.distributed.CampaignPaused`
    through ``execute_plan`` (the checkpoint holds the progress).  A
    completed leaf's checkpoint stays until the next job under the same
    path starts afresh over it.
    """

    name = "campaign"

    def campaign(self, M: np.ndarray, spec: CampaignSpec, *,
                 ctx: Any | None = None, checkpoint_path: str | None = None,
                 progress_cb=None, max_waves: int | None = None,
                 counters: Counter | None = None) -> complex | float:
        from . import distributed as Dm
        mesh = _ctx_mesh(ctx)
        if mesh is None:
            import jax
            from jax.sharding import Mesh
            mesh = Mesh(np.array(jax.devices()), ("step",))
        value, state = Dm.run_campaign(
            M, mesh, total_slices=spec.total_slices,
            chunks_per_slice=spec.chunks_per_slice,
            chunk_size=spec.chunk_size, precision=spec.precision,
            backend=spec.backend, geometry=spec.geometry,
            checkpoint_path=checkpoint_path,
            progress_cb=progress_cb, max_waves=max_waves,
            counters=counters)
        if value is None:
            raise Dm.CampaignPaused(state)
        return _scalar(value)


_BACKENDS: dict[str, Backend] = {}


def register_backend(backend: Backend, name: str | None = None) -> Backend:
    """Register a strategy object under ``name`` (default: backend.name)."""
    _BACKENDS[name or backend.name] = backend
    return backend


def get_backend(name: str) -> Backend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(f"unknown backend {name!r}; registered: "
                         f"{sorted(_BACKENDS)}") from None


def available_backends() -> list[str]:
    return sorted(_BACKENDS)


register_backend(JnpBackend())
register_backend(PallasBackend())
register_backend(DistributedBackend())
register_backend(DistributedBatchBackend())
register_backend(CampaignBackend())

_FALLBACK = "jnp"


# ---------------------------------------------------------------------------
# Plan execution
# ---------------------------------------------------------------------------

def _geometry_tag(leaf: LeafTask, produced_by: str) -> str:
    """Geometry component of the cache key for ``leaf``.

    A geometry tag enters the key only when kernel numerics actually
    depend on it: campaign leaves carry theirs on the spec (the wave
    body is the kernel), plain leaves only when a Pallas kernel serves
    them.  Values produced by the jnp engines -- including pallas->jnp
    downgrades -- key under the ``"-"`` sentinel so tuning never splits
    or contaminates geometry-free results.
    """
    if leaf.route == ROUTE_CAMPAIGN:
        g = leaf.campaign.geometry if leaf.campaign is not None else None
    elif produced_by == "pallas":
        g = leaf.geometry
    else:
        g = None
    return g.tag() if g is not None else "-"


def _cache_key(leaf: LeafTask, plan: ExecutionPlan, produced_by: str) -> tuple:
    """Result-cache key for ``leaf``.

    ``produced_by`` is the *value-producing* backend name (see
    ``Backend.value_backend``), NOT ``plan.config.backend`` -- a
    pallas/distributed bucket that downgrades to jnp stores (and finds)
    its numbers under ``jnp``, so a jnp-computed value can never satisfy
    a genuine kernel lookup whose numerics differ at the ulp level.
    The leaf dtype is part of the identity too (belt and braces over the
    content hash): a float64 leaf and a complex128 leaf with zero
    imaginary part must never collide, and ``plan.precision`` is the
    *effective* precision, so a complex ``qq`` plan keys under ``kahan``.
    Resolved kernel geometry joins the key the same way (see
    :func:`_geometry_tag`): two geometries reduce in different fixed
    orders and must never share an entry.
    """
    return ResultCache.key(leaf.key, leaf.route, plan.precision,
                           produced_by, plan.config.num_chunks,
                           dtype=leaf.matrix.dtype.str,
                           geometry=_geometry_tag(leaf, produced_by))


def _run_leaf(leaf: LeafTask, plan: ExecutionPlan, backend: Backend,
              report: PermanentReport, stats: ExecStats,
              ctx: Any | None) -> complex | float:
    """One leaf through the scalar strategy path (plan-order dispatch)."""
    n = leaf.n
    cfg = plan.config
    if leaf.route == ROUTE_SPARSE:
        # scalar sparse tags carry backend attribution like every batch
        # tag: ``sparse(n=..,<backend>)``, with a ``cfg->produced``
        # downgrade suffix when another strategy's numerics serve the
        # leaf -- so --plan-json reports where sparse values came from
        produced = backend.value_backend(ROUTE_SPARSE, n, batched=False,
                                         ctx=ctx)
        if produced == cfg.backend:
            tag = f"sparse(n={n},{produced})"
        else:
            tag = f"sparse(n={n},{cfg.backend}->{produced})"
            stats.downgrades.append(tag)
        report.dispatch.append(tag)
        sp = S.SparseMatrix.from_dense(leaf.matrix)
        with stats.dispatch(f"sparse(n={n},{produced})"):
            val = backend.sparse(sp, precision=plan.precision,
                                 num_chunks=cfg.num_chunks,
                                 geometry=leaf.geometry, ctx=ctx)
    else:
        produced = backend.value_backend(ROUTE_DENSE, n, batched=False,
                                         ctx=ctx)
        report.dispatch.append(f"dense(n={n})")
        with stats.dispatch(f"dense(n={n},{produced})"):
            val = backend.dense(leaf.matrix, precision=plan.precision,
                                num_chunks=cfg.num_chunks,
                                geometry=leaf.geometry, ctx=ctx)
    stats.device_dispatches += 1
    stats.scalar_leaves += 1
    return val


def _inline_value(m: np.ndarray) -> complex | float:
    return m[0, 0] if m.shape[0] == 1 else \
        m[0, 0] * m[1, 1] + m[0, 1] * m[1, 0]


def execute_plan(plan: ExecutionPlan, *, cache: ResultCache | None = None,
                 distributed_ctx: Any | None = None,
                 campaign_progress=None):
    """Dispatch every leaf of ``plan`` and accumulate per-matrix totals.

    Returns ``(totals, reports, stats)`` where ``totals`` is a (B,)
    complex128 array (callers extract the real part for real plans),
    ``reports`` one PermanentReport per planned matrix, and ``stats`` the
    dispatch/cache accounting.  ``campaign_progress`` is an optional
    ``JobState -> None`` callback fired after every checkpointed wave of
    a ROUTE_CAMPAIGN leaf.
    """
    cfg = plan.config
    backend = get_backend(cfg.backend)
    fallback = get_backend(_FALLBACK)
    stats = ExecStats()
    B = plan.num_matrices
    totals = np.zeros(B, dtype=np.complex128)
    reports = [PermanentReport(n=e.n, nnz=e.nnz, density=e.density,
                               dm_removed=e.dm_removed,
                               fm_leaves=e.fm_leaves,
                               leaf_sizes=list(e.leaf_sizes),
                               precision=plan.precision, backend=cfg.backend)
               for e in plan.entries]
    for e in plan.entries:
        totals[e.index] += e.const
    if plan.precision_downgrade:
        # surface the planner's silent complex precision fallback the same
        # way backend downgrades are surfaced (satellite: qq->kahan tag)
        ptag = f"precision({plan.precision_downgrade})"
        stats.downgrades.append(ptag)
        for r in reports:
            r.dispatch.append(ptag)

    def produced_by(leaf: LeafTask, batched: bool) -> str:
        """Name of the strategy whose numerics will serve this leaf.

        Campaign leaves key under the full wave-body identity recorded in
        their spec -- backend AND slice geometry -- because the twofloat
        wave partials depend on the decomposition, not just the engine."""
        if leaf.route == ROUTE_CAMPAIGN:
            s = leaf.campaign
            return (f"campaign[{s.backend},{s.total_slices}x"
                    f"{s.chunks_per_slice}x{s.chunk_size}]")
        return backend.value_backend(leaf.route, leaf.n, batched=batched,
                                     ctx=distributed_ctx)

    def lookup(leaf: LeafTask, batched: bool):
        if cache is None:
            return None, None
        key = _cache_key(leaf, plan, produced_by(leaf, batched))
        val = cache.get(key)
        if val is None:
            stats.cache_misses += 1
        else:
            stats.cache_hits += 1
        return key, val

    campaign_leaves = [l for l in plan.leaves if l.route == ROUTE_CAMPAIGN]

    def campaign_ckpt(leaf: LeafTask) -> str | None:
        """Checkpoint path for a campaign leaf: the configured path
        verbatim for a single-campaign plan, leaf-key-suffixed when
        several leaves campaign (their JobStates must not collide)."""
        base = cfg.campaign_checkpoint
        if base is None:
            return None
        if len(campaign_leaves) == 1:
            return base
        return f"{base}.{leaf.key[:12]}.npz"

    def run_campaign_leaf(leaf: LeafTask) -> complex | float:
        spec = leaf.campaign
        reports[leaf.owner].dispatch.append(
            f"step_sharded(n={leaf.n},slices={spec.total_slices},"
            f"{spec.backend})")
        with stats.dispatch(f"step_sharded(n={leaf.n},{spec.backend})"):
            val = get_backend("campaign").campaign(
                leaf.matrix, spec, ctx=distributed_ctx,
                checkpoint_path=campaign_ckpt(leaf),
                progress_cb=campaign_progress,
                max_waves=cfg.campaign_max_waves, counters=stats.campaign)
        stats.device_dispatches += 1
        stats.scalar_leaves += 1
        return val

    if not plan.batched:
        # scalar mode: strict plan-order per-leaf dispatch (legacy
        # ``permanent`` numerics, tag for tag)
        for leaf in plan.leaves:
            key, val = lookup(leaf, False)
            if val is not None:
                reports[leaf.owner].dispatch.append(
                    f"cache({leaf.route},n={leaf.n})")
            elif leaf.route == ROUTE_CAMPAIGN:
                val = run_campaign_leaf(leaf)
                if key is not None:
                    cache.put(key, val)
            else:
                val = _run_leaf(leaf, plan, backend, reports[leaf.owner],
                                stats, distributed_ctx)
                if key is not None:
                    cache.put(key, val)
            totals[leaf.owner] += leaf.coef * val
        return totals, reports, stats

    # batched mode: inline folds, cache probe, then bucket programs.
    # With a cache attached, duplicate leaves inside one cold batch are
    # scheduled once: followers resolve from the cache after their
    # bucket runs (boson-sampling streams repeat submatrices *within* a
    # request batch, not just across calls).  ``computed`` is keyed by
    # the PROBE key (batched producing-backend prediction); the store key
    # may differ when a bucket downgrades or a singleton takes the scalar
    # path -- followers always resolve through the probe key.
    pending: dict[tuple[str, int], list[int]] = {}
    computed: dict[tuple, complex | float] = {}   # this call's results
    followers: list[LeafTask] = []
    for (route, n), idxs in plan.buckets.items():
        for j in idxs:
            leaf = plan.leaves[j]
            if route == ROUTE_INLINE:
                reports[leaf.owner].dispatch.append(f"dense(n={n})")
                totals[leaf.owner] += leaf.coef * _inline_value(leaf.matrix)
                stats.inline_leaves += 1
                continue
            if cache is not None:
                key = _cache_key(leaf, plan, produced_by(leaf, True))
                if key in computed:
                    followers.append(leaf)
                    continue
                val = cache.get(key)
                if val is not None:
                    stats.cache_hits += 1
                    reports[leaf.owner].dispatch.append(
                        f"cache({route},n={n})")
                    totals[leaf.owner] += leaf.coef * val
                    continue
                stats.cache_misses += 1
                computed[key] = None      # scheduled; filled after its bucket
            pending.setdefault((route, n), []).append(j)

    for (route, n), idxs in sorted(pending.items()):
        bucket_leaves = [plan.leaves[j] for j in idxs]
        if route == ROUTE_CAMPAIGN:
            # campaign leaves never share a device program: each is its
            # own checkpointed wave sequence (probe key == store key --
            # the campaign identity is batched-independent)
            bname = produced_by(bucket_leaves[0], True)
            for leaf in bucket_leaves:
                val = run_campaign_leaf(leaf)
                if cache is not None:
                    k = _cache_key(leaf, plan, bname)
                    cache.put(k, val)
                    computed[k] = val
                totals[leaf.owner] += leaf.coef * complex(val)
            continue
        # one device program per resolved kernel geometry: a (route, n)
        # bucket can mix densities whose tuning-table hits differ, and
        # geometry is a static jit argument AND numeric identity -- such
        # leaves must never share a dispatch
        groups: dict[str, list[LeafTask]] = {}
        for leaf in bucket_leaves:
            gtag = leaf.geometry.tag() if leaf.geometry is not None else "-"
            groups.setdefault(gtag, []).append(leaf)
        for _gtag, leaves in sorted(groups.items()):
            bname = produced_by(leaves[0], True)
            geometry = leaves[0].geometry
            # ragged straggler: scalar path -- but only while the scalar
            # strategy produces the same numerics family as the bucket
            # one (under distributed+mesh the scalar path is the
            # step-space split, which is NOT bit-identical to the batch
            # engines and would be stored under a key the batched probes
            # never use)
            if len(leaves) == 1 and bname == produced_by(leaves[0], False):
                leaf = leaves[0]
                val = _run_leaf(leaf, plan, backend, reports[leaf.owner],
                                stats, distributed_ctx)
                if cache is not None:
                    cache.put(_cache_key(leaf, plan, bname), val)
                    computed[_cache_key(leaf, plan, bname)] = val
                totals[leaf.owner] += leaf.coef * complex(val)
                continue
            tag = f"{route}_batch(n={n},b={len(leaves)})"
            with stats.dispatch(f"{route}_batch(n={n},{bname})",
                                leaves=len(leaves)) as d:
                if route == ROUTE_DENSE:
                    items = np.stack([l.matrix for l in leaves])
                    run, run_fallback = backend.dense_batch, \
                        fallback.dense_batch
                else:
                    items = [S.SparseMatrix.from_dense(l.matrix)
                             for l in leaves]
                    run, run_fallback = backend.sparse_batch, \
                        fallback.sparse_batch
                vals = run(items, precision=plan.precision,
                           num_chunks=cfg.num_chunks, geometry=geometry,
                           ctx=distributed_ctx)
                if vals is None:     # e.g. tiny bucket under pallas
                    vals = run_fallback(items, precision=plan.precision,
                                        num_chunks=cfg.num_chunks)
                    tag = f"{route}_batch(n={n},b={len(leaves)}," \
                          f"{cfg.backend}->{_FALLBACK})"
                    stats.downgrades.append(tag)
                    bname = _FALLBACK   # the fallback produced these values
                    d.attrs["key"] = f"{route}_batch(n={n},{bname})"
            stats.device_dispatches += 1
            stats.batched_leaves += len(leaves)
            vals = np.asarray(vals)
            for leaf, v in zip(leaves, vals):
                v = _scalar(v)
                reports[leaf.owner].dispatch.append(tag)
                if cache is not None:
                    cache.put(_cache_key(leaf, plan, bname), v)
                    computed[_cache_key(leaf, plan,
                                        produced_by(leaf, True))] = v
                totals[leaf.owner] += leaf.coef * v

    for leaf in followers:                 # duplicates of scheduled leaves
        # resolve from this call's own results, not the shared cache -- an
        # LRU smaller than the batch may already have evicted the entry
        val = computed[_cache_key(leaf, plan, produced_by(leaf, True))]
        assert val is not None, "scheduled leaf must have been computed"
        cache.hits += 1                    # in-flight dedup is still a hit
        stats.cache_hits += 1
        reports[leaf.owner].dispatch.append(
            f"cache({leaf.route},n={leaf.n})")
        totals[leaf.owner] += leaf.coef * val
    return totals, reports, stats
