"""Distributed permanent execution bodies (paper Sec. 6.3, scaled to pods).

Post-campaign-refactor layering -- this module owns every *mesh program*
(shard_map bodies and their compiled-fn caches); policy lives above it:

* **step-space split** (one huge matrix over the Gray-step space):
  ``permanent_on_mesh`` is the one-shot psum path (the paper's MPI
  reduce); ``slice_sums_on_mesh`` is the wave primitive underneath the
  campaign -- one slice per device, no reduction, sentinel-padded lanes
  masked out.  ``run_campaign`` drives waves of pending slices through
  it with checkpointed twofloat partials (``core.resume.JobState``):
  deterministic slice decomposition (``core.stepspace.plan_slices``),
  elastic device count, failed waves re-queued, and a fixed-order final
  reduction -- a killed-and-resumed campaign is bitwise-identical to an
  uninterrupted one.
* **batch-axis split** (many moderate matrices): ``batch_permanents_on_mesh``
  / ``sparse_batch_permanents_on_mesh`` shard a same-size bucket's
  leading axis; each device owns whole matrices, ragged tails are padded
  and masked, and the per-device body shares the single-device engines'
  trace, so sharded values are bit-identical to the ``jnp`` backend per
  precision mode.
* **dispatch** happens one layer up: ``core.planner`` routes a leaf to
  ``step_sharded`` (campaign) when its step-cost estimate exceeds
  ``SolverConfig.campaign_threshold``, and ``core.executor``'s
  ``CampaignBackend`` / ``DistributedBackend`` / ``DistributedBatchBackend``
  strategies call down into this module.  ``DistributedPermanent`` remains
  as a thin pre-plan-era wrapper over ``run_campaign``.

Complex input is first-class everywhere: the batch-axis entry points
shard the matrices' split (re, im) planes through the same shard_map body
as the jnp backend; the step-space split carries complex through its
twofloat sums (TwoSum is componentwise-exact under complex addition)
and, under ``backend="pallas"``, runs the split-plane kernel per device.

Every accumulation in this module is governed by the fixed-order
reduction invariant (permlint rule PL001, ``docs/INVARIANTS.md``): raw
``jnp`` reductions appear only where the reduced shape is fixed by the
matrix or the ``CampaignSpec`` geometry -- never by the device count --
and each such site carries an inline ``# permlint: disable=PL001``
justification that the linter inventories on every run.

APIs:
  ``permanent_on_mesh``     one-shot step-space split (psum reduction)
  ``slice_sums_on_mesh``    per-device slice sums, no reduction (wave mode)
  ``run_campaign``          checkpointed, elastic, resumable wave driver
  ``CampaignPaused``        control-flow signal for wave-budgeted runs
  ``batch_permanents_on_mesh``         batch-axis sharded dense bucket
  ``sparse_batch_permanents_on_mesh``  batch-axis sharded sparse bucket
  ``DistributedPermanent``  legacy wrapper over ``run_campaign``
"""

from __future__ import annotations

import math
from collections import Counter
from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P_

from ..utils.compat import shard_map
from ..utils.spans import span
from . import gray as G
from . import precision as P
from .resume import JobState
from .ryser import (batched_values, batched_values_complex, chain_prod_complex,
                    chunk_geometry, complex_precision, launch_and_wait,
                    nw_base_vector, signed_column, _final_factor)
from .stepspace import DEFAULT_GEOMETRY, Geometry, plan_slices

__all__ = ["permanent_on_mesh", "slice_sums_on_mesh", "run_campaign",
           "CampaignPaused",
           "batch_permanents_on_mesh", "sparse_batch_permanents_on_mesh",
           "DistributedPermanent", "plan_slices"]


def _planes(A) -> tuple[np.ndarray, ...]:
    """Host-side real planes of a matrix: ``(A,)`` or ``(re, im)``.

    The step-space programs never see a complex dtype: the TPU compiler
    has no c128 arithmetic (its x64 rewriter aborts the process on c128
    converts, dots and products), so a complex matrix travels as two
    real planes, like the batch engines' split-plane bodies.
    """
    A = np.asarray(A)
    if np.iscomplexobj(A):
        return (np.ascontiguousarray(A.real), np.ascontiguousarray(A.imag))
    return (A.astype(np.float64),)


def _join(parts) -> np.ndarray:
    """Per-plane host arrays (last axis) back into real or complex."""
    parts = np.asarray(parts)
    if parts.shape[-1] == 1:
        return parts[..., 0]
    return parts[..., 0] + 1j * parts[..., 1]


def _dyn_chunk_partials(A, first_chunk, T: int, C: int, precision: str):
    """Chunk partial sums with a *traced* starting chunk index.

    Mirrors ``ryser.chunk_partial_sums`` but computes the Gray-code init
    bits and the tail schedule with jnp uint64 bit math, so the chunk
    offset may be a device-varying traced value -- required under
    shard_map, where every device runs the same program on different
    slice ids.  Needs jax_enable_x64 for n > 31 (the Pallas kernel uses a
    32-bit pair encoding on real TPUs instead; see kernels/ryser_pallas).

    ``A`` is a real matrix (returns one TwoFloat) or the ``(re, im)``
    plane pair of a complex one (returns one TwoFloat per plane; the
    product is the explicit complex chain of ``ryser.chain_prod_complex``).
    """
    planes = A if isinstance(A, tuple) else (A,)
    n = planes[0].shape[0]
    k = int(math.log2(C))
    assert C == 1 << k and k >= 1
    dtype = planes[0].dtype
    space = jnp.uint64(1) << jnp.uint64(n - 1)
    dot = partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)

    starts = (first_chunk.astype(jnp.uint64)
              + jnp.arange(T, dtype=jnp.uint64)) * jnp.uint64(C)
    gray_s = starts ^ (starts >> jnp.uint64(1))
    jbits = jnp.arange(n, dtype=jnp.uint64)[:, None]
    Gbits = ((gray_s[None, :] >> jbits) & jnp.uint64(1)).astype(dtype)  # (n,T)
    X0 = tuple(nw_base_vector(M)[:, None] + dot(M, Gbits)
               for M in planes)

    # schedules for w = 1..C-1 (host constants -- identical for all chunks)
    sched = G.changed_bit_schedule(k)
    w_arr = np.arange(1, C, dtype=np.uint64)
    jj = sched.astype(np.uint64)
    bit_j = ((w_arr >> jj) ^ (w_arr >> (jj + np.uint64(1)))) & np.uint64(1)
    mid_mask = (jj + 1 == k)
    sched_j = jnp.asarray(sched)
    base_bits = jnp.asarray(bit_j.astype(np.int32))
    mid_flags = jnp.asarray(mid_mask.astype(np.int32))
    w_parity = jnp.asarray((w_arr & np.uint64(1)).astype(np.int32))
    lane_bitk = ((starts >> jnp.uint64(k)) & jnp.uint64(1)).astype(jnp.int32)

    # tail step (w = C): traced bit math
    g_tail = starts + jnp.uint64(C)
    low = g_tail & (~g_tail + jnp.uint64(1))
    tail_j = jax.lax.population_count(low - jnp.uint64(1)).astype(jnp.int32)
    gray_t = g_tail ^ (g_tail >> jnp.uint64(1))
    tail_sign = jnp.where((gray_t & low) != 0, 1.0, -1.0).astype(dtype)
    tail_live = g_tail <= (space - jnp.uint64(1))
    tail_j = jnp.where(tail_live, tail_j, 0)

    def product(X):
        if len(X) == 2:
            return chain_prod_complex(*X)
        # column product over the fixed axis n -- shape set by the matrix,
        # never by device count, so association is stable across meshes
        return (jnp.prod(X[0], axis=0),)  # permlint: disable=PL001  # fixed-axis column product

    def accum(acc, term):
        if precision == "dq_fast":
            t = P.tf_add_fast(P.TwoFloat(*acc), term)
            return (t.hi, t.lo)
        if precision in ("dq_acc", "qq"):
            t = P.tf_add_acc(P.TwoFloat(*acc), term)
            return (t.hi, t.lo)
        if precision == "kahan":
            return P.kahan_add(acc, term)
        return (acc[0] + term, acc[1])  # dd

    def scan_body(carry, inputs):
        X, acc = carry
        col_j, bit, midf, par = inputs
        sign_bits = bit ^ (midf & lane_bitk)
        X = tuple(x + signed_column(M[:, col_j], sign_bits)
                  for x, M in zip(X, planes))
        acc = tuple(accum(a, jnp.where(par == 1, -p, p))
                    for a, p in zip(acc, product(X)))
        return (X, acc), None

    # derive the zero accumulator from X0 so its varying-manual-axes match
    # under shard_map (JAX >= 0.8 vma typing)
    z = X0[0][0] * 0
    (X, acc), _ = jax.lax.scan(
        scan_body, (X0, tuple((z, z) for _ in planes)),
        (sched_j, base_bits, mid_flags, w_parity))

    # tail: per-lane column via one-hot matmul (gather-free; kernel-identical)
    onehot = (tail_j[None, :] == jnp.arange(n, dtype=jnp.int32)[:, None])
    scale = (tail_sign * tail_live.astype(dtype))[None, :]
    X = tuple(x + dot(M, onehot.astype(dtype)) * scale
              for x, M in zip(X, planes))
    neg = (C & 1) == 1
    out = []
    for a, prod in zip(acc, product(X)):
        term = jnp.where(tail_live, -prod if neg else prod,
                         jnp.zeros_like(prod))
        a = accum(a, term)
        lo = jnp.zeros_like(a[0]) if precision in ("kahan", "dd") else a[1]
        out.append(P.TwoFloat(a[0], lo))
    return tuple(out) if isinstance(A, tuple) else out[0]


def _device_partials(planes, first_chunk, T: int, C: int, precision: str,
                     backend: str, geometry: Geometry | None, vma):
    """One device's chunk range through the chosen wave body; one
    TwoFloat per plane."""
    if backend == "pallas":
        return _pallas_device_partials(planes, first_chunk, T, C, precision,
                                       geometry=geometry, vma=vma)
    return _dyn_chunk_partials(planes, first_chunk, T, C, precision)


def permanent_on_mesh(A, mesh: Mesh, *, precision: str = "dq_acc",
                      slices_per_device: int = 1,
                      lanes_per_device: int = 1024,
                      backend: str = "jnp"):
    """One-shot distributed permanent over every device of ``mesh``.

    The iteration space is sharded over *all* mesh axes; ``A`` is replicated
    (it is tiny); the result is the psum of twofloat partials -- the same
    communication structure as the paper's MPI reduce.

    backend="pallas" runs the TPU kernel (interpret-mode on CPU) on each
    device's chunk range instead of the jnp engine -- the full production
    path: two-level split -> Pallas grid -> lanes -> one psum.

    Complex matrices work on both backends as split (re, im) planes: the
    twofloat psum reduction runs per plane (TwoSum is componentwise), and
    the pallas backend launches the split-plane complex kernel per device.
    Unlike the batch engines, no qq->kahan mapping is needed (or applied)
    here: the step-space family has no twofloat product path --
    ``_dyn_chunk_partials`` accumulates qq as ``tf_add_acc`` for real and
    complex alike, so ``permanent_on_mesh``, ``slice_sums_on_mesh`` and
    ``DistributedPermanent`` agree at every precision mode.
    """
    planes = _planes(A)
    n = planes[0].shape[0]
    D = math.prod(mesh.devices.shape)
    total_slices, chunks_per_slice, C = plan_slices(
        n, D, slices_per_device, lanes_per_device)
    spd = max(1, total_slices // D)
    axes = tuple(mesh.axis_names)
    slice_table = np.arange(D * spd, dtype=np.int32).reshape(D, spd)
    # slices beyond total_slices would double-count; plan_slices pads the
    # slice count to a power of two <= D*spd, so clamp via masking
    live = (slice_table < total_slices)
    slice_table = np.where(live, slice_table, 0)

    dev_slices = jax.device_put(slice_table,
                                NamedSharding(mesh, P_(axes)))
    dev_live = jax.device_put(live.astype(np.float64),
                              NamedSharding(mesh, P_(axes)))

    hi, lo = _oneshot_mesh_fn(mesh, spd, chunks_per_slice, C, precision,
                              backend)(planes, dev_slices, dev_live)
    p0 = np.prod(nw_base_vector(np.asarray(A)))  # permlint: disable=PL001  # length-n product, shape set by the matrix
    total = P.tf_add_acc(P.TwoFloat(_join(hi), _join(lo)), p0)
    return P.tf_value(total) * _final_factor(n)


@lru_cache(maxsize=None)
def _oneshot_mesh_fn(mesh: Mesh, spd: int, chunks_per_slice: int, C: int,
                     precision: str, backend: str):
    """Compiled one-shot mesh program for ``permanent_on_mesh``.

    Extracted from the former per-call closure so (a) repeated one-shot
    calls on the same (mesh, plan geometry, precision, backend) reuse
    one compiled program instead of retracing every call, and (b)
    permprove can ``.lower()`` the exact production program for the
    PLI104 collective audit: exactly one twofloat psum pair -- two
    ``all-reduce`` instructions per mesh axis at most -- may appear.
    Complex input needs no extra cache key: jit re-specializes on the
    number of planes under the same program.
    """
    axes = tuple(mesh.axis_names)

    def body(planes, slices_local, live_local):
        dtype = planes[0].dtype
        acc = [P.TwoFloat(jnp.zeros((), dtype), jnp.zeros((), dtype))
               for _ in planes]
        for i in range(spd):
            first_chunk = slices_local[0, i] * chunks_per_slice
            parts = _device_partials(planes, first_chunk, chunks_per_slice,
                                     C, precision, backend, None,
                                     frozenset(axes))
            m = live_local[0, i].astype(dtype)
            for p, part in enumerate(parts):
                # permlint: disable=PL001  # parts shape fixed by chunks_per_slice, mesh-invariant
                h, l = P.two_sum(jnp.sum(part.hi) * m, jnp.sum(part.lo) * m)
                acc[p] = P.tf_add_tf(acc[p], P.TwoFloat(h, l))
        hi = jnp.stack([a.hi for a in acc])
        lo = jnp.stack([a.lo for a in acc])
        for ax in axes:
            hi = jax.lax.psum(hi, ax)
            lo = jax.lax.psum(lo, ax)
        return hi, lo

    # check_vma=False: interpret-mode pallas inside shard_map trips
    # the vma typing on its internal grid dynamic_slices
    return jax.jit(shard_map(body, mesh=mesh,
                             in_specs=(P_(), P_(axes), P_(axes)),
                             out_specs=(P_(), P_()),
                             check_vma=False))


@lru_cache(maxsize=None)
def _wave_fn(mesh: Mesh, chunks_per_slice: int, chunk_size: int,
             precision: str, backend: str, geometry: Geometry | None = None):
    """Compiled per-wave mesh program for one (mesh, geometry, precision,
    backend) -- cached so a many-wave campaign compiles ONCE per
    configuration instead of once per wave (jit caches on function
    identity; a fresh closure per call would retrace every wave).
    ``geometry`` (the tuned kernel geometry, pallas backend only) is part
    of the cache key: two geometries are two different wave programs.

    The body masks sentinel lanes (slice id < 0): a padded device runs an
    arithmetically-discarded slice-0 program -- under SPMD every device
    executes the same wave program, so the masked work costs no wall
    clock -- and its (hi, lo) contribution is multiplied to exact zero.
    Outputs are (D, planes): one (hi, lo) column per real plane.
    """
    axes = tuple(mesh.axis_names)

    def body(planes, slices_local):
        sid = slices_local[0, 0]
        first_chunk = jnp.maximum(sid, 0) * chunks_per_slice
        parts = _device_partials(planes, first_chunk, chunks_per_slice,
                                 chunk_size, precision, backend, geometry,
                                 frozenset(axes))
        # sentinel mask: live lanes multiply by exactly 1.0 (identity
        # under IEEE-754), padded lanes by 0.0
        m = (sid >= 0).astype(planes[0].dtype)
        hs, ls = [], []
        for part in parts:
            # permlint: disable=PL001  # parts shape fixed by chunks_per_slice, mesh-invariant
            h, l = P.two_sum(jnp.sum(part.hi) * m, jnp.sum(part.lo) * m)
            hs.append(h)
            ls.append(l)
        return jnp.stack(hs)[None], jnp.stack(ls)[None]

    return jax.jit(shard_map(body, mesh=mesh,
                             in_specs=(P_(), P_(axes)),
                             out_specs=(P_(axes), P_(axes)),
                             check_vma=False))


def slice_sums_on_mesh(A, mesh: Mesh, slice_ids: np.ndarray, *,
                       chunks_per_slice: int, chunk_size: int,
                       precision: str = "dq_acc", backend: str = "jnp",
                       geometry: Geometry | None = None):
    """Per-slice twofloat sums for one wave of D slices (no reduction).

    slice_ids: (D,) int32, one slice per device.  Entries < 0 are
    sentinel padding for short waves: their lanes return exact zeros and
    callers must discard them explicitly (``run_campaign`` does) -- no
    already-done slice is ever re-recorded.  Returns (his, los) of shape
    (D,), complex for a complex ``A``.  ``geometry`` tunes the per-device
    kernel launch (pallas backend only; the jnp body has no kernel
    geometry).
    """
    D = math.prod(mesh.devices.shape)
    slice_ids = np.asarray(slice_ids, dtype=np.int32)
    assert slice_ids.shape == (D,)
    axes = tuple(mesh.axis_names)
    dev_slices = jax.device_put(slice_ids.reshape(D, 1),
                                NamedSharding(mesh, P_(axes)))
    his, los = launch_and_wait(
        _wave_fn(mesh, chunks_per_slice, chunk_size, precision, backend,
                 geometry), _planes(A), dev_slices)
    return _join(his), _join(los)


def _pallas_device_partials(planes, first_chunk, T: int, C: int,
                            precision: str, geometry: Geometry | None = None,
                            vma=None):
    """Per-device Pallas kernel over the chunk range [first_chunk,
    first_chunk+T); the kernel's u64 lane math consumes the traced base
    index, so the same program serves every device (shard_map-safe).
    ``planes`` is ``(A,)`` (real kernel) or ``(Ar, Ai)`` (split-plane
    complex kernel); returns one TwoFloat per plane.
    ``geometry`` tunes lanes (block size within T) and the update window
    (within C); T and C themselves come from the CampaignSpec and are
    part of the campaign's numeric identity, not the tuner's."""
    from ..kernels.ops import pad_matrix, pad_base_vector
    from ..kernels.ryser_complex import ryser_pallas_call_complex
    from ..kernels.ryser_pallas import ryser_pallas_call

    n = planes[0].shape[0]
    g = geometry or DEFAULT_GEOMETRY
    TB = min(g.lanes, T)
    num_blocks = T // TB
    Wu = min(g.window, C)
    prec = precision if precision in ("dd", "kahan", "dq_acc", "dq_fast") \
        else "dq_acc"
    pads = [pad_matrix(A) for A in planes]
    n_pad = pads[0].shape[0]
    # padded rows multiply by (1 + 0i): ones in the re plane, zeros in im
    xbs = [pad_base_vector(nw_base_vector(planes[0]), n_pad)[:, None]]
    geom = dict(n=n, TB=TB, C=C, Wu=Wu, num_blocks=num_blocks,
                precision=prec, vma=vma)
    if len(planes) == 1:
        out = ryser_pallas_call(pads[0], xbs[0], first_chunk,
                                mode="batched", **geom)
        return (P.TwoFloat(out[:, 0], out[:, 1]),)
    xbs.append(jnp.zeros((n_pad, 1), pads[1].dtype)
               .at[:n, 0].set(nw_base_vector(planes[1])))
    out = ryser_pallas_call_complex(*pads, *xbs, first_chunk, **geom)
    return (P.TwoFloat(out[:, 0], out[:, 1]),
            P.TwoFloat(out[:, 2], out[:, 3]))


# ---------------------------------------------------------------------------
# Batch-axis sharding: data parallelism over matrices, not Gray steps
# ---------------------------------------------------------------------------

def _batch_pad(B: int, mesh: Mesh) -> int:
    """Rows of padding needed so the batch axis divides the device count."""
    D = math.prod(mesh.devices.shape)
    return (-B) % D


@lru_cache(maxsize=None)
def _dense_batch_mesh_fn(mesh: Mesh, T: int, C: int, precision: str):
    """Compiled mesh program for one (mesh, chunk geometry, precision).

    The shard_map body is ``ryser.batched_values`` verbatim over each
    device's local sub-stack -- chunk offsets are always 0 (devices own
    whole matrices), so the host-constant CEG schedules apply unchanged
    and no dynamic-offset (``_dyn_chunk_partials``) machinery is needed.
    """
    axes = tuple(mesh.axis_names)

    def body(local):                     # (B/D, n, n) per device
        return batched_values(local, T, C, precision)

    return jax.jit(shard_map(body, mesh=mesh, in_specs=P_(axes),
                             out_specs=P_(axes), check_vma=False))


@lru_cache(maxsize=None)
def _dense_batch_mesh_fn_complex(mesh: Mesh, T: int, C: int, precision: str):
    """Split-plane complex analogue of ``_dense_batch_mesh_fn``: the body
    is ``ryser.batched_values_complex`` verbatim over each device's local
    (re, im) sub-stacks -- one trace shared with the jnp backend."""
    axes = tuple(mesh.axis_names)

    def body(local_r, local_i):          # (B/D, n, n) x2 per device
        return batched_values_complex(local_r, local_i, T, C, precision)

    return jax.jit(shard_map(body, mesh=mesh,
                             in_specs=(P_(axes), P_(axes)),
                             out_specs=(P_(axes), P_(axes)),
                             check_vma=False))


def batch_permanents_on_mesh(stack, mesh: Mesh, *,
                             precision: str = "dq_acc",
                             num_chunks: int = 4096) -> np.ndarray:
    """Permanents of a (B, n, n) stack, batch axis sharded over ``mesh``.

    Each device computes the full 2^{n-1} step space for the matrices it
    owns (data parallelism over the bucket), so there is no cross-device
    reduction at all; ragged tails (B not divisible by the device count)
    are padded with zero matrices whose results are discarded on the
    host.  Values are bit-identical to ``ryser.perm_ryser_batched`` for
    every precision mode -- the per-device body shares its trace.
    Complex stacks shard their split (re, im) planes through
    ``ryser.batched_values_complex`` under the same contract.
    """
    stack = np.asarray(stack)
    if stack.ndim != 3 or stack.shape[1] != stack.shape[2]:
        raise ValueError(f"(B, n, n) stack required, got {stack.shape}")
    B, n = stack.shape[0], stack.shape[1]
    if n == 1:
        return np.asarray(stack[:, 0, 0])
    if n == 2:
        return np.asarray(stack[:, 0, 0] * stack[:, 1, 1]
                          + stack[:, 0, 1] * stack[:, 1, 0])
    is_complex = np.iscomplexobj(stack)
    stack = stack.astype(np.complex128 if is_complex else np.float64)
    pad = _batch_pad(B, mesh)
    if pad:
        stack = np.concatenate(
            [stack, np.zeros((pad, n, n), stack.dtype)], axis=0)
    axes = tuple(mesh.axis_names)
    T, C, _ = chunk_geometry(n, num_chunks)
    shard = NamedSharding(mesh, P_(axes))
    if is_complex:
        vr, vi = launch_and_wait(
            _dense_batch_mesh_fn_complex(mesh, T, C,
                                         complex_precision(precision)),
            jax.device_put(np.ascontiguousarray(stack.real), shard),
            jax.device_put(np.ascontiguousarray(stack.imag), shard))
        return (vr + 1j * vi)[:B]
    dev_stack = jax.device_put(stack, shard)
    return launch_and_wait(_dense_batch_mesh_fn(mesh, T, C, precision),
                           dev_stack)[:B]


@lru_cache(maxsize=None)
def _sparse_batch_mesh_fn(mesh: Mesh, T: int, C: int, precision: str):
    from .sparyser import sparse_batched_values
    axes = tuple(mesh.axis_names)

    def body(A_local, rows_local, vals_local):
        return sparse_batched_values(A_local, rows_local, vals_local,
                                     T, C, precision)

    return jax.jit(shard_map(body, mesh=mesh,
                             in_specs=(P_(axes), P_(axes), P_(axes)),
                             out_specs=P_(axes), check_vma=False))


@lru_cache(maxsize=None)
def _sparse_batch_mesh_fn_complex(mesh: Mesh, T: int, C: int,
                                  precision: str):
    from .sparyser import sparse_batched_values_complex
    axes = tuple(mesh.axis_names)

    def body(Ar_local, Ai_local, rows_local, vr_local, vi_local):
        return sparse_batched_values_complex(
            Ar_local, Ai_local, rows_local, vr_local, vi_local,
            T, C, precision)

    return jax.jit(shard_map(body, mesh=mesh,
                             in_specs=(P_(axes),) * 5,
                             out_specs=(P_(axes), P_(axes)),
                             check_vma=False))


@lru_cache(maxsize=None)
def _sparse_batch_mesh_fn_pallas(mesh: Mesh, precision: str):
    """Per-device SpaRyser *kernel* over the local sub-stack: the sparse
    analogue of ``permanent_on_mesh``'s ``backend="pallas"`` -- each
    device launches the (batch, block)-grid padded-CCS kernel on the
    matrices it owns (``kernels.ops.sparse_batched_values_pallas``; the
    traced body splits complex planes itself, so one mesh program serves
    real and complex buckets alike).  Kernel numerics, not the jnp trace:
    values match the single-device pallas backend, and the jnp path to
    the usual 1e-9 kernel tolerance rather than bitwise.
    """
    from ..kernels.ops import sparse_batched_values_pallas
    axes = tuple(mesh.axis_names)

    def body(A_local, rows_local, vals_local):
        return sparse_batched_values_pallas(A_local, rows_local,
                                            vals_local, precision=precision)

    return jax.jit(shard_map(body, mesh=mesh, in_specs=(P_(axes),) * 3,
                             out_specs=P_(axes), check_vma=False))


def sparse_batch_permanents_on_mesh(sps: list, mesh: Mesh, *,
                                    precision: str = "dq_acc",
                                    num_chunks: int = 4096,
                                    backend: str = "jnp") -> np.ndarray:
    """Sparse-bucket analogue of :func:`batch_permanents_on_mesh`.

    The bucket is packed once on the host (``sparyser.pack_padded_ccs``,
    bucket-wide maxdeg -- padding scatters into the dummy row and never
    perturbs numerics), padded to the device count with inert all-dummy
    entries, and the padded-CCS SpaRyser body is sharded over the batch
    axis.  Bit-identical to ``sparyser.perm_sparyser_batched`` -- complex
    buckets included (split re/im planes through
    ``sparyser.sparse_batched_values_complex``).

    ``backend="pallas"`` runs the SpaRyser *kernel* per device instead of
    the jnp trace (real or complex, one body) -- the last ``--mesh``
    route that used to have no kernel option.  Kernel values agree with
    the jnp path to the established 1e-9 pallas tolerance (the bitwise
    contract is jnp<->distributed's, not the kernel's).
    """
    from .sparyser import pack_padded_ccs, perm_sparyser_chunked
    assert sps, "empty bucket"
    n = sps[0].n
    if n <= 2:
        return np.array([perm_sparyser_chunked(sp, num_chunks=num_chunks,
                                               precision=precision)
                         for sp in sps])
    A_stack, rows_stack, vals_stack = pack_padded_ccs(sps)
    B = A_stack.shape[0]
    pad = _batch_pad(B, mesh)
    if pad:
        maxdeg = rows_stack.shape[2]
        A_stack = np.concatenate(
            [A_stack, np.zeros((pad, n, n), A_stack.dtype)], axis=0)
        rows_stack = np.concatenate(
            [rows_stack, np.full((pad, n, maxdeg), n, np.int32)], axis=0)
        vals_stack = np.concatenate(
            [vals_stack, np.zeros((pad, n, maxdeg), vals_stack.dtype)],
            axis=0)
    axes = tuple(mesh.axis_names)
    T, C, _ = chunk_geometry(n, num_chunks)
    shard = NamedSharding(mesh, P_(axes))
    if backend == "pallas":
        return launch_and_wait(
            _sparse_batch_mesh_fn_pallas(mesh, precision),
            jax.device_put(A_stack, shard),
            jax.device_put(rows_stack, shard),
            jax.device_put(vals_stack, shard))[:B]
    if np.iscomplexobj(vals_stack):
        vr, vi = launch_and_wait(
            _sparse_batch_mesh_fn_complex(mesh, T, C,
                                          complex_precision(precision)),
            jax.device_put(np.ascontiguousarray(A_stack.real), shard),
            jax.device_put(np.ascontiguousarray(A_stack.imag), shard),
            jax.device_put(rows_stack, shard),
            jax.device_put(np.ascontiguousarray(vals_stack.real), shard),
            jax.device_put(np.ascontiguousarray(vals_stack.imag), shard))
        return (vr + 1j * vi)[:B]
    return launch_and_wait(
        _sparse_batch_mesh_fn(mesh, T, C, precision),
        jax.device_put(A_stack, shard), jax.device_put(rows_stack, shard),
        jax.device_put(vals_stack, shard))[:B]


class CampaignPaused(Exception):
    """A wave-budgeted campaign ran out of ``max_waves`` with slices still
    pending.  Carries the in-memory :class:`JobState` so the caller can
    keep driving the same job (``run_campaign(..., state=exc.state)``)
    without re-reading the checkpoint."""

    def __init__(self, state: JobState):
        self.state = state
        super().__init__(
            f"campaign paused at {state.fraction_done():.1%} "
            f"({len(state.pending_slices())} of {state.total_slices} "
            "slices pending)")


def run_campaign(A, mesh: Mesh, *, total_slices: int, chunks_per_slice: int,
                 chunk_size: int, precision: str = "dq_acc",
                 backend: str = "jnp", geometry: Geometry | None = None,
                 checkpoint_path: str | None = None,
                 state: JobState | None = None, progress_cb=None,
                 max_waves: int | None = None, max_wave_retries: int = 2,
                 counters: Counter | None = None):
    """Execute a step-space campaign in device-count-sized waves.

    The unit of work is a *slice* (contiguous block of ``chunks_per_slice``
    chunks of ``chunk_size`` Gray steps); the decomposition comes from the
    caller (``core.stepspace.plan_slices`` via the planner's
    ``CampaignSpec``) and is independent of the runtime device count, so:

    * waves are re-formed from the pending slice set each iteration --
      a resumed job may use any mesh (elastic);
    * a failed/preempted wave records nothing; its slices stay pending
      and are re-queued into the next wave (straggler rebalance at wave
      granularity; after ``max_wave_retries`` consecutive failures the
      error propagates);
    * after each wave the twofloat per-slice partials are checkpointed
      (``JobState``, config-safe ``.npz``), losing at most one wave to a
      SIGKILL;
    * the final reduction is a fixed slice-id-order twofloat sum, so a
      killed-and-resumed run -- under any device count -- is
      bitwise-identical to an uninterrupted one.

    Returns ``(value, state)``; ``value`` is ``None`` when ``max_waves``
    paused the run with slices still pending (callers that need the
    pause as control flow raise :class:`CampaignPaused`, e.g. the
    executor's ``CampaignBackend``).

    Spans (``repro.utils.spans``): ``campaign.wave`` (``wave``,
    ``slices``, ``chips``) around each wave, holding its
    ``engine.launch``/``engine.wait`` and its ``campaign.checkpoint``
    (``bytes``); ``campaign.reduce`` around the final reduce and the host
    epilogue.  ``counters`` gains ``waves``, ``slices``,
    ``retried_waves`` and ``checkpoint_bytes``.
    """
    A = np.asarray(A)
    n = A.shape[0]
    D = math.prod(mesh.devices.shape)
    if state is None:
        state = JobState.load_or_create(
            checkpoint_path, A, total_slices, precision=precision,
            backend=backend, chunks_per_slice=chunks_per_slice,
            chunk_size=chunk_size,
            geometry=geometry.tag() if geometry is not None else "-")
    counters = Counter() if counters is None else counters
    waves = 0
    retries = 0
    while True:
        pending = state.pending_slices()
        if not pending:
            break
        if max_waves is not None and waves >= max_waves:
            return None, state
        wave = pending[:D]
        ids = np.array(wave + [-1] * (D - len(wave)), dtype=np.int32)
        with span("campaign.wave", wave=waves, slices=len(wave), chips=D):
            try:
                his, los = slice_sums_on_mesh(
                    A, mesh, ids, chunks_per_slice=chunks_per_slice,
                    chunk_size=chunk_size, precision=precision,
                    backend=backend, geometry=geometry)
            except Exception:
                # preempted/straggling wave: nothing recorded, its slices
                # stay pending and the next iteration re-forms the wave
                retries += 1
                counters["retried_waves"] += 1
                if retries > max_wave_retries:
                    raise
                continue
            retries = 0
            # discard sentinel-padded lanes explicitly: only the wave's
            # own slice ids are recorded
            state.record_wave(wave, his[:len(wave)], los[:len(wave)])
            waves += 1
            counters["waves"] += 1
            counters["slices"] += len(wave)
            if checkpoint_path:
                with span("campaign.checkpoint") as ck:
                    ck.attrs["bytes"] = state.save(checkpoint_path)
                counters["checkpoint_bytes"] += ck.attrs["bytes"]
        if progress_cb:
            progress_cb(state)

    with span("campaign.reduce"):
        hi, lo = state.reduce()
        # the epilogue is host arithmetic: a few adds, and no c128 value
        # ever reaches the device
        p0 = np.prod(nw_base_vector(A))  # permlint: disable=PL001  # length-n product, shape set by the matrix
        total = P.tf_add_acc(P.TwoFloat(np.asarray(hi), np.asarray(lo)),
                             p0)
        # .item(): float for real jobs (the legacy return type), complex
        # for complex jobs
        value = np.asarray(P.tf_value(total)).item() * _final_factor(n)
    return value, state


@dataclass
class DistributedPermanent:
    """Checkpointable, elastic multi-slice permanent job (legacy wrapper).

    Pre-plan-era entry point kept for direct library use; the slice
    decomposition is derived from THIS mesh's device count, and the wave
    loop is :func:`run_campaign`.  New code should route through the
    planner (``SolverConfig.campaign_threshold``) so the decomposition is
    recorded in the ``ExecutionPlan`` and independent of the mesh.
    """
    mesh: Mesh
    precision: str = "dq_acc"
    slices_per_device: int = 8
    lanes_per_device: int = 1024
    checkpoint_path: str | None = None
    backend: str = "jnp"          # "pallas" -> per-device TPU kernel

    def permanent(self, A, progress_cb=None):
        A = np.asarray(A)
        n = A.shape[0]
        D = math.prod(self.mesh.devices.shape)
        total_slices, chunks_per_slice, C = plan_slices(
            n, D, self.slices_per_device, self.lanes_per_device)
        value, _ = run_campaign(
            A, self.mesh, total_slices=total_slices,
            chunks_per_slice=chunks_per_slice, chunk_size=C,
            precision=self.precision, backend=self.backend,
            checkpoint_path=self.checkpoint_path, progress_cb=progress_cb)
        return value
