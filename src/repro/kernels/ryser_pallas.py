"""Pallas TPU kernels for Gray-code Ryser permanents (paper Sec. 3).

Geometry (one ``pallas_call``):

    grid = (num_blocks,)                 one block per VMEM-resident lane set
    block = TB chunks (lanes)            each lane owns one Alg.-3 chunk
    chunk = C = Wu * M Gray steps        M macro-windows of Wu steps each

TPU mapping of the paper's GPU optimizations (DESIGN.md Sec. 2):

* CEG (Sec. 3.2.1): chunks are power-of-2 sized and window-aligned, so for
  local steps ``w = 1 .. Wu-1`` the changed bit ``ctz(w)`` and (almost
  always) the sign are *host constants* -- the column update is a broadcast
  ``X += s * A[:, j]`` with zero gathers.  Only each window's boundary step
  has per-lane columns; it is resolved with a one-hot MXU matmul.
* x in registers (Sec. 3.3): the whole X tile (n_pad, TB) lives in VMEM and
  the Wu-step schedule is unrolled at trace time -- the analogue of the
  paper's matrix-specific rebuild.
* A in shared memory (Sec. 3.2): A is a replicated (n_pad, n_pad) VMEM
  block.
* 64-bit step indices: TPU has no i64; chunk ids/steps use uint32-pair
  emulation (kernels/u64emu.py).

Two modes:

* ``baseline``  -- paper-faithful Alg. 3: sequential X updates per step.
* ``batched``   -- beyond-paper window-batched form: per-window states are
  generated as ``X0 + A @ cumsig`` (one MXU matmul, lane-shared), removing
  the serial X dependency and all per-step X writes (see DESIGN.md and
  EXPERIMENTS.md Sec. Perf).

Accumulation: ``dd`` (plain), ``kahan``, ``dq_acc`` (twofloat) per lane;
the cross-lane / cross-block reduction happens outside in ops.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..core import gray as G
from ..core.stepspace import kernel_geometry
from ..utils.compat import shape_dtype_struct
from . import u64emu as U
from .ops import pallas_interpret

__all__ = ["ryser_pallas_call", "ryser_pallas_call_batched",
           "kernel_geometry", "device_base_u32"]

# Per-block partials leave a kernel as one whole (8, 128) tile (an f32
# vreg) with the values in the first lanes of row 0: Mosaic tiles the
# last two dims of every block by (8, 128), and a (1, 2) block does not
# lower.  The wrappers slice the values back out.
_TILE = (8, 128)

# Block index maps return int32 zeros: under x64 a Python 0 lowers as an
# i64 constant, which Mosaic cannot legalize.
_Z = np.int32(0)


def _write_partials(out_ref, values):
    """Store ``values`` (scalars) into lanes 0.. of row 0 of the block's
    tile, zeros elsewhere; the whole tile is written at once."""
    row = jax.lax.broadcasted_iota(jnp.int32, _TILE, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, _TILE, 1)
    tile = jnp.zeros(_TILE, out_ref.dtype)
    for k, v in enumerate(values):
        tile = jnp.where((row == 0) & (lane == k), v, tile)
    out_ref[...] = tile.reshape(out_ref.shape)


def _partials_out(num_blocks: int, dtype, batch: int | None = None,
                  vma=None):
    """(out_spec, out_shape) of the per-block partial tiles."""
    if batch is None:
        return (pl.BlockSpec((1,) + _TILE, lambda i: (i, _Z, _Z)),
                shape_dtype_struct((num_blocks,) + _TILE, dtype, vma=vma))
    return (pl.BlockSpec((1, 1) + _TILE, lambda b, i: (b, i, _Z, _Z)),
            shape_dtype_struct((batch, num_blocks) + _TILE, dtype))


def device_base_u32(dev_chunk_base):
    """Encode a device chunk base as a (1, 1) uint32 (hi, lo) pair.

    Accepts a host int or a traced scalar (the distributed shard_map path):
    uint64 under x64 keeps the full range; 32-bit ints cover per-device
    ranges in tests.  Shared by the real and complex kernel wrappers.
    """
    if isinstance(dev_chunk_base, (int, np.integer)):
        base_hi = jnp.full((1, 1), (int(dev_chunk_base) >> 32) & 0xFFFFFFFF,
                           jnp.uint32)
        base_lo = jnp.full((1, 1), int(dev_chunk_base) & 0xFFFFFFFF,
                           jnp.uint32)
        return base_hi, base_lo
    b = jnp.asarray(dev_chunk_base)
    if b.dtype in (jnp.uint64, jnp.int64):
        base_hi = (b >> 32).astype(jnp.uint32).reshape(1, 1)
        base_lo = b.astype(jnp.uint32).reshape(1, 1)
    else:
        base_hi = jnp.zeros((1, 1), jnp.uint32) * b.astype(jnp.uint32)
        base_lo = b.astype(jnp.uint32).reshape(1, 1)
    return base_hi.reshape(1, 1), base_lo


def _signed_const_schedule(Wu: int):
    """Host schedule for inner steps w = 1..Wu-1 of any aligned window.

    Returns [(j, s_const, is_mid, parity)], where the true sign is
    ``s_const`` except at the mid step (w = Wu/2), where lanes whose window
    base has bit kw set use ``-s_const`` (see core/gray.py notes).
    """
    kw = int(math.log2(Wu))
    out = []
    for w in range(1, Wu):
        j = G.ctz(w)
        if j + 1 < kw or kw == 0:
            bit = ((w >> j) ^ (w >> (j + 1))) & 1
            is_mid = False
        else:  # w == Wu // 2, j == kw - 1
            bit = ((w >> j)) & 1  # == 1; true bit = 1 ^ bit_kw(base)
            is_mid = True
        s = 2 * bit - 1
        parity = w & 1
        out.append((j, s, is_mid, parity))
    return out


def _mm(a, b, dtype):
    """``a @ b`` at full precision.  On TPU a float32 dot at default
    precision runs one bfloat16 pass on the MXU (about 3 significant
    digits), which the kernels' float32 values cannot afford."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=dtype)


def _rprod(X):
    """Product over the rows of an (n_pad, TB) state, as a fixed chain
    (Mosaic has no reduce_prod)."""
    p = X[0]
    for i in range(1, X.shape[0]):
        p = p * X[i]
    return p


def _accum_make(dtype, shape):
    z = jnp.zeros(shape, dtype)
    return (z, z)


def _accum_add(acc, term, precision):
    s, c = acc
    if precision == "kahan":
        y = term - c
        t = s + y
        return (t, (t - s) - y)
    if precision == "dq_acc":
        # two_sum based twofloat accumulate
        hi = s + term
        bp = hi - s
        e = (s - (hi - bp)) + (term - bp)
        return (hi, c + e)
    if precision == "dq_fast":
        # Dekker-style sloppy twofloat accumulate (tf_add_fast): two_sum
        # into the hi limb, then renormalize with fast_two_sum
        hi = s + term
        bp = hi - s
        e = (s - (hi - bp)) + (term - bp) + c
        s2 = hi + e
        return (s2, e - (s2 - hi))
    return (s + term, c)  # dd (and qq: no twofloat product in-kernel)


def _accum_value(acc, precision):
    if precision in ("dq_acc", "dq_fast"):
        return acc[0], acc[1]
    return acc[0], jnp.zeros_like(acc[1])


def _sched_select_host(sched, n_pad: int) -> np.ndarray:
    """Per-step signed one-hot selection matrix (n_pad, Wu-1):
    column idx holds s_const(w) e_{j(w)}.  The wrapper multiplies by A to
    get the signed schedule columns (the 'schedmat' beyond-paper mode:
    the per-step broadcast-multiply and column slice both disappear --
    each inner step is ONE vector add + the product chain)."""
    S = np.zeros((n_pad, max(1, len(sched))), dtype=np.float64)
    for idx, (j, sgn, _is_mid, _) in enumerate(sched):
        S[j, idx] = sgn
    return S


def _cumsig_host(sched, n_pad: int) -> np.ndarray:
    """Cumulative signed one-hot schedule (n_pad, Wu-1) for batched mode.

    Column idx holds sum_{w' <= w} s_const(w') e_{j(w')}; the mid step's
    lane-dependent sign is corrected in-kernel.
    """
    C0 = np.zeros((n_pad, max(1, len(sched))), dtype=np.float64)
    run = np.zeros(n_pad, dtype=np.float64)
    for idx, (j, s, _is_mid, _) in enumerate(sched):
        run[j] += s
        C0[:, idx] = run
    return C0


def _ryser_block(i, A, xb, c0, dev_base, *,
                 n: int, n_pad: int, TB: int, C: int, Wu: int,
                 space: int, precision: str, mode: str, dtype):
    """One grid block: TB chunks x C Gray steps; returns (hi, lo) scalars.

    Shared between the single-matrix kernel (grid over blocks) and the
    batch-grid kernel (grid over (batch, block)); ``i`` is the block id
    along the chunk axis and ``dev_base`` the u32-pair device chunk base.
    """
    k = int(math.log2(C))
    kw = int(math.log2(Wu))
    M = C // Wu

    # ---- chunk ids & start steps (u64 lane math) ----
    # (1, TB) iota then reshape: Mosaic requires >= 2D iota on TPU
    lane = jax.lax.broadcasted_iota(jnp.uint32, (1, TB), 1).reshape(TB)
    block_first = (i * TB).astype(jnp.uint32)
    chunk64 = U.u64_add_u32((jnp.broadcast_to(dev_base[0], (TB,)),
                             jnp.broadcast_to(dev_base[1], (TB,))),
                            block_first + lane)
    start64 = U.u64_shl(chunk64, k)

    # ---- init X = xb + A @ graybits(start) (MXU) ----
    gbits_start = U.u64_gray(start64)
    rows = []
    for j in range(n_pad):
        if j < n:
            rows.append(U.to_float(U.u64_bit(gbits_start, np.uint32(j)), dtype))
        else:
            rows.append(jnp.zeros((TB,), dtype))
    Gb = jnp.stack(rows, axis=0)                     # (n_pad, TB)
    X = xb + _mm(A, Gb, dtype)

    sched = _signed_const_schedule(Wu)
    space_m1 = U.u64_from_int(space - 1, like=lane)
    row_iota = jax.lax.broadcasted_iota(jnp.uint32, (n_pad, TB), 0)

    # schedule-matrix kernel input: cumulative signed one-hots (batched)
    # or A-premultiplied signed columns (schedmat)
    if mode in ("batched", "schedmat"):
        C0 = c0                                      # (n_pad, Wu-1)
        mid_idx = next((ix for ix, st in enumerate(sched) if st[2]), None)

    def macro_body(m, carry):
        X, acc = carry
        m_u = m.astype(jnp.uint32) * np.uint32(Wu)
        macro64 = U.u64_add_u32(start64, m_u)
        # per-lane bit kw of the macro base (mid-step sign correction)
        bitk = U.to_float(U.u64_bit(macro64, np.uint32(kw)), dtype)  # (TB,)
        mid_flip = 1 - 2 * bitk                                  # +-1

        if mode == "baseline":
            for (j, s, is_mid, parity) in sched:
                colj = A[:, j:j + 1]  # (n_pad,1)
                if is_mid:
                    slane = (s * mid_flip)[None, :]              # (1, TB)
                    X = X + colj * slane
                else:
                    X = X + colj * float(s)
                prod = _rprod(X)
                term = -prod if parity else prod
                acc = _accum_add(acc, term, precision)
        elif mode == "schedmat":
            # beyond-paper: per-step signed column precomputed (C0 = A@Sel);
            # inner step = one add + product; mid step adds one correction
            col_mid = A[:, kw - 1:kw] \
                if kw >= 1 else jnp.zeros((n_pad, 1), dtype)
            for idx, (j, s, is_mid, parity) in enumerate(sched):
                X = X + C0[:, idx][:, None]
                if is_mid:
                    X = X + col_mid * (float(-2.0 * s) * bitk)[None, :]
                prod = _rprod(X)
                term = -prod if parity else prod
                acc = _accum_add(acc, term, precision)
        else:
            # window-batched: states from one shared matmul, X never written
            D = _mm(A, C0, dtype)  # (n_pad,Wu-1)
            col_mid = A[:, kw - 1:kw] if kw >= 1 \
                else jnp.zeros((n_pad, 1), dtype)
            # lanes with bitk=1 need mid sign -s i.e. subtract 2*s*col_mid
            s_mid = sched[mid_idx][1] if mid_idx is not None else 0
            corr = col_mid * (float(-2.0 * s_mid) * bitk)[None, :]
            for idx, (j, s, is_mid, parity) in enumerate(sched):
                state = X + D[:, idx][:, None]
                if mid_idx is not None and idx >= mid_idx:
                    state = state + corr
                prod = _rprod(state)
                term = -prod if parity else prod
                acc = _accum_add(acc, term, precision)
            # advance X to the last inner state for the boundary step
            X = X + D[:, Wu - 2][:, None] if Wu >= 2 else X
            if mid_idx is not None:
                X = X + corr

        # ---- boundary step w = Wu (per-lane column via one-hot MXU) ----
        gb64 = U.u64_add_u32(macro64, np.uint32(Wu))
        jb = U.u64_ctz(gb64)                                    # (TB,)
        sign_bit = U.to_float(U.u64_bit(U.u64_gray(gb64), jb), dtype)
        sb = 2 * sign_bit - 1                                   # (TB,)
        live = U.u64_leq(gb64, space_m1).astype(dtype)          # (TB,)
        onehot = (row_iota == jb[None, :].astype(jnp.uint32)).astype(dtype)
        colb = _mm(A, onehot, dtype)
        X = X + colb * (sb * live)[None, :]
        prod = _rprod(X)
        # (-1)^{g_boundary} == (-1)^{Wu} == +1 (Wu is even)
        acc = _accum_add(acc, prod * live, precision)
        return (X, acc)

    acc0 = _accum_make(dtype, (TB,))
    if M == 1:
        X, acc = macro_body(jnp.int32(0), (X, acc0))
    else:
        X, acc = jax.lax.fori_loop(jnp.int32(0), jnp.int32(M), macro_body,
                                   (X, acc0))

    hi, lo = _accum_value(acc, precision)
    # permlint: disable=PL001  # in-kernel lane reduce, under the 1e-9 kernel contract
    return jnp.sum(hi), jnp.sum(lo)


def _ryser_kernel(base_hi_ref, base_lo_ref, A_ref, xb_ref, c0_ref, out_ref,
                  **geom):
    """Single-matrix kernel: grid = (num_blocks,); writes (hi, lo)."""
    dev_base = (base_hi_ref[0, 0].astype(jnp.uint32),
                base_lo_ref[0, 0].astype(jnp.uint32))
    _write_partials(out_ref, _ryser_block(
        pl.program_id(0), A_ref[...], xb_ref[...], c0_ref[...], dev_base,
        **geom))


def _ryser_kernel_batched(A_ref, xb_ref, c0_ref, out_ref, **geom):
    """Batch-grid kernel: grid = (B, num_blocks); one launch covers the
    whole stack.  Block b of the A/xb stacks is selected by the BlockSpec;
    the chunk base is 0 (each matrix owns its full iteration space)."""
    zero = jnp.uint32(0)
    _write_partials(out_ref, _ryser_block(
        pl.program_id(1), A_ref[0], xb_ref[0], c0_ref[...], (zero, zero),
        **geom))


def ryser_pallas_call(A_pad, x_base_pad, dev_chunk_base, *,
                      n: int, TB: int, C: int, Wu: int, num_blocks: int,
                      precision: str = "dq_acc", mode: str = "baseline",
                      interpret: bool | None = None, vma=None):
    """Launch the kernel over ``num_blocks`` blocks; returns (blocks, 2)
    per-block (hi, lo) partial sums (base g=0 term NOT included)."""
    interpret = pallas_interpret(A_pad, x_base_pad, interpret=interpret)
    n_pad = A_pad.shape[0]
    dtype = A_pad.dtype
    space = 1 << (n - 1)
    base_hi, base_lo = device_base_u32(dev_chunk_base)
    sched = _signed_const_schedule(Wu)
    if mode == "schedmat":
        sel = jnp.asarray(_sched_select_host(sched, n_pad), dtype)
        c0 = A_pad @ sel                             # signed schedule columns
    else:
        c0 = jnp.asarray(_cumsig_host(sched, n_pad), dtype)

    kernel = functools.partial(
        _ryser_kernel, n=n, n_pad=n_pad, TB=TB, C=C, Wu=Wu, space=space,
        precision=precision, mode=mode, dtype=dtype)

    out_spec, out_shape = _partials_out(num_blocks, dtype, vma=vma)
    return pl.pallas_call(
        kernel,
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (_Z, _Z)),
            pl.BlockSpec((1, 1), lambda i: (_Z, _Z)),
            pl.BlockSpec((n_pad, n_pad), lambda i: (_Z, _Z)),
            pl.BlockSpec((n_pad, 1), lambda i: (_Z, _Z)),
            pl.BlockSpec(c0.shape, lambda i: (_Z, _Z)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(base_hi, base_lo, A_pad, x_base_pad, c0)[:, 0, :2]


def ryser_pallas_call_batched(A_pads, x_base_pads, *,
                              n: int, TB: int, C: int, Wu: int,
                              num_blocks: int, precision: str = "dq_acc",
                              mode: str = "batched",
                              interpret: bool | None = None):
    """Launch ONE kernel over a (B, n_pad, n_pad) stack: grid is
    (batch, block), so a single ``pallas_call`` covers every matrix's full
    2^{n-1} step space.  Returns (B, num_blocks, 2) (hi, lo) partials
    (base g=0 terms NOT included).

    ``schedmat`` mode premultiplies the schedule by A and is therefore
    per-matrix; the batch grid shares one schedule input, so only the
    A-independent ``baseline``/``batched`` modes are supported here.
    """
    if mode not in ("baseline", "batched"):
        raise ValueError(f"batch grid supports baseline|batched, got {mode}")
    interpret = pallas_interpret(A_pads, x_base_pads, interpret=interpret)
    B, n_pad, _ = A_pads.shape
    dtype = A_pads.dtype
    space = 1 << (n - 1)
    sched = _signed_const_schedule(Wu)
    c0 = jnp.asarray(_cumsig_host(sched, n_pad), dtype)

    kernel = functools.partial(
        _ryser_kernel_batched, n=n, n_pad=n_pad, TB=TB, C=C, Wu=Wu,
        space=space, precision=precision, mode=mode, dtype=dtype)

    out_spec, out_shape = _partials_out(num_blocks, dtype, batch=B)
    return pl.pallas_call(
        kernel,
        grid=(B, num_blocks),
        in_specs=[
            pl.BlockSpec((1, n_pad, n_pad), lambda b, i: (b, _Z, _Z)),
            pl.BlockSpec((1, n_pad, 1), lambda b, i: (b, _Z, _Z)),
            pl.BlockSpec(c0.shape, lambda b, i: (_Z, _Z)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(A_pads, x_base_pads, c0)[:, :, 0, :2]
