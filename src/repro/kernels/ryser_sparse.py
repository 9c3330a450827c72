"""Pallas SpaRyser kernels over the padded-CCS layout (paper Alg. 2).

The dense kernels (``ryser_pallas`` / ``ryser_complex``) update the
row-sum state X with whole matrix columns; here the Gray-code column
updates come from the shape-static padded CCS arrays that
``sparyser.pack_padded_ccs`` already produces -- per column ``j`` a
``(rows[j], vals[j])`` pair of length ``maxdeg``, padded with
``(row=n, val=0)`` entries that scatter into the dummy row (or nowhere
at all when ``n == n_pad``) and are arithmetically inert.

TPU mapping: a data-dependent scatter does not vectorize on the VPU, so
each padded column is first *densified in VMEM* with a one-hot compare
against a row iota -- ``u_j[i] = sum_d [rows[j, d] == i] * vals[j, d]``,
an (n_pad, maxdeg) compare + matvec instead of the dense kernels'
(n_pad,) column slice.  The CEG window schedule only ever flips the
``kw = log2(Wu)`` low columns at inner steps, so the kernel scatters
exactly ``kw`` columns once per block and generates the per-window
states from the *same* cumulative signed schedule as the dense batched
mode (``_cumsig_host``), restricted to those rows:

    D = U @ c0[:kw]        instead of        D = A @ c0

-- an (n_pad, kw, Wu) contraction instead of (n_pad, n_pad, Wu).  Chunk
init and the per-lane boundary column keep the dense one-hot MXU path
(the dense matrix is resident anyway, exactly like the jnp SpaRyser
engine keeps A for its init matmul).

Geometry (``kernel_geometry``), the u64 lane math, the window schedule
(``_signed_const_schedule`` / ``_cumsig_host``) and the
``device_base_u32`` traced-chunk-base convention are all shared with the
dense kernels, so the scalar launch runs under ``shard_map`` unchanged.
Launch shapes mirror ``ryser_pallas`` / ``ryser_complex``:

* ``ryser_sparse_pallas_call``                  -- grid (num_blocks,), one
  matrix, host-int OR traced device chunk base; (num_blocks, 2) partials.
* ``ryser_sparse_pallas_call_batched``          -- grid (batch, block),
  one launch covers a same-size bucket; (B, num_blocks, 2) partials.
* ``ryser_sparse_pallas_call_complex``          -- split re/im planes,
  (num_blocks, 4) partials (re_hi, re_err, im_hi, im_err).
* ``ryser_sparse_pallas_call_complex_batched``  -- (B, num_blocks, 4).

Real and complex share one pair of block bodies (``_ryser_block_sp`` /
``_ryser_block_sp_cx``), the body-sharing pattern the complex kernels
established.  Accumulation: ``dd``/``kahan``/``dq_acc``/``dq_fast`` per
lane (``qq`` runs as ``dd``, like every kernel); the cross-block twofloat
reduction lives in ops.py (``kernel_reduce``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import u64emu as U
from .ops import pallas_interpret
from .ryser_complex import _cprod
from .ryser_pallas import (_Z, _accum_add, _accum_make, _accum_value,
                           _cumsig_host, _mm, _partials_out, _rprod,
                           _signed_const_schedule, _write_partials,
                           device_base_u32)

__all__ = ["ryser_sparse_pallas_call", "ryser_sparse_pallas_call_batched",
           "ryser_sparse_pallas_call_complex",
           "ryser_sparse_pallas_call_complex_batched"]


def _chunk_starts(i, dev_base, TB: int, C: int):
    """(start64, lane iota) of this block's TB chunks -- u64 lane math
    identical to the dense block bodies."""
    k = int(math.log2(C))
    lane = jax.lax.broadcasted_iota(jnp.uint32, (1, TB), 1).reshape(TB)
    chunk64 = U.u64_add_u32((jnp.broadcast_to(dev_base[0], (TB,)),
                             jnp.broadcast_to(dev_base[1], (TB,))),
                            (i * TB).astype(jnp.uint32) + lane)
    return U.u64_shl(chunk64, k), lane


def _gray_init_bits(start64, n: int, n_pad: int, TB: int, dtype):
    """(n_pad, TB) Gray-code bit matrix of the chunk start steps."""
    gbits = U.u64_gray(start64)
    rows = [U.to_float(U.u64_bit(gbits, np.uint32(j)), dtype) if j < n
            else jnp.zeros((TB,), dtype) for j in range(n_pad)]
    return jnp.stack(rows, axis=0)


def _scatter_low_columns(rows, vals, kw: int, n_pad: int, dtype):
    """Densify the ``kw`` low CCS columns the window schedule flips.

    ``rows``/``vals`` are the (n, maxdeg) padded CCS arrays; returns
    U (n_pad, kw) with ``U[i, j] = sum_d [rows[j, d] == i] vals[j, d]``.
    Padding entries point at the dummy row ``n``: when ``n < n_pad`` they
    scatter ``val = 0`` (inert), when ``n == n_pad`` the compare matches
    nothing -- either way padded X rows stay exactly 1.
    """
    maxdeg = rows.shape[-1]
    riota = jax.lax.broadcasted_iota(jnp.int32, (n_pad, maxdeg), 0)
    cols = []
    for j in range(kw):
        onehot = (riota == rows[j][None, :].astype(jnp.int32)).astype(dtype)
        cols.append(_mm(onehot, vals[j][:, None].astype(dtype),
                        dtype))                          # (n_pad, 1)
    return jnp.concatenate(cols, axis=1)                 # (n_pad, kw)


def _boundary_inputs(macro64, Wu: int, space: int, lane, n_pad: int, TB: int,
                     dtype):
    """Per-lane boundary-step (w = Wu) schedule: one-hot column selector,
    signed liveness mask -- shared verbatim with the dense block bodies."""
    space_m1 = U.u64_from_int(space - 1, like=lane)
    gb64 = U.u64_add_u32(macro64, np.uint32(Wu))
    jb = U.u64_ctz(gb64)
    sb = 2 * U.to_float(U.u64_bit(U.u64_gray(gb64), jb), dtype) - 1
    live = U.u64_leq(gb64, space_m1).astype(dtype)
    row_iota = jax.lax.broadcasted_iota(jnp.uint32, (n_pad, TB), 0)
    onehot = (row_iota == jb[None, :].astype(jnp.uint32)).astype(dtype)
    return onehot, sb * live, live


def _ryser_block_sp(i, A, rows, vals, xb, c0, dev_base, *, n: int,
                    n_pad: int, TB: int, C: int, Wu: int, space: int,
                    precision: str, dtype):
    """One grid block of the sparse kernel: TB chunks x C Gray steps.

    Shared between the single-matrix kernel (grid over blocks) and the
    batch-grid kernel (grid over (batch, block)); ``i`` is the block id
    along the chunk axis, ``dev_base`` the u32-pair device chunk base.
    Returns (hi, lo) scalars.
    """
    kw = int(math.log2(Wu))
    M = C // Wu
    dd = (((1,), (0,)), ((), ()))

    start64, lane = _chunk_starts(i, dev_base, TB, C)
    Gb = _gray_init_bits(start64, n, n_pad, TB, dtype)
    X = xb + _mm(A, Gb, dtype)

    sched = _signed_const_schedule(Wu)
    mid_idx = next((ix for ix, st in enumerate(sched) if st[2]), None)
    s_mid = sched[mid_idx][1] if mid_idx is not None else 0

    # window states from the scattered low columns -- macro-invariant:
    # the inner schedule flips columns 0..kw-1 in every window
    Ucols = _scatter_low_columns(rows, vals, kw, n_pad, dtype)
    D = _mm(Ucols, c0[:kw, :], dtype)  # (n_pad, Wu-1)
    col_mid = Ucols[:, kw - 1:kw]

    def macro_body(m, carry):
        X, acc = carry
        macro64 = U.u64_add_u32(start64,
                                m.astype(jnp.uint32) * np.uint32(Wu))
        bitk = U.to_float(U.u64_bit(macro64, np.uint32(kw)), dtype)
        corr = col_mid * (float(-2.0 * s_mid) * bitk)[None, :]
        for idx, (j, s, is_mid, parity) in enumerate(sched):
            state = X + D[:, idx][:, None]
            if mid_idx is not None and idx >= mid_idx:
                state = state + corr
            prod = _rprod(state)
            acc = _accum_add(acc, -prod if parity else prod, precision)
        X = X + D[:, Wu - 2][:, None] if Wu >= 2 else X
        if mid_idx is not None:
            X = X + corr

        # boundary step w = Wu: per-lane column via one-hot MXU (dense A
        # is resident for the init matmul anyway -- same as jnp SpaRyser)
        onehot, sgn, live = _boundary_inputs(macro64, Wu, space, lane,
                                             n_pad, TB, dtype)
        colb = _mm(A, onehot, dtype)
        X = X + colb * sgn[None, :]
        prod = _rprod(X)
        acc = _accum_add(acc, prod * live, precision)  # (-1)^Wu == +1
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


def _ryser_block_sp_cx(i, Ar, Ai, rows, vals_r, vals_i, xbr, xbi, c0,
                       dev_base, *, n: int, n_pad: int, TB: int, C: int,
                       Wu: int, space: int, precision: str, dtype):
    """Split-plane complex sparse block body; mirrors ``_ryser_block_sp``
    with the matrix carried as (re, im) planes and the product chain as
    the complex multiply recurrence (``ryser_complex._cprod``).  Returns
    the four scalars (re_hi, re_err, im_hi, im_err)."""
    kw = int(math.log2(Wu))
    M = C // Wu
    dd = (((1,), (0,)), ((), ()))

    start64, lane = _chunk_starts(i, dev_base, TB, C)
    Gb = _gray_init_bits(start64, n, n_pad, TB, dtype)
    Xr = xbr + _mm(Ar, Gb, dtype)
    Xi = xbi + _mm(Ai, Gb, dtype)

    sched = _signed_const_schedule(Wu)
    mid_idx = next((ix for ix, st in enumerate(sched) if st[2]), None)
    s_mid = sched[mid_idx][1] if mid_idx is not None else 0

    Ur = _scatter_low_columns(rows, vals_r, kw, n_pad, dtype)
    Ui = _scatter_low_columns(rows, vals_i, kw, n_pad, dtype)
    Dr = _mm(Ur, c0[:kw, :], dtype)
    Di = _mm(Ui, c0[:kw, :], dtype)
    cmr = Ur[:, kw - 1:kw]
    cmi = Ui[:, kw - 1:kw]

    def macro_body(m, carry):
        Xr, Xi, acc_r, acc_i = carry
        macro64 = U.u64_add_u32(start64,
                                m.astype(jnp.uint32) * np.uint32(Wu))
        bitk = U.to_float(U.u64_bit(macro64, np.uint32(kw)), dtype)
        corr = (float(-2.0 * s_mid) * bitk)[None, :]
        for idx, (j, s, is_mid, parity) in enumerate(sched):
            sr = Xr + Dr[:, idx][:, None]
            si = Xi + Di[:, idx][:, None]
            if mid_idx is not None and idx >= mid_idx:
                sr = sr + cmr * corr
                si = si + cmi * corr
            pr, pi = _cprod(sr, si, n_pad)
            acc_r = _accum_add(acc_r, -pr if parity else pr, precision)
            acc_i = _accum_add(acc_i, -pi if parity else pi, precision)
        Xr = Xr + Dr[:, Wu - 2][:, None]
        Xi = Xi + Di[:, Wu - 2][:, None]
        if mid_idx is not None:
            Xr = Xr + cmr * corr
            Xi = Xi + cmi * corr

        # boundary step (dense one-hot MXU, both planes)
        onehot, sgn, live = _boundary_inputs(macro64, Wu, space, lane,
                                             n_pad, TB, dtype)
        colr = _mm(Ar, onehot, dtype)
        coli = _mm(Ai, onehot, dtype)
        Xr = Xr + colr * sgn[None, :]
        Xi = Xi + coli * sgn[None, :]
        pr, pi = _cprod(Xr, Xi, n_pad)
        acc_r = _accum_add(acc_r, pr * live, precision)  # (-1)^Wu == +1
        acc_i = _accum_add(acc_i, pi * live, precision)
        return (Xr, Xi, acc_r, acc_i)

    acc_r = _accum_make(dtype, (TB,))
    acc_i = _accum_make(dtype, (TB,))
    if M == 1:
        Xr, Xi, acc_r, acc_i = macro_body(jnp.int32(0),
                                          (Xr, Xi, acc_r, acc_i))
    else:
        Xr, Xi, acc_r, acc_i = jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(M), macro_body, (Xr, Xi, acc_r, acc_i))

    zero = jnp.zeros((), dtype)
    keep_err = precision in ("dq_acc", "dq_fast")
    re_err = jnp.sum(acc_r[1]) if keep_err else zero  # permlint: disable=PL001  # in-kernel lane reduce, under the 1e-9 kernel contract
    im_err = jnp.sum(acc_i[1]) if keep_err else zero  # permlint: disable=PL001  # in-kernel lane reduce, under the 1e-9 kernel contract
    return jnp.sum(acc_r[0]), re_err, jnp.sum(acc_i[0]), im_err  # permlint: disable=PL001  # in-kernel lane reduce, under the 1e-9 kernel contract


# ---------------------------------------------------------------------------
# pallas_call wrappers (launch shapes mirror ryser_pallas / ryser_complex)
# ---------------------------------------------------------------------------

def _ryser_sp_kernel(base_hi_ref, base_lo_ref, A_ref, rows_ref, vals_ref,
                     xb_ref, c0_ref, out_ref, **geom):
    """Single-matrix kernel: grid = (num_blocks,); writes (hi, lo)."""
    dev = (base_hi_ref[0, 0].astype(jnp.uint32),
           base_lo_ref[0, 0].astype(jnp.uint32))
    _write_partials(out_ref, _ryser_block_sp(
        pl.program_id(0), A_ref[...], rows_ref[...], vals_ref[...],
        xb_ref[...], c0_ref[...], dev, **geom))


def _ryser_sp_kernel_batched(A_ref, rows_ref, vals_ref, xb_ref, c0_ref,
                             out_ref, **geom):
    """Batch-grid kernel: grid = (B, num_blocks); one launch covers the
    whole bucket.  Block b of the stacks is selected by the BlockSpec;
    the chunk base is 0 (each matrix owns its full iteration space)."""
    zero = jnp.uint32(0)
    _write_partials(out_ref, _ryser_block_sp(
        pl.program_id(1), A_ref[0], rows_ref[0], vals_ref[0], xb_ref[0],
        c0_ref[...], (zero, zero), **geom))


def _ryser_sp_kernel_cx(base_hi_ref, base_lo_ref, Ar_ref, Ai_ref, rows_ref,
                        vr_ref, vi_ref, xbr_ref, xbi_ref, c0_ref, out_ref,
                        **geom):
    """Single-matrix complex kernel: grid = (num_blocks,); 4 partials."""
    dev = (base_hi_ref[0, 0].astype(jnp.uint32),
           base_lo_ref[0, 0].astype(jnp.uint32))
    _write_partials(out_ref, _ryser_block_sp_cx(
        pl.program_id(0), Ar_ref[...], Ai_ref[...], rows_ref[...],
        vr_ref[...], vi_ref[...], xbr_ref[...], xbi_ref[...], c0_ref[...],
        dev, **geom))


def _ryser_sp_kernel_cx_batched(Ar_ref, Ai_ref, rows_ref, vr_ref, vi_ref,
                                xbr_ref, xbi_ref, c0_ref, out_ref, **geom):
    """Batch-grid complex kernel: grid = (B, num_blocks); 4 partials."""
    zero = jnp.uint32(0)
    _write_partials(out_ref, _ryser_block_sp_cx(
        pl.program_id(1), Ar_ref[0], Ai_ref[0], rows_ref[0], vr_ref[0],
        vi_ref[0], xbr_ref[0], xbi_ref[0], c0_ref[...], (zero, zero),
        **geom))


def _c0_input(Wu: int, n_pad: int, dtype):
    return jnp.asarray(_cumsig_host(_signed_const_schedule(Wu), n_pad), dtype)


def ryser_sparse_pallas_call(A_pad, rows, vals, xb, dev_chunk_base, *,
                             n: int, TB: int, C: int, Wu: int,
                             num_blocks: int, precision: str = "dq_acc",
                             interpret: bool | None = None, vma=None):
    """(num_blocks, 2) sparse (hi, lo) partials, base g=0 term NOT included.

    ``rows``/``vals`` are the (n, maxdeg) padded CCS arrays of ONE matrix;
    ``dev_chunk_base`` may be a host int or a traced scalar (the
    distributed shard_map path), exactly like the dense kernels.
    """
    interpret = pallas_interpret(A_pad, vals, xb, interpret=interpret)
    n_pad = A_pad.shape[0]
    dtype = A_pad.dtype
    maxdeg = rows.shape[-1]
    base_hi, base_lo = device_base_u32(dev_chunk_base)
    c0 = _c0_input(Wu, n_pad, dtype)
    kernel = functools.partial(
        _ryser_sp_kernel, n=n, n_pad=n_pad, TB=TB, C=C, Wu=Wu,
        space=1 << (n - 1), precision=precision, dtype=dtype)
    rep = lambda i: (_Z, _Z)
    out_spec, out_shape = _partials_out(num_blocks, dtype, vma=vma)
    return pl.pallas_call(
        kernel,
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec((1, 1), rep), pl.BlockSpec((1, 1), rep),
            pl.BlockSpec((n_pad, n_pad), rep),
            pl.BlockSpec((n, maxdeg), rep),
            pl.BlockSpec((n, maxdeg), rep),
            pl.BlockSpec((n_pad, 1), rep),
            pl.BlockSpec(c0.shape, rep),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(base_hi, base_lo, A_pad, rows, vals, xb, c0)[:, 0, :2]


def ryser_sparse_pallas_call_batched(A_pads, rows_stack, vals_stack,
                                     xb_pads, *, n: int, TB: int, C: int,
                                     Wu: int, num_blocks: int,
                                     precision: str = "dq_acc",
                                     interpret: bool | None = None):
    """Launch ONE sparse kernel over a (B, n_pad, n_pad) + (B, n, maxdeg)
    padded-CCS bucket: grid is (batch, block), the sparse analogue of
    ``ryser_pallas_call_batched`` (same geometry inputs and window
    schedule).  Returns (B, num_blocks, 2) (hi, lo) partials."""
    interpret = pallas_interpret(A_pads, vals_stack, xb_pads,
                                 interpret=interpret)
    B, n_pad, _ = A_pads.shape
    dtype = A_pads.dtype
    maxdeg = rows_stack.shape[-1]
    c0 = _c0_input(Wu, n_pad, dtype)
    kernel = functools.partial(
        _ryser_sp_kernel_batched, n=n, n_pad=n_pad, TB=TB, C=C, Wu=Wu,
        space=1 << (n - 1), precision=precision, dtype=dtype)
    sel = lambda b, i: (b, _Z, _Z)
    out_spec, out_shape = _partials_out(num_blocks, dtype, batch=B)
    return pl.pallas_call(
        kernel,
        grid=(B, num_blocks),
        in_specs=[
            pl.BlockSpec((1, n_pad, n_pad), sel),
            pl.BlockSpec((1, n, maxdeg), sel),
            pl.BlockSpec((1, n, maxdeg), sel),
            pl.BlockSpec((1, n_pad, 1), sel),
            pl.BlockSpec(c0.shape, lambda b, i: (_Z, _Z)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(A_pads, rows_stack, vals_stack, xb_pads, c0)[:, :, 0, :2]


def ryser_sparse_pallas_call_complex(Ar_pad, Ai_pad, rows, vals_r, vals_i,
                                     xbr, xbi, dev_chunk_base, *, n: int,
                                     TB: int, C: int, Wu: int,
                                     num_blocks: int,
                                     precision: str = "dq_acc",
                                     interpret: bool | None = None,
                                     vma=None):
    """(num_blocks, 4) split-plane sparse partials
    (re_hi, re_err, im_hi, im_err); chunk base host int or traced."""
    interpret = pallas_interpret(Ar_pad, Ai_pad, vals_r, vals_i, xbr, xbi,
                                 interpret=interpret)
    n_pad = Ar_pad.shape[0]
    dtype = Ar_pad.dtype
    maxdeg = rows.shape[-1]
    base_hi, base_lo = device_base_u32(dev_chunk_base)
    c0 = _c0_input(Wu, n_pad, dtype)
    kernel = functools.partial(
        _ryser_sp_kernel_cx, n=n, n_pad=n_pad, TB=TB, C=C, Wu=Wu,
        space=1 << (n - 1), precision=precision, dtype=dtype)
    rep = lambda i: (_Z, _Z)
    out_spec, out_shape = _partials_out(num_blocks, dtype, vma=vma)
    return pl.pallas_call(
        kernel,
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec((1, 1), rep), pl.BlockSpec((1, 1), rep),
            pl.BlockSpec((n_pad, n_pad), rep),
            pl.BlockSpec((n_pad, n_pad), rep),
            pl.BlockSpec((n, maxdeg), rep),
            pl.BlockSpec((n, maxdeg), rep),
            pl.BlockSpec((n, maxdeg), rep),
            pl.BlockSpec((n_pad, 1), rep), pl.BlockSpec((n_pad, 1), rep),
            pl.BlockSpec(c0.shape, rep),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(base_hi, base_lo, Ar_pad, Ai_pad, rows, vals_r, vals_i, xbr, xbi,
      c0)[:, 0, :4]


def ryser_sparse_pallas_call_complex_batched(Ar_pads, Ai_pads, rows_stack,
                                             vals_r_stack, vals_i_stack,
                                             xbr_pads, xbi_pads, *, n: int,
                                             TB: int, C: int, Wu: int,
                                             num_blocks: int,
                                             precision: str = "dq_acc",
                                             interpret: bool | None = None):
    """(B, num_blocks, 4) split-plane sparse partials over a (batch, block)
    grid -- the complex analogue of ``ryser_sparse_pallas_call_batched``."""
    interpret = pallas_interpret(Ar_pads, Ai_pads, vals_r_stack,
                                 vals_i_stack, xbr_pads, xbi_pads,
                                 interpret=interpret)
    B, n_pad, _ = Ar_pads.shape
    dtype = Ar_pads.dtype
    maxdeg = rows_stack.shape[-1]
    c0 = _c0_input(Wu, n_pad, dtype)
    kernel = functools.partial(
        _ryser_sp_kernel_cx_batched, n=n, n_pad=n_pad, TB=TB, C=C, Wu=Wu,
        space=1 << (n - 1), precision=precision, dtype=dtype)
    sel = lambda b, i: (b, _Z, _Z)
    out_spec, out_shape = _partials_out(num_blocks, dtype, batch=B)
    return pl.pallas_call(
        kernel,
        grid=(B, num_blocks),
        in_specs=[
            pl.BlockSpec((1, n_pad, n_pad), sel),
            pl.BlockSpec((1, n_pad, n_pad), sel),
            pl.BlockSpec((1, n, maxdeg), sel),
            pl.BlockSpec((1, n, maxdeg), sel),
            pl.BlockSpec((1, n, maxdeg), sel),
            pl.BlockSpec((1, n_pad, 1), sel),
            pl.BlockSpec((1, n_pad, 1), sel),
            pl.BlockSpec(c0.shape, lambda b, i: (_Z, _Z)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(Ar_pads, Ai_pads, rows_stack, vals_r_stack, vals_i_stack,
      xbr_pads, xbi_pads, c0)[:, :, 0, :4]
