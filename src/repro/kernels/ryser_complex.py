"""Complex-matrix Pallas Ryser kernels (boson-sampling workloads, Sec. 1).

TPU VPUs have no complex dtype, so the kernels carry split re/im planes:
the row-sum state is (Xr, Xi), column updates are two real adds, and the
product chain is the complex multiply recurrence

    (pr, pi) <- (pr*xr - pi*xi, pr*xi + pi*xr)

unrolled over rows (4 mults + 2 adds per row per lane).  Geometry, u64
lane math, CEG window alignment and the boundary one-hot matmul are shared
with the real kernel (window-batched mode: per-window states from two real
MXU matmuls).  Padded rows multiply by (1 + 0i).

Two launch shapes, mirroring ``ryser_pallas``:

* ``ryser_pallas_call_complex``          -- grid (num_blocks,), one matrix;
  accepts a host int OR traced device chunk base, so the distributed
  step-space split can run it per device under shard_map.
* ``ryser_pallas_call_complex_batched``  -- grid (batch, block), one launch
  covers a whole same-size stack (the complex analogue of
  ``ryser_pallas_call_batched``); chunk bases are 0.

Both wrap the same block body ``_ryser_block_cx``.  Accumulation: dd or
kahan or dq_acc per component; output columns are
(re_hi, re_err, im_hi, im_err).
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
from .ryser_pallas import (_Z, _accum_add, _accum_make, _cumsig_host, _mm,
                           _partials_out, _signed_const_schedule,
                           _write_partials, device_base_u32)

__all__ = ["ryser_pallas_call_complex", "ryser_pallas_call_complex_batched"]


def _cprod(Xr, Xi, n_pad):
    """Complex product over rows: (n_pad, TB) x2 -> (TB,) x2."""
    pr, pi = Xr[0], Xi[0]
    for i in range(1, n_pad):
        pr, pi = pr * Xr[i] - pi * Xi[i], pr * Xi[i] + pi * Xr[i]
    return pr, pi


def _ryser_block_cx(i, Ar, Ai, xbr, xbi, c0, dev_base, *, n: int, n_pad: int,
                    TB: int, C: int, Wu: int, space: int, precision: str,
                    dtype):
    """One grid block of the split-plane kernel: TB chunks x C Gray steps.

    Shared between the single-matrix kernel (grid over blocks) and the
    batch-grid kernel (grid over (batch, block)), exactly like the real
    kernel's ``_ryser_block``; ``i`` is the block id along the chunk axis
    and ``dev_base`` the u32-pair device chunk base.  Returns the four
    scalars (re_hi, re_err, im_hi, im_err).
    """
    k = int(math.log2(C))
    kw = int(math.log2(Wu))
    M = C // Wu

    lane = jax.lax.broadcasted_iota(jnp.uint32, (1, TB), 1).reshape(TB)
    chunk64 = U.u64_add_u32((jnp.broadcast_to(dev_base[0], (TB,)),
                             jnp.broadcast_to(dev_base[1], (TB,))),
                            (i * TB).astype(jnp.uint32) + lane)
    start64 = U.u64_shl(chunk64, k)

    gbits = U.u64_gray(start64)
    rows = [U.to_float(U.u64_bit(gbits, np.uint32(j)), dtype) if j < n
            else jnp.zeros((TB,), dtype) for j in range(n_pad)]
    Gb = jnp.stack(rows, axis=0)
    dd = (((1,), (0,)), ((), ()))
    Xr = xbr + _mm(Ar, Gb, dtype)
    Xi = xbi + _mm(Ai, Gb, dtype)

    sched = _signed_const_schedule(Wu)
    space_m1 = U.u64_from_int(space - 1, like=lane)
    row_iota = jax.lax.broadcasted_iota(jnp.uint32, (n_pad, TB), 0)
    C0 = c0
    mid_idx = next((ix for ix, st in enumerate(sched) if st[2]), None)

    def macro_body(m, carry):
        Xr, Xi, acc_r, acc_i = carry
        macro64 = U.u64_add_u32(start64,
                                m.astype(jnp.uint32) * np.uint32(Wu))
        bitk = U.to_float(U.u64_bit(macro64, np.uint32(kw)), dtype)

        # window-batched states: D = A @ cumsig for both planes
        Dr = _mm(Ar, C0, dtype)
        Di = _mm(Ai, C0, dtype)
        cmr = Ar[:, kw - 1:kw]
        cmi = Ai[:, kw - 1:kw]
        s_mid = sched[mid_idx][1] if mid_idx is not None else 0
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

        # boundary step
        gb64 = U.u64_add_u32(macro64, np.uint32(Wu))
        jb = U.u64_ctz(gb64)
        sb = 2 * U.to_float(U.u64_bit(U.u64_gray(gb64), jb), dtype) - 1
        live = U.u64_leq(gb64, space_m1).astype(dtype)
        onehot = (row_iota == jb[None, :].astype(jnp.uint32)).astype(dtype)
        colr = _mm(Ar, onehot, dtype)
        coli = _mm(Ai, onehot, dtype)
        Xr = Xr + colr * (sb * live)[None, :]
        Xi = Xi + coli * (sb * live)[None, :]
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
    # in-kernel lane reduce: fixed (TB,) lane axis inside one block; kernel
    # values are covered by the 1e-9 kernel-vs-jnp contract, not mesh identity
    re_err = jnp.sum(acc_r[1]) if keep_err else zero  # permlint: disable=PL001  # in-kernel lane reduce, under the 1e-9 kernel contract
    im_err = jnp.sum(acc_i[1]) if keep_err else zero  # permlint: disable=PL001  # in-kernel lane reduce, under the 1e-9 kernel contract
    return jnp.sum(acc_r[0]), re_err, jnp.sum(acc_i[0]), im_err  # permlint: disable=PL001  # in-kernel lane reduce, under the 1e-9 kernel contract


def _ryser_kernel_cx(base_hi_ref, base_lo_ref, Ar_ref, Ai_ref, xbr_ref,
                     xbi_ref, c0_ref, out_ref, **geom):
    """Single-matrix kernel: grid = (num_blocks,); writes the 4 partials."""
    dev = (base_hi_ref[0, 0].astype(jnp.uint32),
           base_lo_ref[0, 0].astype(jnp.uint32))
    _write_partials(out_ref, _ryser_block_cx(
        pl.program_id(0), Ar_ref[...], Ai_ref[...], xbr_ref[...],
        xbi_ref[...], c0_ref[...], dev, **geom))


def _ryser_kernel_cx_batched(Ar_ref, Ai_ref, xbr_ref, xbi_ref, c0_ref,
                             out_ref, **geom):
    """Batch-grid kernel: grid = (B, num_blocks); one launch covers the
    whole stack.  Block b of the plane stacks is selected by the
    BlockSpec; the chunk base is 0 (each matrix owns its full space)."""
    zero = jnp.uint32(0)
    _write_partials(out_ref, _ryser_block_cx(
        pl.program_id(1), Ar_ref[0], Ai_ref[0], xbr_ref[0], xbi_ref[0],
        c0_ref[...], (zero, zero), **geom))


def ryser_pallas_call_complex(Ar_pad, Ai_pad, xbr, xbi,
                              dev_chunk_base, *, n: int, TB: int,
                              C: int, Wu: int, num_blocks: int,
                              precision: str = "dq_acc",
                              interpret: bool | None = None, vma=None):
    """(num_blocks, 4) partials: (re_hi, re_err, im_hi, im_err).

    ``dev_chunk_base`` may be a host int or a traced scalar (the
    distributed shard_map path), exactly like the real kernel.
    """
    interpret = pallas_interpret(Ar_pad, Ai_pad, xbr, xbi,
                                 interpret=interpret)
    n_pad = Ar_pad.shape[0]
    dtype = Ar_pad.dtype
    space = 1 << (n - 1)
    base_hi, base_lo = device_base_u32(dev_chunk_base)
    c0 = jnp.asarray(_cumsig_host(_signed_const_schedule(Wu), n_pad), dtype)
    kernel = functools.partial(
        _ryser_kernel_cx, n=n, n_pad=n_pad, TB=TB, C=C, Wu=Wu, space=space,
        precision=precision, dtype=dtype)
    rep = lambda i: (_Z, _Z)
    out_spec, out_shape = _partials_out(num_blocks, dtype, vma=vma)
    return pl.pallas_call(
        kernel,
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec((1, 1), rep), pl.BlockSpec((1, 1), rep),
            pl.BlockSpec((n_pad, n_pad), rep),
            pl.BlockSpec((n_pad, n_pad), rep),
            pl.BlockSpec((n_pad, 1), rep), pl.BlockSpec((n_pad, 1), rep),
            pl.BlockSpec(c0.shape, rep),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(base_hi, base_lo, Ar_pad, Ai_pad, xbr, xbi, c0)[:, 0, :4]


def ryser_pallas_call_complex_batched(Ar_pads, Ai_pads, xbr_pads, xbi_pads,
                                      *, n: int, TB: int, C: int, Wu: int,
                                      num_blocks: int,
                                      precision: str = "dq_acc",
                                      interpret: bool | None = None):
    """Launch ONE split-plane kernel over a (B, n_pad, n_pad) plane pair:
    grid is (batch, block), so a single ``pallas_call`` covers every
    matrix's full 2^{n-1} step space -- the complex analogue of
    ``ryser_pallas_call_batched``, sharing its geometry inputs
    (``kernel_geometry``) and the window schedule (``_cumsig_host``).
    Returns (B, num_blocks, 4) (re_hi, re_err, im_hi, im_err) partials
    (base g=0 terms NOT included).
    """
    interpret = pallas_interpret(Ar_pads, Ai_pads, xbr_pads, xbi_pads,
                                 interpret=interpret)
    B, n_pad, _ = Ar_pads.shape
    dtype = Ar_pads.dtype
    space = 1 << (n - 1)
    c0 = jnp.asarray(_cumsig_host(_signed_const_schedule(Wu), n_pad), dtype)

    kernel = functools.partial(
        _ryser_kernel_cx_batched, n=n, n_pad=n_pad, TB=TB, C=C, Wu=Wu,
        space=space, precision=precision, dtype=dtype)

    out_spec, out_shape = _partials_out(num_blocks, dtype, batch=B)
    return pl.pallas_call(
        kernel,
        grid=(B, num_blocks),
        in_specs=[
            pl.BlockSpec((1, n_pad, n_pad), lambda b, i: (b, _Z, _Z)),
            pl.BlockSpec((1, n_pad, n_pad), lambda b, i: (b, _Z, _Z)),
            pl.BlockSpec((1, n_pad, 1), lambda b, i: (b, _Z, _Z)),
            pl.BlockSpec((1, n_pad, 1), lambda b, i: (b, _Z, _Z)),
            pl.BlockSpec(c0.shape, lambda b, i: (_Z, _Z)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(Ar_pads, Ai_pads, xbr_pads, xbi_pads, c0)[:, :, 0, :4]
