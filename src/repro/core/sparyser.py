"""Sparse-matrix permanent: CRS/CCS storage and SpaRyser (paper Alg. 2).

The matrix is stored in the paper's dual CRS + CCS formats (Fig. 1).  The
Gray-code loop updates the row-sum vector ``x`` using only the nonzeros of
the changed column -- O(nnz_j) instead of O(n) per step.

TPU adaptation (DESIGN.md Sec. 2): lockstep lanes cannot skip work, so the
per-column nonzero lists are *padded to the max column degree* and the
padded entries point at a dummy row (index n) with value 0 -- the scatter
stays shape-static and vectorizes, while the arithmetic still touches only
``maxdeg`` rows.  The sparsity pattern is a trace-time constant: the jitted
engine is specialized per pattern, the analogue of the paper's per-matrix
kernel generation ([22], Sec. 3.3).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import precision as P
from .ryser import (chain_prod, chain_prod_complex, chunk_geometry,
                    complex_precision, launch_and_wait, nw_base_vector,
                    rank1_chunk_init, tf_tree_sum, _CEGSchedules,
                    _final_factor)

__all__ = ["SparseMatrix", "perm_sparyser_chunked", "perm_sparyser_batched",
           "sparse_batched_values", "sparse_batched_values_complex",
           "sparse_chunked_value", "pack_padded_ccs",
           "sparse_chunk_partial_sums"]


@dataclass(frozen=True)
class SparseMatrix:
    """CRS + CCS dual storage (paper Fig. 1). Host-side numpy arrays."""
    n: int
    rptrs: np.ndarray   # (n+1,)
    cids: np.ndarray    # (nnz,) column ids, row-major order
    rvals: np.ndarray   # (nnz,)
    cptrs: np.ndarray   # (n+1,)
    rids: np.ndarray    # (nnz,) row ids, column-major order
    cvals: np.ndarray   # (nnz,)

    @property
    def nnz(self) -> int:
        return int(self.cids.shape[0])

    @property
    def density(self) -> float:
        return self.nnz / float(self.n * self.n)

    @staticmethod
    def from_dense(A: np.ndarray, tol: float = 0.0) -> "SparseMatrix":
        A = np.asarray(A)
        n = A.shape[0]
        mask = np.abs(A) > tol
        rptrs = np.zeros(n + 1, dtype=np.int32)
        cids, rvals = [], []
        for i in range(n):
            js = np.nonzero(mask[i])[0]
            cids.append(js)
            rvals.append(A[i, js])
            rptrs[i + 1] = rptrs[i] + len(js)
        cptrs = np.zeros(n + 1, dtype=np.int32)
        rids, cvals = [], []
        for j in range(n):
            is_ = np.nonzero(mask[:, j])[0]
            rids.append(is_)
            cvals.append(A[is_, j])
            cptrs[j + 1] = cptrs[j] + len(is_)
        cat = lambda xs, dt: (np.concatenate(xs).astype(dt) if xs else
                              np.zeros(0, dtype=dt))
        return SparseMatrix(
            n=n,
            rptrs=rptrs, cids=cat(cids, np.int32), rvals=cat(rvals, A.dtype),
            cptrs=cptrs, rids=cat(rids, np.int32), cvals=cat(cvals, A.dtype))

    def to_dense(self) -> np.ndarray:
        A = np.zeros((self.n, self.n), dtype=self.rvals.dtype)
        for i in range(self.n):
            sl = slice(self.rptrs[i], self.rptrs[i + 1])
            A[i, self.cids[sl]] = self.rvals[sl]
        return A

    def padded_columns(self):
        """(rows, vals) of shape (n, maxdeg): column-j nonzeros, padded with
        (row=n, val=0) -- the shape-static scatter form."""
        n = self.n
        maxdeg = max(1, int(np.max(self.cptrs[1:] - self.cptrs[:-1])))
        rows = np.full((n, maxdeg), n, dtype=np.int32)
        vals = np.zeros((n, maxdeg), dtype=self.cvals.dtype)
        for j in range(n):
            sl = slice(self.cptrs[j], self.cptrs[j + 1])
            deg = sl.stop - sl.start
            rows[j, :deg] = self.rids[sl]
            vals[j, :deg] = self.cvals[sl]
        return rows, vals

    def min_degree(self):
        """(which, index, deg): minimum nonzero count over rows and columns.

        which is 'row' or 'col'.  Used by the Alg. 4 dispatcher.
        """
        rdeg = self.rptrs[1:] - self.rptrs[:-1]
        cdeg = self.cptrs[1:] - self.cptrs[:-1]
        ri = int(np.argmin(rdeg))
        ci = int(np.argmin(cdeg))
        if rdeg[ri] <= cdeg[ci]:
            return "row", ri, int(rdeg[ri])
        return "col", ci, int(cdeg[ci])


def sparse_chunk_partial_sums(sp: SparseMatrix, T: int, C: int,
                              precision: str = "dq_acc",
                              chunk_offset: int = 0,
                              total_chunks: int | None = None) -> P.TwoFloat:
    """SpaRyser (Alg. 2) partial sums for a chunk range; mirrors
    ``ryser.chunk_partial_sums`` but updates x through the padded CCS."""
    A = jnp.asarray(sp.to_dense())       # used only for init matmul (n x n)
    rows_pad, vals_pad = sp.padded_columns()
    return _sparse_partials_traced(A, jnp.asarray(rows_pad),
                                   jnp.asarray(vals_pad), T, C, precision,
                                   chunk_offset, total_chunks)


def _sparse_partials_traced(A, rows_pad, vals_pad, T: int, C: int,
                            precision: str, chunk_offset: int = 0,
                            total_chunks: int | None = None) -> P.TwoFloat:
    """Traced-core SpaRyser partials: the matrix enters only through the
    (traced) dense ``A`` (init matmul), ``rows_pad`` and ``vals_pad``
    (n, maxdeg) padded CCS arrays -- so the same program vmaps over a
    stack of same-shape sparse matrices (``perm_sparyser_batched``)."""
    if total_chunks is None:
        total_chunks = T
    n = A.shape[0]
    dtype = A.dtype
    S = _CEGSchedules(n, T, C, chunk_offset, total_chunks)
    # fixed-order rank-1 init, not ``A @ Gbits`` (see ryser.chain_prod:
    # XLA's contraction split is batch-shape-dependent), extended with
    # dummy row n for padded scatters
    X0 = rank1_chunk_init(A, nw_base_vector(A), S.gray_bits(n, dtype))
    X0 = jnp.concatenate([X0, jnp.zeros((1, T), dtype=dtype)], axis=0)

    sched_j, base_bits, mid_flags, w_parity = S.scan_inputs
    lane_bitk = S.lane_bitk
    tail_j, tail_sign, tail_live = S.tail_j, S.tail_sign, S.tail_live

    def accum(acc, term):
        if precision == "dq_fast":
            t = P.tf_add_fast(P.TwoFloat(*acc), term)
            return (t.hi, t.lo)
        if precision in ("dq_acc", "qq"):
            t = P.tf_add_acc(P.TwoFloat(*acc), term)
            return (t.hi, t.lo)
        if precision == "kahan":
            return P.kahan_add(acc, term)
        return (acc[0] + term, acc[1])

    def scan_body(carry, inputs):
        X, acc = carry
        col_j, bit, midf, par = inputs
        sign_bits = bit ^ (midf & lane_bitk)
        s = (2 * sign_bits - 1).astype(dtype)              # (T,)
        r = rows_pad[col_j]                                # (maxdeg,)
        v = vals_pad[col_j]                                # (maxdeg,)
        X = X.at[r, :].add(v[:, None] * s[None, :])
        prod = chain_prod(X[:n])
        term = jnp.where(par == 1, -prod, prod)
        acc = accum(acc, term)
        return (X, acc), None

    z = jnp.zeros((T,), dtype=dtype)
    (X, acc), _ = jax.lax.scan(scan_body, (X0, (z, z)),
                               (sched_j, base_bits, mid_flags, w_parity))

    # tail step
    r = rows_pad[jnp.asarray(tail_j)]                      # (T, maxdeg)
    v = vals_pad[jnp.asarray(tail_j)]                      # (T, maxdeg)
    sgn = jnp.asarray((tail_sign * tail_live).astype(np.float64)).astype(dtype)
    upd = (v * sgn[:, None]).T                             # (maxdeg, T)
    X = X.at[r.T, jnp.arange(T)[None, :]].add(upd)
    prod = chain_prod(X[:n])
    live = jnp.asarray(tail_live)
    neg = (C & 1) == 1
    term = jnp.where(live, -prod if neg else prod, jnp.zeros_like(prod))
    acc = accum(acc, term)

    if precision in ("kahan", "dd"):
        return P.TwoFloat(acc[0], jnp.zeros_like(acc[0]))
    return P.TwoFloat(acc[0], acc[1])


def _sparse_partials_traced_complex(Ar, Ai, rows_pad, vals_r, vals_i,
                                    T: int, C: int, precision: str,
                                    chunk_offset: int = 0,
                                    total_chunks: int | None = None):
    """Split-plane complex SpaRyser partials; mirrors
    ``_sparse_partials_traced`` with the matrix carried as (re, im) float
    planes (see ``ryser.chunk_partial_sums_complex`` for the
    representation contract).  Returns ``(re, im, base)`` -- (T,)
    TwoFloats per component plus the scalar base-term pair read off lane
    0's initial state (valid at ``chunk_offset == 0``)."""
    precision = complex_precision(precision)
    if total_chunks is None:
        total_chunks = T
    n = Ar.shape[0]
    dtype = Ar.dtype
    S = _CEGSchedules(n, T, C, chunk_offset, total_chunks)
    Gbits = S.gray_bits(n, dtype)
    Xr = rank1_chunk_init(Ar, nw_base_vector(Ar), Gbits)
    Xi = rank1_chunk_init(Ai, nw_base_vector(Ai), Gbits)
    # base product from the lane products' (n, T) vector pattern (a
    # standalone (B,)-shaped chain compiles batch-shape-dependently)
    b0r, b0i = chain_prod_complex(Xr, Xi)
    base = (b0r[0], b0i[0])
    zrow = jnp.zeros((1, T), dtype=dtype)
    Xr = jnp.concatenate([Xr, zrow], axis=0)     # dummy row n for scatters
    Xi = jnp.concatenate([Xi, zrow], axis=0)

    lane_bitk = S.lane_bitk
    tail_j, tail_sign, tail_live = S.tail_j, S.tail_sign, S.tail_live

    def accum(acc, term):
        if precision == "dq_fast":
            t = P.tf_add_fast(P.TwoFloat(*acc), term)
            return (t.hi, t.lo)
        if precision == "dq_acc":
            t = P.tf_add_acc(P.TwoFloat(*acc), term)
            return (t.hi, t.lo)
        if precision == "kahan":
            return P.kahan_add(acc, term)
        return (acc[0] + term, acc[1])  # dd

    def scan_body(carry, inputs):
        Xr, Xi, acc_r, acc_i = carry
        col_j, bit, midf, par = inputs
        sign_bits = bit ^ (midf & lane_bitk)
        s = (2 * sign_bits - 1).astype(dtype)              # (T,)
        r = rows_pad[col_j]                                # (maxdeg,)
        Xr = Xr.at[r, :].add(vals_r[col_j][:, None] * s[None, :])
        Xi = Xi.at[r, :].add(vals_i[col_j][:, None] * s[None, :])
        pr, pi = chain_prod_complex(Xr[:n], Xi[:n])
        acc_r = accum(acc_r, jnp.where(par == 1, -pr, pr))
        acc_i = accum(acc_i, jnp.where(par == 1, -pi, pi))
        return (Xr, Xi, acc_r, acc_i), None

    z = jnp.zeros((T,), dtype=dtype)
    (Xr, Xi, acc_r, acc_i), _ = jax.lax.scan(
        scan_body, (Xr, Xi, (z, z), (z, z)), S.scan_inputs)

    # tail step
    r = rows_pad[jnp.asarray(tail_j)]                      # (T, maxdeg)
    sgn = jnp.asarray((tail_sign * tail_live).astype(np.float64)).astype(dtype)
    cols = jnp.arange(T)[None, :]
    Xr = Xr.at[r.T, cols].add((vals_r[jnp.asarray(tail_j)] * sgn[:, None]).T)
    Xi = Xi.at[r.T, cols].add((vals_i[jnp.asarray(tail_j)] * sgn[:, None]).T)
    pr, pi = chain_prod_complex(Xr[:n], Xi[:n])
    live = jnp.asarray(tail_live)
    neg = (C & 1) == 1
    zero = jnp.zeros_like(pr)
    acc_r = accum(acc_r, jnp.where(live, -pr if neg else pr, zero))
    acc_i = accum(acc_i, jnp.where(live, -pi if neg else pi, zero))

    if precision in ("kahan", "dd"):
        return (P.TwoFloat(acc_r[0], jnp.zeros_like(acc_r[0])),
                P.TwoFloat(acc_i[0], jnp.zeros_like(acc_i[0])), base)
    return (P.TwoFloat(acc_r[0], acc_r[1]),
            P.TwoFloat(acc_i[0], acc_i[1]), base)


def _sparse_key(sp: SparseMatrix):
    return (sp.n, sp.cids.tobytes(), sp.rptrs.tobytes())


def sparse_chunked_value(A, rows_pad, vals_pad, T: int, C: int,
                         precision: str):
    """Traced scalar SpaRyser permanent from (dense, padded-CCS) arrays.

    The scalar composition behind ``perm_sparyser_chunked`` as one
    traceable function of traced arrays -- the same fixed-order
    reductions as ``sparse_batched_values``'s per-element epilogue
    (bit-identity between a scalar straggler and a bucket member), and
    the entry permprove's IR verifier traces for the sparse jnp scalar
    route.
    """
    n = A.shape[0]
    partials = _sparse_partials_traced(A, rows_pad, vals_pad, T, C,
                                       precision)
    p_hi, p_lo = jax.lax.optimization_barrier((partials.hi, partials.lo))
    hi, e1 = tf_tree_sum(p_hi, p_lo)
    p0 = chain_prod(nw_base_vector(A))
    total = P.tf_add_acc(P.TwoFloat(hi, e1), p0)
    return P.tf_value(total) * _final_factor(n)


def perm_sparyser_chunked(sp: SparseMatrix, num_chunks: int = 4096,
                          precision: str = "dq_acc"):
    """Permanent of a sparse matrix via chunked SpaRyser.

    Complex matrices run the split-plane engine as a B=1 batch program
    (``perm_sparyser_batched``), so scalar stragglers are bit-identical to
    the same leaf served inside a bucket.
    """
    n = sp.n
    A = np.asarray(sp.to_dense())
    if n == 1:
        return A.item()
    if n == 2:
        return (A[0, 0] * A[1, 1] + A[0, 1] * A[1, 0]).item()
    if np.iscomplexobj(sp.cvals):
        return perm_sparyser_batched([sp], num_chunks=num_chunks,
                                     precision=precision)[0].item()
    A = jnp.asarray(A)
    T, C, _ = chunk_geometry(n, num_chunks)
    rows_pad, vals_pad = sp.padded_columns()
    val = sparse_chunked_value(A, jnp.asarray(rows_pad),
                               jnp.asarray(vals_pad), T, C, precision)
    return np.asarray(val).item()


def sparse_batched_values(A_stack, rows_stack, vals_stack, T: int, C: int,
                          precision: str):
    """Traced (B,) sparse permanents of a packed same-size stack.

    Shared by the jitted single-device program (``_sparse_batched_jit``)
    and the per-device body of the mesh-sharded sparse batch path
    (``distributed.sparse_batch_permanents_on_mesh``) -- one trace (and
    ``ryser.tf_tree_sum``'s fixed-order cross-chunk reduction), so sharded
    and local values are bit-identical for any shard shape.
    """
    n = A_stack.shape[1]
    parts = jax.vmap(
        lambda A, r, v: _sparse_partials_traced(A, r, v, T, C, precision)
    )(A_stack, rows_stack, vals_stack)
    # see ryser.batched_values: fusion across this boundary is
    # batch-shape-dependent and would break shard/local bit-identity
    p_hi, p_lo = jax.lax.optimization_barrier((parts.hi, parts.lo))

    def reduce_one(A, hi_t, lo_t):
        hi, e1 = tf_tree_sum(hi_t, lo_t)
        p0 = chain_prod(nw_base_vector(A))
        total = P.tf_add_acc(P.TwoFloat(hi, e1), p0)
        return P.tf_value(total) * _final_factor(n)

    return jax.vmap(reduce_one)(A_stack, p_hi, p_lo)


@partial(jax.jit, static_argnames=("T", "C", "precision"))
def _sparse_batched_jit(A_stack, rows_stack, vals_stack, T: int, C: int,
                        precision: str):
    return sparse_batched_values(A_stack, rows_stack, vals_stack, T, C,
                                 precision)


def sparse_batched_values_complex(Ar_stack, Ai_stack, rows_stack,
                                  vals_r_stack, vals_i_stack,
                                  T: int, C: int, precision: str):
    """Traced (re, im) pair for a packed split-plane complex sparse stack.

    The complex analogue of ``sparse_batched_values``: one body shared by
    the jitted single-device program and the per-device body of
    ``distributed.sparse_batch_permanents_on_mesh``.  Batched with
    ``lax.map`` rather than vmap for the same reason as
    ``ryser.batched_values_complex``: one body program regardless of the
    batch/shard extent makes per-element values shape-independent by
    construction.
    """
    precision = complex_precision(precision)
    n = Ar_stack.shape[1]

    def one(packed):
        ar, ai, rows, vr, vi = packed
        parts_r, parts_i, (p0r, p0i) = _sparse_partials_traced_complex(
            ar, ai, rows, vr, vi, T, C, precision)
        rh, rl, ih, il, p0r, p0i = jax.lax.optimization_barrier(
            (parts_r.hi, parts_r.lo, parts_i.hi, parts_i.lo, p0r, p0i))
        hr, er = tf_tree_sum(rh, rl)
        hi_, ei = tf_tree_sum(ih, il)
        tot_r = P.tf_add_acc(P.TwoFloat(hr, er), p0r)
        tot_i = P.tf_add_acc(P.TwoFloat(hi_, ei), p0i)
        f = _final_factor(n)
        return P.tf_value(tot_r) * f, P.tf_value(tot_i) * f

    return jax.lax.map(
        one, (Ar_stack, Ai_stack, rows_stack, vals_r_stack, vals_i_stack))


@partial(jax.jit, static_argnames=("T", "C", "precision"))
def _sparse_batched_complex_jit(Ar_stack, Ai_stack, rows_stack,
                                vals_r_stack, vals_i_stack,
                                T: int, C: int, precision: str):
    return sparse_batched_values_complex(
        Ar_stack, Ai_stack, rows_stack, vals_r_stack, vals_i_stack,
        T, C, precision)


def pack_padded_ccs(sps: list[SparseMatrix]):
    """Pack a same-size bucket into batch-stacked dense + padded-CCS arrays.

    Returns host-side ``(A_stack, rows_stack, vals_stack)`` with shapes
    (B, n, n), (B, n, maxdeg), (B, n, maxdeg); the per-matrix columns are
    padded to the bucket-wide max column degree with (row=n, val=0)
    entries, which scatter into the dummy row and are arithmetically
    inert -- per-element numerics do not depend on the bucket's maxdeg.
    """
    assert sps, "empty bucket"
    n = sps[0].n
    assert all(sp.n == n for sp in sps), "bucket must be same-size"
    padded = [sp.padded_columns() for sp in sps]
    maxdeg = max(r.shape[1] for r, _ in padded)
    B = len(sps)
    dtype = np.result_type(*(v.dtype for _, v in padded))
    rows_stack = np.full((B, n, maxdeg), n, dtype=np.int32)
    vals_stack = np.zeros((B, n, maxdeg), dtype=dtype)
    for b, (r, v) in enumerate(padded):
        rows_stack[b, :, :r.shape[1]] = r
        vals_stack[b, :, :v.shape[1]] = v
    A_stack = np.stack([sp.to_dense().astype(dtype) for sp in sps])
    return A_stack, rows_stack, vals_stack


def perm_sparyser_batched(sps: list[SparseMatrix], num_chunks: int = 4096,
                          precision: str = "dq_acc") -> np.ndarray:
    """Permanents of a bucket of same-size sparse matrices, one dispatch.

    All matrices must share ``n``; their padded CCS columns are padded
    further to the bucket-wide max column degree (padding points at the
    dummy row, so it is arithmetically inert) and the SpaRyser body is
    vmapped over the stack.  The jitted program is specialized per
    (n, maxdeg, T, C) -- the batched analogue of the per-pattern kernel
    specialization, amortized over the whole bucket.
    """
    assert sps, "empty bucket"
    n = sps[0].n
    assert all(sp.n == n for sp in sps), "bucket must be same-size"
    if n <= 2:
        # pass the caller's precision/num_chunks through to the scalar
        # path -- dropping them silently would serve tiny buckets at the
        # default config whatever the plan asked for
        return np.array([perm_sparyser_chunked(sp, num_chunks=num_chunks,
                                               precision=precision)
                         for sp in sps])
    T, C, _ = chunk_geometry(n, num_chunks)
    A_stack, rows_stack, vals_stack = pack_padded_ccs(sps)
    if np.iscomplexobj(vals_stack):
        vr, vi = launch_and_wait(
            _sparse_batched_complex_jit,
            jnp.asarray(np.ascontiguousarray(A_stack.real)),
            jnp.asarray(np.ascontiguousarray(A_stack.imag)),
            jnp.asarray(rows_stack),
            jnp.asarray(np.ascontiguousarray(vals_stack.real)),
            jnp.asarray(np.ascontiguousarray(vals_stack.imag)),
            T, C, precision)
        return vr + 1j * vi
    return launch_and_wait(_sparse_batched_jit, jnp.asarray(A_stack),
                           jnp.asarray(rows_stack), jnp.asarray(vals_stack),
                           T, C, precision)
