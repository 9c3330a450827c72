"""Dense Gray-code Ryser permanent engines (paper Alg. 1 / Alg. 3) in JAX.

Three engines, all returning ``perm(A)``:

* ``perm_ryser_seq``     -- faithful sequential Alg. 1 (one ``lax.scan`` over
  the 2^{n-1}-1 Gray steps).  Reference semantics; O(n 2^{n-1}).
* ``perm_ryser_chunked`` -- faithful Alg. 3: the iteration space is split in
  ``T`` chunks; each chunk rebuilds its private row-sum vector from
  ``Gray(start-1)`` (here: one matmul ``A @ G``) and iterates locally.
  Chunks are *power-of-2, window-aligned* (the paper's CEG load
  distribution, Sec. 3.2.1) so the changed bit is chunk-uniform at every
  local step except each window's last -- in vectorized form the column
  update is a broadcast, not a gather.
* the same chunked body is reused per-device by ``core.distributed`` and in
  matmul ("window-batched") form by the Pallas kernel.

Precision modes (paper Table 3): ``dd`` (plain), ``dq_fast`` (Dekker add,
[30]), ``dq_acc`` (accurate add, [31]), ``qq`` (twofloat inner product too),
``kahan`` ([29]).  The outer cross-chunk reduction is always twofloat
("quad for the outer sum", Sec. 5).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.spans import span
from . import gray as G
from . import precision as P

__all__ = [
    "launch_and_wait",
    "nw_base_vector",
    "perm_ryser_seq",
    "perm_ryser_chunked",
    "perm_ryser_batched",
    "batched_values",
    "batched_values_complex",
    "tf_tree_sum",
    "chain_prod",
    "chain_prod_complex",
    "chunk_partial_sums",
    "chunk_partial_sums_complex",
    "chunk_geometry",
    "complex_precision",
    "ryser_flops",
    "signed_column",
]


def nw_base_vector(A):
    """Nijenhuis-Wilf start vector  x[i] = a[i, n-1] - rowsum_i / 2.

    The row sum is a fixed-order sequential chain, not ``jnp.sum``: XLA
    reassociates axis reductions depending on the surrounding program
    shape, and the batch-sharded path needs every contraction in the
    engine to be batch-shape-independent (see ``batched_values``).
    """
    n = A.shape[1]
    rowsum = A[:, 0]
    for j in range(1, n):
        rowsum = rowsum + A[:, j]
    return A[:, -1] - rowsum / 2


def _final_factor(n: int) -> int:
    """(4 * (n mod 2) - 2) == 2 * (-1)^{n-1}."""
    return 4 * (n % 2) - 2


def ryser_flops(n: int) -> float:
    """Model FLOPs of the chunked engine: ~2n per Gray step (n adds for the
    row-sum update + n mults for the product) over 2^{n-1} steps."""
    return 2.0 * n * 2.0 ** (n - 1)


# ---------------------------------------------------------------------------
# Sequential (faithful Alg. 1)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n",))
def _ryser_seq_jit(A, n: int):
    idx_dtype = jnp.int64 if n > 31 else jnp.int32
    x0 = nw_base_vector(A)
    p0 = jnp.prod(x0)  # permlint: disable=PL001  # length-n product, Alg. 1 reference

    def body(carry, g):
        x, acc_hi, acc_lo = carry
        low = g & -g
        j = jax.lax.population_count(low - 1)
        gray_g = g ^ (g >> 1)
        s = jnp.where((gray_g & low) != 0, 1.0, -1.0).astype(A.dtype)
        x = x + s * A[:, j]
        prod = jnp.prod(x)  # permlint: disable=PL001  # length-n product, Alg. 1 reference
        term = jnp.where((g & 1) != 0, -prod, prod)
        acc = P.tf_add_acc(P.TwoFloat(acc_hi, acc_lo), term)
        return (x, acc.hi, acc.lo), None

    gs = jnp.arange(1, 2 ** (n - 1), dtype=idx_dtype)
    (x, hi, lo), _ = jax.lax.scan(body, (x0, p0, jnp.zeros_like(p0)), gs)
    return (hi + lo) * _final_factor(n)


def perm_ryser_seq(A):
    """Faithful Algorithm 1 with twofloat accumulation. n <= ~26 advised."""
    A = jnp.asarray(A)
    n = A.shape[0]
    if n == 1:
        return A[0, 0]
    return _ryser_seq_jit(A, n)


# ---------------------------------------------------------------------------
# Chunked / vectorized (faithful Alg. 3 + CEG chunking)
# ---------------------------------------------------------------------------

# chunk_geometry lives in core.stepspace (pure host math shared with the
# jax-free planner); re-exported here for the engines and their callers.
from .stepspace import chunk_geometry  # noqa: E402


class _CEGSchedules:
    """Host-constant CEG schedules for chunks [offset, offset + T).

    Everything here depends only on (n, T, C, chunk_offset) -- never on the
    matrix -- so the real engine, the split-plane complex engine and the
    sparse engine all share one computation (and, transitively, one
    definition of the iteration order).
    """

    def __init__(self, n: int, T: int, C: int, chunk_offset: int = 0,
                 total_chunks: int | None = None):
        if total_chunks is None:
            total_chunks = T
        k = int(math.log2(C))
        assert C == 1 << k and k >= 1, "chunks must be power-of-2 sized, C >= 2"
        space = 1 << (n - 1)
        assert total_chunks * C == space, (total_chunks, C, space)
        self.k = k
        starts = (np.arange(T, dtype=np.uint64)
                  + np.uint64(chunk_offset)) * np.uint64(C)
        self.starts = starts

        # --- trace-time schedules (the "matrix-specific rebuild" analogue) ---
        sched = G.changed_bit_schedule(k)        # (C-1,) uniform changed bits
        # per-step signs need bits j and j+1 of g = start + w.  For w < C
        # these depend only on w, except bit k of the start enters at w = C/2.
        w_arr = np.arange(1, C, dtype=np.uint64)
        jj = sched.astype(np.uint64)
        bit_j = ((w_arr >> jj) ^ (w_arr >> (jj + np.uint64(1)))) & np.uint64(1)
        mid_mask = (jj + 1 == k)                           # only at w = C/2
        start_bit_k = ((starts >> np.uint64(k)) & np.uint64(1)).astype(np.int32)

        self.sched_j = jnp.asarray(sched)                  # (C-1,)
        self.base_bits = jnp.asarray(bit_j.astype(np.int32))    # (C-1,)
        self.mid_flags = jnp.asarray(mid_mask.astype(np.int32))  # (C-1,)
        self.w_parity = jnp.asarray((w_arr & np.uint64(1)).astype(np.int32))
        self.lane_bitk = jnp.asarray(start_bit_k)          # (T,)

        # tail step (w = C): per-chunk column and sign, host constants.
        g_tail = starts + np.uint64(C)
        tail_j = np.array([G.ctz(int(gt)) for gt in g_tail], dtype=np.int32)
        tail_sign = np.array([G.step_sign(int(gt)) for gt in g_tail],
                             dtype=np.int64)
        tail_live = g_tail <= np.uint64(space - 1)
        self.tail_j = np.where(tail_live, tail_j, 0)
        self.tail_sign = tail_sign
        self.tail_live = tail_live

    @property
    def scan_inputs(self):
        return (self.sched_j, self.base_bits, self.mid_flags, self.w_parity)

    def gray_bits(self, n: int, dtype):
        """(n, T) Gray-code bits of the chunk start steps."""
        return jnp.asarray(G.gray_bits_matrix(self.starts, n), dtype=dtype)

    def tail_columns(self, A):
        """Signed, liveness-masked tail column matrix A[:, tail_j] (n, T)."""
        return A[:, jnp.asarray(self.tail_j)] * jnp.asarray(
            (self.tail_sign * self.tail_live).astype(np.float64)
        ).astype(A.dtype)[None, :]


def rank1_chunk_init(A, x_base, Gbits):
    """Chunk state init (Alg. 3 lines 10-13) as fixed-order rank-1
    accumulation: a plain ``A @ Gbits`` matmul lets XLA pick the
    contraction split per program shape, which breaks the sharded/local
    bit-identity contract (see ``batched_values``)."""
    X0 = x_base[:, None]
    for j in range(A.shape[0]):
        X0 = X0 + A[:, j:j + 1] * Gbits[j:j + 1, :]                   # (n, T)
    return X0


def signed_column(col, sign_bits):
    """(n, T) Gray-step column update: ``+col`` where the lane's sign bit
    is 1, ``-col`` where it is 0.

    An exact select, not a multiply by a float +-1: the values are the
    same bit for bit (signed zeros included), and the step saves one
    multiply per element, an emulated float64 one on a TPU.
    """
    c = col[:, None]
    return jnp.where(sign_bits[None, :] == 1, c, -c)


def chunk_partial_sums(A, T: int, C: int, precision: str = "dq_acc",
                       chunk_offset: int = 0, total_chunks: int | None = None):
    """Per-chunk partial sums for chunks [chunk_offset, chunk_offset + T).

    This is the device-level workhorse reused by ``core.distributed``: each
    device calls it on its own chunk range.  Returns a TwoFloat of shape (T,)
    with ``partial[t] = sum_{w=1..C} (-1)^{g} prod_i x_{t,w}[i]`` -- the base
    (g == 0) term is NOT included (added once by the caller).  Requires
    C == 2^k with k >= 1 and chunk starts aligned to C.
    """
    n = A.shape[0]
    dtype = A.dtype
    S = _CEGSchedules(n, T, C, chunk_offset, total_chunks)
    X0 = rank1_chunk_init(A, nw_base_vector(A), S.gray_bits(n, dtype))
    sched_j, base_bits, mid_flags, w_parity = S.scan_inputs
    lane_bitk = S.lane_bitk
    Atail = S.tail_columns(A)
    tail_live = S.tail_live

    use_qq = precision == "qq"

    def tf_update(Xhi, Xlo, d):
        shi, slo = P.two_sum(Xhi, d)
        return P.fast_two_sum(shi, slo + Xlo)

    def product(Xhi, Xlo):
        if not use_qq:
            # sequential chain, not jnp.prod: fixed association order
            # regardless of the surrounding batch shape
            t = Xhi[0]
            for i in range(1, n):
                t = t * Xhi[i]
            return P.tf_from(t)
        t = P.TwoFloat(Xhi[0], Xlo[0])
        for i in range(1, n):
            t = P.tf_mul_tf(t, P.TwoFloat(Xhi[i], Xlo[i]))
        return t

    def init_acc():
        z = jnp.zeros((T,), dtype=dtype)
        return (z, z)

    def accum(acc, term: P.TwoFloat):
        """Fold a product term into the per-chunk partial accumulator."""
        if precision == "dq_fast":
            t = P.tf_add_fast(P.TwoFloat(*acc), term.hi)
            return (t.hi, t.lo)
        if precision == "dq_acc":
            t = P.tf_add_acc(P.TwoFloat(*acc), term.hi)
            return (t.hi, t.lo)
        if precision == "qq":
            t = P.tf_add_tf(P.TwoFloat(*acc), term)
            return (t.hi, t.lo)
        if precision == "kahan":
            return P.kahan_add(acc, term.hi)
        return (acc[0] + term.hi, acc[1])  # dd

    def scan_body(carry, inputs):
        Xhi, Xlo, acc = carry
        col_j, bit, midf, par = inputs
        sign_bits = bit ^ (midf & lane_bitk)               # (T,) in {0,1}
        d = signed_column(A[:, col_j], sign_bits)          # broadcast column
        if use_qq:
            Xhi, Xlo = tf_update(Xhi, Xlo, d)
        else:
            Xhi = Xhi + d
        prod = product(Xhi, Xlo)
        term = P.TwoFloat(jnp.where(par == 1, -prod.hi, prod.hi),
                          jnp.where(par == 1, -prod.lo, prod.lo))
        acc = accum(acc, term)
        return (Xhi, Xlo, acc), None

    Xlo0 = jnp.zeros_like(X0)
    carry = (X0, Xlo0, init_acc())
    carry, _ = jax.lax.scan(scan_body, carry,
                            (sched_j, base_bits, mid_flags, w_parity))
    Xhi, Xlo, acc = carry

    # tail step w = C (per-chunk column; sign/mask folded into Atail)
    if use_qq:
        Xhi, Xlo = tf_update(Xhi, Xlo, Atail)
    else:
        Xhi = Xhi + Atail
    prod = product(Xhi, Xlo)
    live = jnp.asarray(tail_live)
    neg = (C & 1) == 1  # (-1)^{g = start + C} == (-1)^C, chunk-uniform
    hi = jnp.where(live, -prod.hi if neg else prod.hi, jnp.zeros_like(prod.hi))
    lo = jnp.where(live, -prod.lo if neg else prod.lo, jnp.zeros_like(prod.lo))
    acc = accum(acc, P.TwoFloat(hi, lo))

    if precision == "kahan":
        return P.TwoFloat(acc[0], jnp.zeros_like(acc[0]))
    if precision == "dd":
        return P.TwoFloat(acc[0], jnp.zeros_like(acc[0]))
    return P.TwoFloat(acc[0], acc[1])


@partial(jax.jit, static_argnames=("num_chunks", "precision"))
def _chunked_jit(A, num_chunks: int, precision: str):
    n = A.shape[0]
    T, C, _ = chunk_geometry(n, num_chunks)
    partials = chunk_partial_sums(A, T, C, precision)
    # outer reduction always in twofloat (paper: quad outer sum), with the
    # same fixed-order tree/chain reductions as ``batched_values`` so the
    # scalar and batched engines stay bit-identical
    p_hi, p_lo = jax.lax.optimization_barrier((partials.hi, partials.lo))
    hi, e1 = tf_tree_sum(p_hi, p_lo)
    x_base = nw_base_vector(A)
    p0 = chain_prod(x_base)
    total = P.tf_add_acc(P.TwoFloat(hi, e1), p0)
    return P.tf_value(total) * _final_factor(n)


def perm_ryser_chunked(A, num_chunks: int = 4096, precision: str = "dq_acc"):
    """Faithful Alg. 3 (chunked parallel Ryser) with CEG-aligned chunks.

    Complex matrices run the split-plane engine as a B=1 batch program, so
    the scalar and batched complex paths share one trace (and one set of
    numerics) -- a ragged straggler served scalar is bit-identical to the
    same leaf served inside a bucket.
    """
    if jnp.iscomplexobj(A):
        return _complex_batched(np.asarray(A)[None], num_chunks,
                                precision)[0]
    A = jnp.asarray(A)
    n = A.shape[0]
    if n == 1:
        return A[0, 0]
    if n == 2:
        return A[0, 0] * A[1, 1] + A[0, 1] * A[1, 0]
    return _chunked_jit(A, num_chunks, precision)


# ---------------------------------------------------------------------------
# Batched (vmapped Alg. 3): one device program for a stack of matrices
# ---------------------------------------------------------------------------

def chain_prod(X):
    """Fixed-order product over axis 0 (see ``tf_tree_sum``: ``jnp.prod``'s
    association is an XLA scheduling choice, not a contract)."""
    t = X[0]
    for i in range(1, X.shape[0]):
        t = t * X[i]
    return t


def chain_prod_complex(Xr, Xi):
    """Fixed-order complex product over axis 0 of split (re, im) planes.

    The explicit 4-mult/2-add recurrence -- the same one the Pallas complex
    kernel unrolls -- instead of complex-dtype ``*``: XLA's complex multiply
    lowering is free to fuse/reassociate per program shape, and the
    split-plane engines promise shard-shape-independent values.
    """
    pr, pi = Xr[0], Xi[0]
    for i in range(1, Xr.shape[0]):
        pr, pi = pr * Xr[i] - pi * Xi[i], pr * Xi[i] + pi * Xr[i]
    return pr, pi


def complex_precision(precision: str) -> str:
    """Effective precision mode for the complex engines.

    ``qq``'s twofloat inner product relies on Dekker splitting, which is
    real-only; complex runs it as ``kahan`` (the planner surfaces this as a
    ``qq->kahan`` downgrade tag).  Every split-plane entry point routes its
    precision through here so the jnp / distributed traces agree.
    """
    return "kahan" if precision == "qq" else precision


def tf_tree_sum(hi, lo):
    """Pairwise twofloat tree reduction with a FIXED association order.

    ``jnp.sum``'s reduction split is an XLA scheduling decision that
    depends on the surrounding program shape -- the same (T,) sum inside
    a (4, T) program and a (32, T) program can associate differently and
    diverge at the ulp level, and the batch-sharded path promises values
    bit-identical to the single-device batched engine for ANY shard
    shape.  So the cross-chunk reduction fixes its own order: halve and
    merge (hi, lo) pairs with the compensated ``tf_add_tf`` until one
    element is left (elementwise ops are order-free; the odd tail
    element is peeled per level, so any length works).  Each merge keeps
    its rounding error in the lo limb, which is also more accurate on
    cancellation-heavy inputs than summing hi and lo separately in plain
    f64 (the pre-PR outer reduction).  Returns scalar ``(hi, lo)``.
    """
    L = hi.shape[0]
    while L > 1:
        half = L // 2
        t = P.tf_add_tf(P.TwoFloat(hi[:half], lo[:half]),
                        P.TwoFloat(hi[half:2 * half], lo[half:2 * half]))
        if L == 2 * half:
            hi, lo = t.hi, t.lo
        else:
            hi = jnp.concatenate([t.hi, hi[2 * half:]], axis=0)
            lo = jnp.concatenate([t.lo, lo[2 * half:]], axis=0)
        L = (L + 1) // 2
    return hi[0], lo[0]


def batched_values(As, T: int, C: int, precision: str):
    """Traced (B,) permanents of a same-size stack, chunk geometry fixed.

    The single traced body shared by the jitted single-device program
    (``_batched_jit``) and the per-device body of the mesh-sharded batch
    path (``distributed.batch_permanents_on_mesh``) -- sharing the trace
    (plus ``tf_tree_sum``'s fixed-order cross-chunk reduction) is what makes
    the sharded values bit-identical to the local ones.
    """
    n = As.shape[1]
    parts = jax.vmap(lambda A: chunk_partial_sums(A, T, C, precision))(As)
    # pin the scan -> outer-reduction boundary: without the barrier XLA
    # fuses the reduction epilogue into the scan differently at different
    # batch shapes (fma/reassociation), breaking the bit-identity
    # contract between sharded and local execution.  (Applied outside the
    # vmap -- optimization_barrier has no batching rule on JAX 0.4.x.)
    p_hi, p_lo = jax.lax.optimization_barrier((parts.hi, parts.lo))

    def reduce_one(A, hi_t, lo_t):
        hi, e1 = tf_tree_sum(hi_t, lo_t)
        p0 = chain_prod(nw_base_vector(A))
        total = P.tf_add_acc(P.TwoFloat(hi, e1), p0)
        return P.tf_value(total) * _final_factor(n)

    return jax.vmap(reduce_one)(As, p_hi, p_lo)


@partial(jax.jit, static_argnames=("num_chunks", "precision"))
def _batched_jit(As, num_chunks: int, precision: str):
    n = As.shape[1]
    T, C, _ = chunk_geometry(n, num_chunks)
    return batched_values(As, T, C, precision)


# ---------------------------------------------------------------------------
# Split-plane complex engine: the matrix travels as explicit (re, im) planes
# ---------------------------------------------------------------------------

def chunk_partial_sums_complex(Ar, Ai, T: int, C: int,
                               precision: str = "dq_acc",
                               chunk_offset: int = 0,
                               total_chunks: int | None = None):
    """Split-plane complex Alg.-3 chunk partials; mirrors
    ``chunk_partial_sums`` with the matrix carried as (re, im) float planes.

    TPU VPUs have no complex dtype, so the whole stack shares the kernel's
    representation: the row-sum state is a plane pair (Xr, Xi), column
    updates are two real broadcasts, the product is the explicit complex
    chain recurrence (``chain_prod_complex``), and the partial sums are
    accumulated *per component* with the same compensated strategies as the
    real engine.  Returns ``(re, im, base)`` where ``re``/``im`` are
    TwoFloats of shape (T,) NOT including the base (g == 0) term, and
    ``base`` is the ``(p0_re, p0_im)`` scalar pair of that base term, read
    off lane 0's initial state (valid when ``chunk_offset == 0``; callers
    at nonzero offsets ignore it).  The base product deliberately shares
    the lane products' (n, T) vector pattern: a standalone (B,)-shaped
    complex chain compiles batch-shape-dependently (ulp drift between B=1
    and B=2 programs, observed on CPU), this pattern does not.  ``qq``
    runs as ``kahan`` (``complex_precision``).
    """
    precision = complex_precision(precision)
    n = Ar.shape[0]
    dtype = Ar.dtype
    S = _CEGSchedules(n, T, C, chunk_offset, total_chunks)
    Gbits = S.gray_bits(n, dtype)
    xr = nw_base_vector(Ar)
    xi = nw_base_vector(Ai)
    Xr = rank1_chunk_init(Ar, xr, Gbits)
    Xi = rank1_chunk_init(Ai, xi, Gbits)
    # lane 0 of chunk 0 starts at g = 0 (all Gray bits zero), so its
    # initial state IS the NW base vector and its product the base term
    b0r, b0i = chain_prod_complex(Xr, Xi)
    base = (b0r[0], b0i[0])
    lane_bitk = S.lane_bitk
    Atail_r = S.tail_columns(Ar)
    Atail_i = S.tail_columns(Ai)

    def accum(acc, term):
        """Per-component compensated accumulate (one real plane)."""
        if precision == "dq_fast":
            t = P.tf_add_fast(P.TwoFloat(*acc), term)
            return (t.hi, t.lo)
        if precision == "dq_acc":
            t = P.tf_add_acc(P.TwoFloat(*acc), term)
            return (t.hi, t.lo)
        if precision == "kahan":
            return P.kahan_add(acc, term)
        return (acc[0] + term, acc[1])  # dd

    def fold(acc_r, acc_i, pr, pi, negate):
        tr = jnp.where(negate, -pr, pr)
        ti = jnp.where(negate, -pi, pi)
        return accum(acc_r, tr), accum(acc_i, ti)

    def scan_body(carry, inputs):
        Xr, Xi, acc_r, acc_i = carry
        col_j, bit, midf, par = inputs
        sign_bits = bit ^ (midf & lane_bitk)               # (T,) in {0,1}
        Xr = Xr + signed_column(Ar[:, col_j], sign_bits)   # broadcast column
        Xi = Xi + signed_column(Ai[:, col_j], sign_bits)
        pr, pi = chain_prod_complex(Xr, Xi)
        acc_r, acc_i = fold(acc_r, acc_i, pr, pi, par == 1)
        return (Xr, Xi, acc_r, acc_i), None

    z = jnp.zeros((T,), dtype=dtype)
    carry = (Xr, Xi, (z, z), (z, z))
    carry, _ = jax.lax.scan(scan_body, carry, S.scan_inputs)
    Xr, Xi, acc_r, acc_i = carry

    # tail step w = C (per-chunk column; sign/mask folded into Atail)
    Xr = Xr + Atail_r
    Xi = Xi + Atail_i
    pr, pi = chain_prod_complex(Xr, Xi)
    live = jnp.asarray(S.tail_live)
    neg = (C & 1) == 1  # (-1)^{g = start + C} == (-1)^C, chunk-uniform
    zero = jnp.zeros_like(pr)
    pr = jnp.where(live, -pr if neg else pr, zero)
    pi = jnp.where(live, -pi if neg else pi, zero)
    acc_r = accum(acc_r, pr)
    acc_i = accum(acc_i, pi)

    if precision in ("kahan", "dd"):
        return (P.TwoFloat(acc_r[0], jnp.zeros_like(acc_r[0])),
                P.TwoFloat(acc_i[0], jnp.zeros_like(acc_i[0])), base)
    return (P.TwoFloat(acc_r[0], acc_r[1]),
            P.TwoFloat(acc_i[0], acc_i[1]), base)


def batched_values_complex(Ars, Ais, T: int, C: int, precision: str):
    """Traced (re, im) value pair for a (B, n, n) split-plane complex stack.

    The complex analogue of ``batched_values``: the single traced body
    shared by the jitted single-device program (``_batched_complex_jit``)
    and the per-device body of the mesh-sharded complex batch path
    (``distributed.batch_permanents_on_mesh``) -- one trace plus
    ``tf_tree_sum``'s fixed-order per-component reductions is what makes
    sharded complex values bit-identical to local ones, mirroring the real
    path's guarantee.  Returns ``(values_re, values_im)`` of shape (B,).
    """
    precision = complex_precision(precision)
    n = Ars.shape[1]

    def one(planes):
        ar, ai = planes
        parts_r, parts_i, (p0r, p0i) = chunk_partial_sums_complex(
            ar, ai, T, C, precision)
        # pin the scan -> outer-reduction boundary (see ``batched_values``;
        # legal here -- the body is not under vmap)
        rh, rl, ih, il, p0r, p0i = jax.lax.optimization_barrier(
            (parts_r.hi, parts_r.lo, parts_i.hi, parts_i.lo, p0r, p0i))
        hr, er = tf_tree_sum(rh, rl)
        hi_, ei = tf_tree_sum(ih, il)
        tot_r = P.tf_add_acc(P.TwoFloat(hr, er), p0r)
        tot_i = P.tf_add_acc(P.TwoFloat(hi_, ei), p0i)
        f = _final_factor(n)
        return P.tf_value(tot_r) * f, P.tf_value(tot_i) * f

    # lax.map, NOT vmap: vmap fuses across the batch axis and XLA's
    # fusion/contraction choices for the complex product chains vary with
    # the batch extent (ulp drift between B=1/B=2/B=5 programs, observed
    # on CPU) -- a scan-over-batch compiles ONE body program whatever B
    # is, so per-element values cannot depend on the batch or shard shape.
    # Per-matrix SIMD parallelism (the T chunk lanes) is unaffected; what
    # batching amortizes here is dispatch + compilation, as in PR 1.
    return jax.lax.map(one, (Ars, Ais))


@partial(jax.jit, static_argnames=("num_chunks", "precision"))
def _batched_complex_jit(Ars, Ais, num_chunks: int, precision: str):
    n = Ars.shape[1]
    T, C, _ = chunk_geometry(n, num_chunks)
    return batched_values_complex(Ars, Ais, T, C, precision)


def perm_ryser_batched(As, num_chunks: int = 4096, precision: str = "dq_acc"):
    """Permanents of a stack of same-size matrices in ONE device program.

    ``As`` is (B, n, n); returns (B,).  The chunked Alg.-3 body (all its
    host-side CEG schedules are batch-invariant: they depend only on
    (n, T, C)) is vmapped over the leading batch axis under a single jit,
    so a whole stack costs one dispatch and one compilation per (B, n)
    instead of B host round-trips -- the substrate for ``permanent_batch``
    and the batched serving loop.  Matches ``perm_ryser_chunked`` per
    element (identical chunk geometry and twofloat outer reduction).
    """
    if jnp.iscomplexobj(As):
        return _complex_batched(np.asarray(As), num_chunks, precision)
    As = jnp.asarray(As)
    if As.ndim != 3 or As.shape[1] != As.shape[2]:
        raise ValueError(f"(B, n, n) stack required, got {As.shape}")
    n = As.shape[1]
    if n == 1:
        return As[:, 0, 0]
    if n == 2:
        return (As[:, 0, 0] * As[:, 1, 1] + As[:, 0, 1] * As[:, 1, 0])
    return _batched_jit(As, num_chunks, precision)


def _complex_batched(As: np.ndarray, num_chunks: int, precision: str):
    """A complex (B, n, n) host stack through the split-plane engine.

    The planes are split and joined on the host: no complex value ever
    reaches the device, because a TPU has no c128 arithmetic (its x64
    rewriter aborts the process on c128 ops rather than raising).
    """
    if As.ndim != 3 or As.shape[1] != As.shape[2]:
        raise ValueError(f"(B, n, n) stack required, got {As.shape}")
    n = As.shape[1]
    if n == 1:
        return As[:, 0, 0]
    if n == 2:
        return As[:, 0, 0] * As[:, 1, 1] + As[:, 0, 1] * As[:, 1, 0]
    vr, vi = launch_and_wait(_batched_complex_jit,
                             np.ascontiguousarray(As.real),
                             np.ascontiguousarray(As.imag),
                             num_chunks, precision)
    return vr + 1j * vi


def launch_and_wait(program, *args, **kwargs):
    """Run one device program and bring its result to the host.

    ``program(*args, **kwargs)`` is dispatched under the span
    ``engine.launch`` (argument transfer, a trace on a cache miss, the
    enqueue); the host's block on its outputs and their copy back run
    under ``engine.wait``.  Every batch path calls it at its one device
    sync, so each device program has exactly one ``engine.wait``.
    Returns NumPy arrays, a tuple of them for a tuple result.
    """
    with span("engine.launch"):
        out = program(*args, **kwargs)
    with span("engine.wait"):
        if isinstance(out, tuple):
            return tuple(np.asarray(o) for o in out)
        return np.asarray(out)
