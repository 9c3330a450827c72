"""Public jitted wrappers around the Pallas Ryser kernels.

``permanent_pallas(A)`` computes perm(A) with the TPU kernel (interpret mode
on CPU); ``permanent_pallas_batched(As)`` covers a whole same-size stack
with one (batch, block)-grid launch.  Both route real AND complex input
through one dispatch helper (``_pallas_values``): geometry, padding, base
vectors and the twofloat cross-block epilogue are computed once, and only
the kernel entry differs -- real matrices run ``ryser_pallas``, complex
matrices run the split re/im plane kernels in ``ryser_complex`` (same
geometry, same window schedule).  The sparse route has the same shape:
``permanent_pallas_sparse(sp)`` / ``permanent_pallas_sparse_batched(sps)``
drive the padded-CCS SpaRyser kernels (``ryser_sparse``) through the
sparse arm of the helper (``_pallas_sparse_values``), sharing
``kernel_geometry`` and ``kernel_reduce`` with the dense arm.
``block_partials_pallas`` exposes the raw per-block partial sums for the
distributed runtime (each device runs the kernel over its own chunk
range; the cross-device reduction is a psum, exactly like the jnp
engine).

Every launch asks :func:`pallas_interpret` whether to run the Pallas
interpreter: the CPU interprets, every other platform compiles with
Mosaic, and a compiled launch refuses 64-bit operands instead of falling
back to the interpreter or to the jnp engines.

Precision passes through untouched on every route: the kernels implement
``dd``/``dq_fast``/``dq_acc``/``kahan`` accumulation and run ``qq`` (no
in-kernel twofloat product) as ``dd`` -- identically for scalar and
batched, real and complex, so bucket members and scalar stragglers share
semantics.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ..core import precision as P
from ..core.ryser import nw_base_vector, _final_factor
from ..core.stepspace import DEFAULT_GEOMETRY, Geometry

__all__ = ["Geometry", "DEFAULT_GEOMETRY", "pallas_interpret",
           "permanent_pallas", "permanent_pallas_batched",
           "permanent_pallas_sparse", "permanent_pallas_sparse_batched",
           "sparse_batched_values_pallas",
           "block_partials_pallas", "kernel_reduce", "pad_matrix",
           "pad_base_vector", "split_matrix_planes", "split_base_planes"]

_SUBLANE = 8  # f32 sublane quantum on TPU


def pallas_interpret(*operands, interpret: bool | None = None) -> bool:
    """Whether a Pallas launch over ``operands`` runs in interpret mode.

    The platform decides: the Pallas interpreter on CPU, Mosaic on every
    other platform.  Mosaic lowers no 64-bit float, so a compiled launch
    with a float64/complex128 operand raises ``TypeError`` -- it neither
    interprets nor drops to the jnp engines; running the kernels on a
    chip needs f32-base numerics (ROADMAP.md, Reach: "f32-base numerics
    end to end").  ``interpret`` overrides the platform where a caller
    must choose: an explicit ``True`` (CPU tuning runs), or ``False`` to
    compile for a described chip from a CPU host.
    """
    platform = jax.default_backend()
    if interpret is None:
        interpret = platform == "cpu"
    elif interpret and platform != "cpu":
        raise ValueError(f"Pallas interpret mode is for the CPU only; this "
                         f"process runs on {platform}")
    wide = sorted({str(x.dtype) for x in operands
                   if jnp.issubdtype(x.dtype, jnp.inexact)
                   and jnp.finfo(x.dtype).bits == 64})
    if not interpret and wide:
        raise TypeError(
            f"Pallas kernels compile with Mosaic on {platform}, which has "
            f"no 64-bit float types (got {', '.join(wide)}); use "
            f"backend='jnp', or see ROADMAP.md, Reach: 'f32-base numerics "
            f"end to end'")
    return interpret


def pad_matrix(A, n_pad: int | None = None):
    """Pad A to (n_pad, n_pad) with zeros; padded x entries must be 1 so
    products are unaffected -- handled by pad_base_vector."""
    A = jnp.asarray(A)
    n = A.shape[0]
    if n_pad is None:
        n_pad = max(_SUBLANE, int(math.ceil(n / _SUBLANE)) * _SUBLANE)
    out = jnp.zeros((n_pad, n_pad), dtype=A.dtype)
    return out.at[:n, :n].set(A)


def pad_base_vector(x, n_pad: int):
    n = x.shape[0]
    out = jnp.ones((n_pad,), dtype=x.dtype)
    return out.at[:n].set(x)


def split_matrix_planes(A):
    """Zero-padded (re, im) planes of a complex matrix or (B, n, n) stack."""
    pad = pad_matrix if A.ndim == 2 else jax.vmap(pad_matrix)
    return pad(jnp.real(A)), pad(jnp.imag(A))


def split_base_planes(xb, n_pad: int):
    """Padded (re, im) planes of NW base vector(s), trailing unit column.

    Padded rows multiply by (1 + 0i): the re plane pads with ones, the im
    plane with zeros.  ``xb`` is (n,) or (B, n); returns (..., n_pad, 1).
    """
    n = xb.shape[-1]
    shape = xb.shape[:-1] + (n_pad,)
    dtype = jnp.real(xb).dtype
    xbr = jnp.ones(shape, dtype).at[..., :n].set(jnp.real(xb))
    xbi = jnp.zeros(shape, dtype).at[..., :n].set(jnp.imag(xb))
    return xbr[..., None], xbi[..., None]


def kernel_reduce(parts_hi, parts_lo, p0, n: int, axis=None):
    """Cross-block twofloat epilogue shared by every kernel entry.

    Sums the per-block (hi, lo) partials, folds in the base (g = 0)
    product and applies the final Ryser factor -- the "quad outer sum" of
    the paper, per matrix (``axis=1`` for batched partials) and per
    complex component (callers run it once per plane).
    """
    # partials axis length = num_blocks, fixed by kernel geometry per plan --
    # association never varies with batch or device count
    hi, e = P.two_sum(jnp.sum(parts_hi, axis=axis),    # permlint: disable=PL001  # shape-stable by kernel geometry
                      jnp.sum(parts_lo, axis=axis))    # permlint: disable=PL001  # shape-stable by kernel geometry
    total = P.tf_add_acc(P.TwoFloat(hi, e), p0)
    return P.tf_value(total) * _final_factor(n)


def block_partials_pallas(A, *, dev_chunk_base: int = 0,
                          num_blocks: int | None = None,
                          geometry: Geometry | None = None,
                          precision: str = "dq_acc",
                          mode: str = "baseline",
                          interpret: bool | None = None):
    """Run the kernel over ``num_blocks`` blocks starting at chunk
    ``dev_chunk_base``; returns (num_blocks, 2) (hi, lo) partials."""
    from .ryser_pallas import ryser_pallas_call
    A = jnp.asarray(A)
    n = A.shape[0]
    TB, C, Wu, full_blocks = (geometry or DEFAULT_GEOMETRY).kernel_geometry(n)
    if num_blocks is None:
        num_blocks = full_blocks
    A_pad = pad_matrix(A)
    xb = pad_base_vector(nw_base_vector(A), A_pad.shape[0]).reshape(-1, 1)
    out = ryser_pallas_call(
        A_pad, xb, dev_chunk_base, n=n, TB=TB, C=C, Wu=Wu,
        num_blocks=num_blocks, precision=precision, mode=mode,
        interpret=interpret)
    return out, (TB, C, Wu, full_blocks)


# ---------------------------------------------------------------------------
# The real/complex x scalar/batched dispatch helpers
# ---------------------------------------------------------------------------
# Shared scaffolding: padding + NW base vectors on the way in, the
# twofloat ``kernel_reduce`` epilogue on the way out -- one copy serving
# both the dense and the sparse arm, which differ only in kernel entry
# points (and the extra padded-CCS operands the sparse kernels take).

def _prep_real(As, batched: bool):
    """(A_pads, xb_pads, xbs) for a real matrix or stack."""
    pad = jax.vmap(pad_matrix) if batched else pad_matrix
    A_pads = pad(As)
    n_pad = A_pads.shape[-1]
    xbs = (jax.vmap(nw_base_vector) if batched else nw_base_vector)(As)
    pad_xb = lambda x: pad_base_vector(x, n_pad)
    xb_pads = (jax.vmap(pad_xb) if batched else pad_xb)(xbs)[..., None]
    return A_pads, xb_pads, xbs


def _prep_complex(As, batched: bool):
    """Split (re, im) planes + padded base-vector planes for complex."""
    Ar_pads, Ai_pads = split_matrix_planes(As)
    # nw_base_vector is elementwise prep (row sums / padding), not an
    # accumulation body -- vmap here shares the exact scalar adds with
    # the unbatched path
    xbs = (jax.vmap(nw_base_vector) if batched else nw_base_vector)(As)  # permlint: disable=PL002  # elementwise prep, not an engine body
    xbr, xbi = split_base_planes(xbs, Ar_pads.shape[-1])
    return Ar_pads, Ai_pads, xbr, xbi, xbs


def _reduce_real(out, xbs, n: int, batched: bool):
    """Cross-block epilogue over (B, blocks, 2) real (hi, lo) partials."""
    p0 = jnp.prod(xbs, axis=-1)  # permlint: disable=PL001  # length-n product, shape set by the matrix
    return kernel_reduce(out[:, :, 0], out[:, :, 1], p0, n, axis=1) \
        if batched else \
        kernel_reduce(out[0, :, 0], out[0, :, 1], p0, n)


def _reduce_complex(out, xbs, n: int, batched: bool):
    """Per-plane epilogue over (B, blocks, 4) split-plane partials."""
    p0 = jnp.prod(xbs, axis=-1)  # permlint: disable=PL001  # length-n product, shape set by the matrix
    if batched:
        re = kernel_reduce(out[:, :, 0], out[:, :, 1], jnp.real(p0), n,
                           axis=1)
        im = kernel_reduce(out[:, :, 2], out[:, :, 3], jnp.imag(p0), n,
                           axis=1)
    else:
        re = kernel_reduce(out[0, :, 0], out[0, :, 1], jnp.real(p0), n)
        im = kernel_reduce(out[0, :, 2], out[0, :, 3], jnp.imag(p0), n)
    return re + 1j * im


def _pallas_values(As, *, batched: bool, precision: str, mode: str,
                   geometry: Geometry, interpret: bool | None):
    """One traced body behind every public dense pallas entry.

    ``As`` is (n, n) (``batched=False``) or (B, n, n); real input launches
    the real kernel, complex input the split-plane kernels -- everything
    else (geometry, padding, NW base vectors, the twofloat epilogue) is
    shared.  ``geometry`` is the single frozen knob bundle the tuner
    injects; its requested sizes are clamped to n's step space here.
    """
    from .ryser_complex import (ryser_pallas_call_complex,
                                ryser_pallas_call_complex_batched)
    from .ryser_pallas import ryser_pallas_call, ryser_pallas_call_batched
    n = As.shape[-1]
    TB, C, Wu, blocks = geometry.kernel_geometry(n)

    if not jnp.iscomplexobj(As):
        A_pads, xb_pads, xbs = _prep_real(As, batched)
        if batched:
            out = ryser_pallas_call_batched(
                A_pads, xb_pads, n=n, TB=TB, C=C, Wu=Wu, num_blocks=blocks,
                precision=precision, mode=mode, interpret=interpret)
        else:
            out = ryser_pallas_call(
                A_pads, xb_pads, 0, n=n, TB=TB, C=C, Wu=Wu,
                num_blocks=blocks, precision=precision, mode=mode,
                interpret=interpret)[None]
        return _reduce_real(out, xbs, n, batched)

    Ar_pads, Ai_pads, xbr, xbi, xbs = _prep_complex(As, batched)
    if batched:
        out = ryser_pallas_call_complex_batched(
            Ar_pads, Ai_pads, xbr, xbi, n=n, TB=TB, C=C, Wu=Wu,
            num_blocks=blocks, precision=precision, interpret=interpret)
    else:
        out = ryser_pallas_call_complex(
            Ar_pads, Ai_pads, xbr, xbi, 0, n=n, TB=TB, C=C, Wu=Wu,
            num_blocks=blocks, precision=precision, interpret=interpret)[None]
    return _reduce_complex(out, xbs, n, batched)


@partial(jax.jit, static_argnames=("batched", "precision", "mode",
                                   "geometry", "interpret"))
def _pallas_values_jit(As, batched, precision, mode, geometry, interpret):
    return _pallas_values(As, batched=batched, precision=precision,
                          mode=mode, geometry=geometry, interpret=interpret)


def _pallas_sparse_values(A_stack, rows_stack, vals_stack, *, batched: bool,
                          precision: str, geometry: Geometry,
                          interpret: bool | None):
    """Sparse arm of the dispatch helper (SpaRyser on Pallas).

    Mirrors ``_pallas_values`` over the padded-CCS layout of
    ``sparyser.pack_padded_ccs``: ``A_stack`` is (n, n) / (B, n, n) (the
    dense form, used only for the init matmul, NW base vectors and the
    boundary one-hot columns -- like the jnp SpaRyser engine),
    ``rows_stack``/``vals_stack`` are the (n, maxdeg) / (B, n, maxdeg)
    padded column arrays driving the Gray-code updates.  Geometry,
    padding and the twofloat epilogue (``kernel_reduce``) are shared with
    the dense arm; real input launches the real sparse kernel, complex
    input the split-plane ones.  The trace is specialized per
    (n, maxdeg) -- the batched analogue of the paper's per-pattern kernel
    generation, amortized over the bucket.
    """
    n = A_stack.shape[-1]
    TB, C, Wu, blocks = geometry.kernel_geometry(n)
    from .ryser_sparse import (ryser_sparse_pallas_call,
                               ryser_sparse_pallas_call_batched,
                               ryser_sparse_pallas_call_complex,
                               ryser_sparse_pallas_call_complex_batched)

    rows_stack = jnp.asarray(rows_stack)
    if not jnp.iscomplexobj(vals_stack):
        A_pads, xb_pads, xbs = _prep_real(A_stack, batched)
        if batched:
            out = ryser_sparse_pallas_call_batched(
                A_pads, rows_stack, vals_stack, xb_pads, n=n, TB=TB, C=C,
                Wu=Wu, num_blocks=blocks, precision=precision,
                interpret=interpret)
        else:
            out = ryser_sparse_pallas_call(
                A_pads, rows_stack, vals_stack, xb_pads, 0, n=n, TB=TB,
                C=C, Wu=Wu, num_blocks=blocks, precision=precision,
                interpret=interpret)[None]
        return _reduce_real(out, xbs, n, batched)

    Ar_pads, Ai_pads, xbr, xbi, xbs = _prep_complex(A_stack, batched)
    vr = jnp.real(vals_stack)
    vi = jnp.imag(vals_stack)
    if batched:
        out = ryser_sparse_pallas_call_complex_batched(
            Ar_pads, Ai_pads, rows_stack, vr, vi, xbr, xbi, n=n, TB=TB,
            C=C, Wu=Wu, num_blocks=blocks, precision=precision,
            interpret=interpret)
    else:
        out = ryser_sparse_pallas_call_complex(
            Ar_pads, Ai_pads, rows_stack, vr, vi, xbr, xbi, 0, n=n, TB=TB,
            C=C, Wu=Wu, num_blocks=blocks, precision=precision,
            interpret=interpret)[None]
    return _reduce_complex(out, xbs, n, batched)


@partial(jax.jit, static_argnames=("batched", "precision", "geometry",
                                   "interpret"))
def _pallas_sparse_values_jit(A_stack, rows_stack, vals_stack, batched,
                              precision, geometry, interpret):
    return _pallas_sparse_values(A_stack, rows_stack, vals_stack,
                                 batched=batched, precision=precision,
                                 geometry=geometry, interpret=interpret)


def sparse_batched_values_pallas(A_stack, rows_stack, vals_stack, *,
                                 precision: str = "dq_acc",
                                 geometry: Geometry | None = None,
                                 interpret: bool | None = None):
    """Traced (B,) sparse kernel values of a packed padded-CCS stack.

    The un-jitted traced body behind ``permanent_pallas_sparse_batched``,
    exposed so ``distributed.sparse_batch_permanents_on_mesh`` can run it
    per device under ``shard_map`` (``backend="pallas"``) -- the sparse
    analogue of the dense kernels' traced-chunk-base reuse.
    """
    return _pallas_sparse_values(A_stack, rows_stack, vals_stack,
                                 batched=True, precision=precision,
                                 geometry=geometry or DEFAULT_GEOMETRY,
                                 interpret=interpret)


def permanent_pallas(A, *, precision: str = "dq_acc", mode: str = "baseline",
                     geometry: Geometry | None = None,
                     interpret: bool | None = None):
    """perm(A) via the Pallas kernel (full iteration space, one device).

    Complex matrices run the split re/im kernel (window-batched mode)."""
    A = jnp.asarray(A)
    n = A.shape[0]
    if n == 1:
        return A[0, 0]
    if n == 2:
        return A[0, 0] * A[1, 1] + A[0, 1] * A[1, 0]
    if jnp.iscomplexobj(A):
        mode = "batched"             # the split-plane kernel's only mode
    return _pallas_values_jit(A, False, precision, mode,
                              geometry or DEFAULT_GEOMETRY,
                              pallas_interpret(A, interpret=interpret))


def permanent_pallas_batched(As, *, precision: str = "dq_acc",
                             mode: str = "batched",
                             geometry: Geometry | None = None,
                             interpret: bool | None = None):
    """perm of a (B, n, n) stack via ONE batch-grid kernel launch.

    The grid is (batch, block): every matrix's full iteration space runs
    inside a single ``pallas_call``, so compilation and dispatch are
    amortized over the stack (vs B separate ``permanent_pallas`` calls).
    Complex stacks launch the split re/im plane kernel
    (``ryser_complex.ryser_pallas_call_complex_batched``) with the same
    grid and geometry.
    """
    As = jnp.asarray(As)
    if As.ndim != 3 or As.shape[1] != As.shape[2]:
        raise ValueError(f"(B, n, n) stack required, got {As.shape}")
    n = As.shape[1]
    if n == 1:
        return As[:, 0, 0]
    if n == 2:
        return As[:, 0, 0] * As[:, 1, 1] + As[:, 0, 1] * As[:, 1, 0]
    if jnp.iscomplexobj(As):
        mode = "batched"             # the split-plane kernel's only mode
    elif mode not in ("baseline", "batched"):
        raise ValueError(f"batch grid supports baseline|batched, got {mode}")
    return _pallas_values_jit(As, True, precision, mode,
                              geometry or DEFAULT_GEOMETRY,
                              pallas_interpret(As, interpret=interpret))


def permanent_pallas_sparse(sp, *, precision: str = "dq_acc",
                            geometry: Geometry | None = None,
                            interpret: bool | None = None):
    """perm of one ``sparyser.SparseMatrix`` via the SpaRyser kernel.

    The scalar sparse entry the executor's pallas backend dispatches to:
    the matrix's padded CCS columns drive the Gray-code updates, the
    dense form serves only the init matmul / base vector / boundary
    one-hots.  Complex matrices run the split re/im plane sparse kernel.
    """
    n = sp.n
    A = jnp.asarray(sp.to_dense())
    if n == 1:
        return A[0, 0]
    if n == 2:
        return A[0, 0] * A[1, 1] + A[0, 1] * A[1, 0]
    rows, vals = sp.padded_columns()
    return _pallas_sparse_values_jit(A, jnp.asarray(rows),
                                     jnp.asarray(vals), False, precision,
                                     geometry or DEFAULT_GEOMETRY,
                                     pallas_interpret(A, interpret=interpret))


def permanent_pallas_sparse_batched(sps, *, precision: str = "dq_acc",
                                    geometry: Geometry | None = None,
                                    interpret: bool | None = None):
    """perms of a same-size ``SparseMatrix`` bucket via ONE (batch, block)
    grid SpaRyser kernel launch.

    The bucket is packed once on the host (``sparyser.pack_padded_ccs``,
    bucket-wide maxdeg; the extra padding scatters into the dummy row and
    never perturbs numerics) and a single ``pallas_call`` covers every
    matrix's full 2^{n-1} step space -- the sparse analogue of
    ``permanent_pallas_batched``.  Complex buckets launch the split-plane
    sparse kernel with the same grid and geometry.
    """
    from ..core.sparyser import pack_padded_ccs
    assert sps, "empty bucket"
    n = sps[0].n
    if n <= 2:
        return jnp.stack([jnp.asarray(permanent_pallas_sparse(
            sp, precision=precision)) for sp in sps])
    A_stack, rows_stack, vals_stack = pack_padded_ccs(sps)
    return _pallas_sparse_values_jit(jnp.asarray(A_stack),
                                     jnp.asarray(rows_stack),
                                     jnp.asarray(vals_stack), True,
                                     precision, geometry or DEFAULT_GEOMETRY,
                                     pallas_interpret(A_stack,
                                                      interpret=interpret))
