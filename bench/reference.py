"""Plain reference permanents: Glynn's formula, jitted with plain jax.numpy.

    perm(A) = 2^-(n-1) * sum over d in {+1,-1}^n with d_1 = +1 of
              (prod_k d_k) * prod_i (sum_j d_j a_ij)

This imports nothing of the program under test.  It uses a different
formula (Glynn, not Ryser), a different enumeration (a table of low
signs crossed with blocks of high signs, not a Gray code), no
compensated arithmetic, and by default the host's CPU device, whose
float64 is IEEE (a TPU's is emulated).

``dtype`` selects the precision.  float64 (complex128 for complex
matrices) is the reference.  float32 (complex64) is the control: the
same computation one precision below, which the comparison's limit has
to fail.  In float64 the per-block sums are added on the host with
``math.fsum``; in float32 every step, the final sum included, stays in
float32.

The free signs d_2..d_n split into ``low`` bits, whose row-sum table
``L = A[:, low] @ S_low`` is made once, and ``high`` bits: for each high
sign vector h the terms are ``sgn(s) sgn(h) prod_i (L[i, s] + H[i, h])``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["permanent", "permanents"]


def _signs(bits: int) -> np.ndarray:
    """(bits, 2^bits) table of +-1: column s holds the bits of s."""
    s = np.arange(1 << bits, dtype=np.int64)
    b = (s[None, :] >> np.arange(bits, dtype=np.int64)[:, None]) & 1
    return (1 - 2 * b).astype(np.float64)


@partial(jax.jit, static_argnames=("low_bits", "block"))
def _block_sums(A, low_bits: int, block: int):
    """Per-high-sign sums, shape (2^high,), in A's dtype."""
    n = A.shape[0]
    k = low_bits
    hb = n - 1 - k
    S_low = jnp.asarray(_signs(k), A.dtype)
    S_high = jnp.asarray(_signs(hb), A.dtype)
    # full precision for the sign tables: a TPU's default float32 dot
    # takes one bfloat16 pass
    dot = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    L = dot(A[:, 1:1 + k], S_low)                     # (n, 2^k)
    H = A[:, :1] + dot(A[:, 1 + k:], S_high)          # (n, 2^hb)
    sgn_low = jnp.prod(S_low, axis=0)
    sgn_high = jnp.prod(S_high, axis=0)
    Hb = H.T.reshape(-1, block, n)                    # (blocks, block, n)

    def one(hblk):
        X = L[None, :, :] + hblk[:, :, None]          # (block, n, 2^k)
        return dot(jnp.prod(X, axis=1), sgn_low)

    return jax.lax.map(one, Hb).reshape(-1) * sgn_high


def _split(n: int, low_bits: int) -> tuple[int, int]:
    free = n - 1
    k = min(low_bits, free)
    hb = free - k
    block = min(1 << hb, 16)
    return k, block


def permanent(A, *, dtype=None, low_bits: int = 16, device=None):
    """perm(A) by Glynn's formula in ``dtype`` (default: float64 or
    complex128, following A) on ``device`` (default: the host CPU)."""
    A = np.asarray(A)
    n = A.shape[0]
    if A.ndim != 2 or A.shape[1] != n or n < 1:
        raise ValueError(f"square matrix required, got {A.shape}")
    if dtype is None:
        dtype = np.complex128 if np.iscomplexobj(A) else np.float64
    dtype = np.dtype(dtype)
    A = A.astype(dtype)
    if n == 1:
        return A[0, 0].item()
    device = device or jax.devices("cpu")[0]
    k, block = _split(n, low_bits)
    sums = _block_sums(jax.device_put(A, device), low_bits=k, block=block)
    if dtype.itemsize * (2 if dtype.kind == "f" else 1) <= 8:
        # control precision: the final sum stays in it too
        return (jnp.sum(sums) * dtype.type(2.0 ** -(n - 1))).item()
    sums = np.asarray(sums)
    if dtype.kind == "c":
        total = complex(math.fsum(sums.real), math.fsum(sums.imag))
    else:
        total = math.fsum(sums)
    return total * 2.0 ** -(n - 1)


def permanents(mats, **kw) -> list:
    """perm of each matrix in ``mats`` (see :func:`permanent`)."""
    return [permanent(M, **kw) for M in mats]
