"""Emulated 64-bit unsigned integers as uint32 (hi, lo) pairs.

TPUs have no native 64-bit integer vector units; the Gray-code iteration
space of an n x n permanent reaches 2^{n-1} - 1 (n up to ~64), so global
step indices do not fit in 32 bits.  The Pallas kernels therefore carry
chunk/step indices as uint32 pairs and use these helpers for the handful
of bit manipulations the Ryser schedule needs:

    shift-left (chunk id -> start step), xor-shift (Gray code),
    bit extraction (signs, init bits), and ctz (changed-bit index).

Everything is element-wise over lane vectors and lowers to plain VPU
integer ops.  Validated against Python bigints in tests/test_u64emu.py.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

__all__ = [
    "u64", "u64_from_int", "u64_add", "u64_add_u32", "u64_shl",
    "u64_shr1", "u64_xor", "u64_gray", "u64_bit", "u64_ctz", "u64_leq",
    "ctz32", "to_float",
]

U1 = np.uint32(1)
U0 = np.uint32(0)


def u64(hi, lo):
    return (jnp.asarray(hi, jnp.uint32), jnp.asarray(lo, jnp.uint32))


def u64_from_int(v: int, like=None):
    """Host int -> (hi, lo) broadcast against `like` (a uint32 array)."""
    hi = np.uint32((v >> 32) & 0xFFFFFFFF)
    lo = np.uint32(v & 0xFFFFFFFF)
    if like is not None:
        return (jnp.full_like(like, hi), jnp.full_like(like, lo))
    return (hi, lo)


def u64_add(a, b):
    ahi, alo = a
    bhi, blo = b
    lo = alo + blo
    carry = (lo < alo).astype(jnp.uint32)
    return (ahi + bhi + carry, lo)


def u64_add_u32(a, v):
    ahi, alo = a
    v = jnp.asarray(v, jnp.uint32)
    lo = alo + v
    carry = (lo < alo).astype(jnp.uint32)
    return (ahi + carry, lo)


def u64_shl(a, k: int):
    """Shift left by a static 0 <= k < 32."""
    ahi, alo = a
    if k == 0:
        return a
    kk = np.uint32(k)
    hi = (ahi << kk) | (alo >> np.uint32(32 - k))
    lo = alo << kk
    return (hi, lo)


def u64_shr1(a):
    ahi, alo = a
    lo = (alo >> U1) | (ahi << np.uint32(31))
    hi = ahi >> U1
    return (hi, lo)


def u64_xor(a, b):
    return (a[0] ^ b[0], a[1] ^ b[1])


def u64_gray(a):
    """g ^ (g >> 1) across the pair."""
    return u64_xor(a, u64_shr1(a))


def u64_bit(a, j):
    """Bit j (0..63, traced per-lane uint32 array) as uint32 {0, 1}."""
    hi, lo = a
    j = jnp.asarray(j, jnp.uint32)
    # where/compare, not jnp.minimum: Mosaic cannot legalize unsigned min
    c31 = np.uint32(31)
    jlo = jnp.where(j < c31, j, c31)
    jhi = jnp.where(j - np.uint32(32) < c31, j - np.uint32(32), c31)
    from_lo = (lo >> jlo) & U1
    from_hi = (hi >> jhi) & U1
    return jnp.where(j < np.uint32(32), from_lo, from_hi)


def to_float(v, dtype):
    """A uint32 below 2^31 (a bit, an index) as float ``dtype``.

    Goes through int32: Mosaic has no uint32 -> float conversion.
    """
    return v.astype(jnp.int32).astype(dtype)


def ctz32(v):
    """Count trailing zeros of nonzero uint32 via exact float32 exponent.

    v & -v isolates the lowest set bit (a power of two <= 2^31); its f32
    representation is exact, so the unbiased exponent equals the index.
    The conversion goes through int32 (Mosaic has no uint32 -> float),
    which turns 2^31 into -2^31: same exponent, sign bit masked off.
    Avoids relying on popcount support in the TPU vector ISA.
    """
    import jax
    low = v & (~v + U1)
    f = low.astype(jnp.int32).astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(f, jnp.uint32)
    exp = ((bits >> np.uint32(23)) & np.uint32(0xFF)).astype(jnp.int32) - 127
    return exp.astype(jnp.uint32)


def u64_ctz(a):
    hi, lo = a
    lo_zero = lo == U0
    safe_lo = jnp.where(lo_zero, U1, lo)
    safe_hi = jnp.where(hi == U0, U1, hi)
    return jnp.where(lo_zero, np.uint32(32) + ctz32(safe_hi), ctz32(safe_lo))


def u64_leq(a, b):
    """a <= b (element-wise)."""
    ahi, alo = a
    bhi, blo = b
    return (ahi < bhi) | ((ahi == bhi) & (alo <= blo))
