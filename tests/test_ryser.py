"""Dense Ryser engines vs exact oracles + precision-mode properties."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st
import hypothesis.extra.numpy as hnp

from repro.core import distributed, oracle, ryser
from repro.core.precision import PRECISION_MODES

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("n", [1, 2, 3, 4, 5, 6, 7, 8, 10, 12, 14])
def test_seq_matches_exact(n):
    A = RNG.uniform(-1, 1, (n, n))
    ref = oracle.perm_ryser_exact(A)
    got = float(ryser.perm_ryser_seq(jnp.asarray(A)))
    np.testing.assert_allclose(got, ref, rtol=1e-10, atol=1e-14)


@pytest.mark.parametrize("n", [3, 4, 6, 9, 11, 13])
@pytest.mark.parametrize("chunks", [2, 8, 64])
def test_chunked_matches_exact(n, chunks):
    A = RNG.uniform(-1, 1, (n, n))
    ref = oracle.perm_ryser_exact(A)
    got = float(ryser.perm_ryser_chunked(jnp.asarray(A), num_chunks=chunks))
    np.testing.assert_allclose(got, ref, rtol=1e-10, atol=1e-14)


@pytest.mark.parametrize("precision", PRECISION_MODES)
def test_all_precision_modes_correct(precision):
    A = RNG.uniform(-1, 1, (10, 10))
    ref = oracle.perm_ryser_exact(A)
    got = float(ryser.perm_ryser_chunked(jnp.asarray(A), num_chunks=16,
                                         precision=precision))
    np.testing.assert_allclose(got, ref, rtol=1e-9, atol=1e-13)


def test_definition_small_n():
    for n in range(1, 7):
        A = RNG.uniform(-1, 1, (n, n))
        d = oracle.perm_definition(A)
        r = oracle.perm_ryser_exact(A)
        np.testing.assert_allclose(d, r, rtol=1e-10, atol=1e-14)


def test_binary_matrix_exact_integer():
    for n in [6, 10, 13]:
        A = (RNG.uniform(0, 1, (n, n)) < 0.5).astype(np.int64)
        bi = oracle.perm_bigint(A)
        got = float(ryser.perm_ryser_chunked(
            jnp.asarray(A, dtype=jnp.float64), num_chunks=8))
        assert round(got) == bi


def test_complex_matrix():
    n = 8
    A = RNG.uniform(-1, 1, (n, n)) + 1j * RNG.uniform(-1, 1, (n, n))
    ref = oracle.perm_ryser_exact(A)
    got = complex(np.asarray(ryser.perm_ryser_chunked(
        jnp.asarray(A), num_chunks=8, precision="kahan")))
    np.testing.assert_allclose(got, ref, rtol=1e-9)


def test_all_ones_closed_form():
    # the paper's Sec. 5 validation family: perm(a * ones(n)) = n! a^n
    for n, a in [(6, 1.0), (8, 0.5), (10, 2.0)]:
        A = np.full((n, n), a)
        ref = oracle.all_ones_permanent(n, a)
        got = float(ryser.perm_ryser_chunked(jnp.asarray(A), num_chunks=8))
        np.testing.assert_allclose(got, ref, rtol=1e-10)


def test_transpose_invariance():
    A = RNG.uniform(-1, 1, (9, 9))
    a = float(ryser.perm_ryser_chunked(jnp.asarray(A)))
    b = float(ryser.perm_ryser_chunked(jnp.asarray(A.T)))
    np.testing.assert_allclose(a, b, rtol=1e-10)


def test_row_scaling_linearity():
    # perm is linear in each row
    A = RNG.uniform(-1, 1, (8, 8))
    B = A.copy()
    B[3] *= 2.5
    a = float(ryser.perm_ryser_chunked(jnp.asarray(A)))
    b = float(ryser.perm_ryser_chunked(jnp.asarray(B)))
    np.testing.assert_allclose(b, 2.5 * a, rtol=1e-9)


@given(hnp.arrays(np.float64, (5, 5),
                  elements=st.floats(min_value=-2, max_value=2,
                                     allow_nan=False)))
@settings(max_examples=30, deadline=None)
def test_property_matches_exact_oracle(A):
    ref = oracle.perm_ryser_exact(A)
    got = float(ryser.perm_ryser_chunked(jnp.asarray(A), num_chunks=4))
    np.testing.assert_allclose(got, ref, rtol=1e-9, atol=1e-10)


def test_chunk_geometry_invariants():
    for n in range(3, 30):
        for req in [1, 2, 7, 64, 10**6]:
            T, C, k = ryser.chunk_geometry(n, req)
            assert T * C == 1 << (n - 1)
            assert C == 1 << k and k >= 1
            assert T & (T - 1) == 0


# ---------------------------------------------------------------------------
# Gray-step column update: exact select, never a float multiply by +-1
# ---------------------------------------------------------------------------

def _multiply_signed_column(col, sign_bits):
    """The multiply form of the update: ``col * (2 * bit - 1)`` as a float."""
    s = (2 * sign_bits - 1).astype(col.dtype)
    return col[:, None] * s[None, :]


def _dense_body(kind, n, T, C, precision):
    """(fn, args) for one dense jnp scan body, traced fresh on each call."""
    rng = np.random.default_rng(1000 + n)
    planes = rng.uniform(-1, 1, (2, 2, n, n))
    # (plane, batch, n, n): signed zeros in every column the steps add,
    # negative entries, and a second matrix whose zero row makes every
    # product a signed zero
    planes[:, :, 0, ::2] = 0.0
    planes[:, :, 1, 1::2] = -0.0
    planes[:, :, 2 % n, :] = -np.abs(planes[:, :, 2 % n, :])
    planes[:, 1, n - 1, :] = np.where(np.arange(n) % 2, 0.0, -0.0)
    Ar, Ai = jnp.asarray(planes[0]), jnp.asarray(planes[1])
    first = jnp.asarray(0, dtype=jnp.int32)
    if kind == "complex_batch":
        return (lambda r, i, live: ryser.batched_values_complex(
                    r, i, live, T, C, precision),
                (Ar, Ai, jnp.asarray(2, dtype=jnp.int32)))
    if kind == "real_chunked":
        return (lambda a: ryser.chunk_partial_sums(a, T, C, precision),
                (Ar[0],))
    if kind == "campaign_real":
        return (lambda a, f: distributed._dyn_chunk_partials(a, f, T, C,
                                                             precision),
                (Ar[0], first))
    return (lambda r, i, f: distributed._dyn_chunk_partials((r, i), f, T, C,
                                                            precision),
            (Ar[0], Ai[0], first))


_DENSE_BODIES = ["complex_batch", "real_chunked", "campaign_real",
                 "campaign_complex"]


def _use_update(monkeypatch, update):
    monkeypatch.setattr(ryser, "signed_column", update)
    monkeypatch.setattr(distributed, "signed_column", update)


@pytest.mark.parametrize("precision", PRECISION_MODES)
@pytest.mark.parametrize("kind", _DENSE_BODIES)
@pytest.mark.parametrize("n", [3, 5, 12])
def test_signed_column_bit_identical_to_multiply(monkeypatch, n, kind,
                                                 precision):
    T, C, _ = ryser.chunk_geometry(n, 8)
    calls = []

    def reference(col, sign_bits):
        calls.append(col.shape)
        return _multiply_signed_column(col, sign_bits)

    _use_update(monkeypatch, reference)
    fn, args = _dense_body(kind, n, T, C, precision)
    want = jax.tree_util.tree_leaves(jax.jit(fn)(*args))
    assert calls, "the reference update was not traced"
    monkeypatch.undo()
    fn, args = _dense_body(kind, n, T, C, precision)
    got = jax.tree_util.tree_leaves(jax.jit(fn)(*args))
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g).view(np.uint64),
                                      np.asarray(w).view(np.uint64))


def _lane_muls_in_gray_scan(closed, lane_shape):
    """Count the ``mul`` ops of (n, T) lane shape inside every scan whose
    carry holds the (n, T) row-sum state (the Gray-step scan)."""
    def sub_jaxprs(params):
        for v in params.values():
            for x in (v if isinstance(v, (tuple, list)) else (v,)):
                if hasattr(x, "eqns"):
                    yield x
                elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                    yield x.jaxpr

    def muls(jaxpr):
        count = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "mul" and any(
                    tuple(o.aval.shape) == lane_shape for o in eqn.outvars):
                count += 1
            for sub in sub_jaxprs(eqn.params):
                count += muls(sub)
        return count

    found = []

    def scans(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                body = eqn.params["jaxpr"].jaxpr
                k0 = eqn.params["num_consts"]
                carry = body.invars[k0:k0 + eqn.params["num_carry"]]
                if any(tuple(v.aval.shape) == lane_shape for v in carry):
                    found.append(muls(body))
                    continue
            for sub in sub_jaxprs(eqn.params):
                scans(sub)

    scans(closed.jaxpr)
    assert len(found) == 1, f"expected one Gray-step scan, found {found}"
    return found[0]


@pytest.mark.parametrize("precision", PRECISION_MODES)
@pytest.mark.parametrize("kind", _DENSE_BODIES)
def test_gray_scan_body_has_no_lane_multiply(monkeypatch, kind, precision):
    n = 6
    T, C, _ = ryser.chunk_geometry(n, 4)
    assert T != n
    fn, args = _dense_body(kind, n, T, C, precision)
    assert _lane_muls_in_gray_scan(jax.make_jaxpr(fn)(*args), (n, T)) == 0
    # the guard sees the multiply form when it is put back
    _use_update(monkeypatch, _multiply_signed_column)
    fn, args = _dense_body(kind, n, T, C, precision)
    assert _lane_muls_in_gray_scan(jax.make_jaxpr(fn)(*args), (n, T)) > 0
