"""Pallas kernel vs ref.py oracle: shape/dtype/geometry sweeps (interpret)."""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import oracle
from repro.kernels import ops, ref
from repro.core.stepspace import Geometry as G

RNG = np.random.default_rng(11)


@pytest.mark.parametrize("n", [4, 5, 6, 8, 10, 12, 14])
@pytest.mark.parametrize("mode", ["baseline", "batched"])
def test_kernel_matches_exact(n, mode):
    A = RNG.uniform(-1, 1, (n, n))
    want = oracle.perm_ryser_exact(A)
    got = float(ops.permanent_pallas(A, mode=mode, geometry=G(8, 8, 4)))
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-12)


@pytest.mark.parametrize("lanes,spc,win", [(4, 4, 2), (16, 16, 16),
                                           (8, 32, 8), (32, 4, 4),
                                           (2, 2, 2), (64, 8, 8)])
@pytest.mark.parametrize("mode", ["baseline", "batched"])
def test_geometry_sweep(lanes, spc, win, mode):
    A = RNG.uniform(-1, 1, (11, 11))
    want = oracle.perm_ryser_exact(A)
    got = float(ops.permanent_pallas(A, mode=mode, geometry=G(lanes, spc, win)))
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-12)


@pytest.mark.parametrize("dtype,rtol", [(np.float64, 1e-9),
                                        (np.float32, 5e-4)])
@pytest.mark.parametrize("mode", ["baseline", "batched"])
def test_dtype_sweep(dtype, rtol, mode):
    A = RNG.uniform(0.1, 1.0, (10, 10)).astype(dtype)
    want = oracle.perm_ryser_exact(A.astype(np.float64))
    got = float(ops.permanent_pallas(A, mode=mode, geometry=G(8, 8, 4)))
    np.testing.assert_allclose(got, want, rtol=rtol)


@pytest.mark.parametrize("precision", ["dd", "kahan", "dq_acc"])
def test_precision_modes(precision):
    A = RNG.uniform(-1, 1, (10, 10))
    want = oracle.perm_ryser_exact(A)
    got = float(ops.permanent_pallas(A, precision=precision, geometry=G(8, 8, 4)))
    np.testing.assert_allclose(got, want, rtol=1e-8)


def test_block_partials_match_ref_oracle():
    """Per-block decomposition must match ref.py exactly (same blocking)."""
    n = 10
    A = RNG.uniform(-1, 1, (n, n))
    out, (TB, C, Wu, blocks) = ops.block_partials_pallas(
        A, geometry=G(8, 8, 4))
    want = ref.block_partials_ref(A, TB=TB, C=C, num_blocks=blocks)
    got = np.asarray(out[:, 0] + out[:, 1])
    np.testing.assert_allclose(
        got, np.asarray(want[:, 0] + want[:, 1]), rtol=1e-12, atol=1e-15)


def test_device_offset_partials_compose():
    """Two half-space kernel calls (as two devices would run) must sum to
    the full-space result -- the distributed decomposition invariant."""
    n = 11
    A = RNG.uniform(-1, 1, (n, n))
    TB, C, Wu, blocks = G(8, 8, 4).kernel_geometry(n)
    assert blocks % 2 == 0
    full, _ = ops.block_partials_pallas(A, geometry=G(8, 8, 4))
    lo_half, _ = ops.block_partials_pallas(
        A, dev_chunk_base=0, num_blocks=blocks // 2, geometry=G(8, 8, 4))
    hi_half, _ = ops.block_partials_pallas(
        A, dev_chunk_base=(blocks // 2) * TB, num_blocks=blocks // 2,
        geometry=G(8, 8, 4))
    np.testing.assert_allclose(float(jnp.sum(full)),
                               float(jnp.sum(lo_half) + jnp.sum(hi_half)),
                               rtol=1e-12)


def test_kernel_vs_ref_permanent_api():
    n = 9
    A = RNG.uniform(-1, 1, (n, n))
    TB, C, Wu, blocks = G(8, 8, 4).kernel_geometry(n)
    a = float(ops.permanent_pallas(A, geometry=G(8, 8, 4)))
    b = float(ref.permanent_ref(A, TB=TB, C=C, num_blocks=blocks))
    np.testing.assert_allclose(a, b, rtol=1e-12)


@given(st.integers(4, 9), st.integers(0, 10**6))
@settings(max_examples=25, deadline=None)
def test_property_kernel_matches_oracle(n, seed):
    rng = np.random.default_rng(seed)
    A = rng.uniform(-1, 1, (n, n))
    want = oracle.perm_ryser_exact(A)
    got = float(ops.permanent_pallas(A, geometry=G(4, 4, 4)))
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-12)


def test_all_ones_family():
    for n in [6, 9, 12]:
        A = np.full((n, n), 0.5)
        want = oracle.all_ones_permanent(n, 0.5)
        got = float(ops.permanent_pallas(A, geometry=G(8, 8, 8)))
        np.testing.assert_allclose(got, want, rtol=1e-10)


# ---------------------------------------------------------------- complex
@pytest.mark.parametrize("n", [4, 6, 9, 12])
def test_complex_kernel_matches_oracle(n):
    """Split re/im kernel (boson-sampling workloads) vs Fraction oracle."""
    rng = np.random.default_rng(100 + n)
    A = rng.normal(size=(n, n)) + 1j * rng.normal(size=(n, n))
    want = oracle.perm_ryser_exact(A)
    got = complex(np.asarray(ops.permanent_pallas(
        A, geometry=G(8, 8, 4))))
    assert abs(got - want) / abs(want) < 1e-9


@pytest.mark.parametrize("precision", ["dd", "kahan", "dq_acc"])
def test_complex_kernel_precisions(precision):
    rng = np.random.default_rng(77)
    A = rng.normal(size=(10, 10)) + 1j * rng.normal(size=(10, 10))
    want = oracle.perm_ryser_exact(A)
    got = complex(np.asarray(ops.permanent_pallas(
        A, precision=precision, geometry=G(8, 16, 8))))
    assert abs(got - want) / abs(want) < 1e-8


def test_complex_unitary_submatrix_probability():
    """|perm|^2 of a Haar-unitary submatrix is a valid probability."""
    rng = np.random.default_rng(5)
    z = (rng.normal(size=(8, 8)) + 1j * rng.normal(size=(8, 8)))
    q, r = np.linalg.qr(z)
    U = q * (np.diag(r) / np.abs(np.diag(r)))
    sub = U[:4, :4]
    amp = complex(np.asarray(ops.permanent_pallas(
        sub, geometry=G(4, 4, 4))))
    want = oracle.perm_ryser_exact(sub)
    assert abs(amp - want) / abs(want) < 1e-10
    assert 0 <= abs(amp) ** 2 <= 1 + 1e-9


# ---------------------------------------------------------------------------
# interpret mode follows the platform; no 64-bit kernel off the CPU
# ---------------------------------------------------------------------------

def _on_platform(monkeypatch, platform):
    """Make the kernels see ``platform`` as this process's backend."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: platform)


@pytest.mark.parametrize("platform,want", [("cpu", True), ("tpu", False)])
def test_interpret_mode_follows_platform(monkeypatch, platform, want):
    _on_platform(monkeypatch, platform)
    x = jnp.zeros((8, 8), jnp.float32)
    assert ops.pallas_interpret(x) is want
    assert ops.pallas_interpret(x, interpret=False) is False


def test_interpret_mode_is_refused_off_the_cpu(monkeypatch):
    _on_platform(monkeypatch, "tpu")
    with pytest.raises(ValueError, match="CPU only"):
        ops.pallas_interpret(jnp.zeros((8, 8), jnp.float32), interpret=True)


@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
def test_64bit_kernel_off_the_cpu_raises(monkeypatch, dtype):
    """backend='pallas' on a chip: a clear error naming the f32-base
    item, never the interpreter and never a silent jnp fallback."""
    from repro.core.solver import PermanentSolver, SolverConfig
    _on_platform(monkeypatch, "tpu")
    A = RNG.uniform(-1, 1, (7, 7)).astype(dtype)
    with pytest.raises(TypeError, match="f32-base numerics"):
        ops.permanent_pallas(A)
    solver = PermanentSolver(SolverConfig(backend="pallas", cache=False))
    with pytest.raises(TypeError, match="f32-base numerics"):
        solver.execute(solver.plan(A))


def test_64bit_campaign_pallas_wave_off_the_cpu_raises(monkeypatch):
    import jax
    from jax.sharding import Mesh
    from repro.core import distributed as Dm
    _on_platform(monkeypatch, "tpu")
    mesh = Mesh(np.array(jax.devices()[:1]), ("step",))
    A = RNG.uniform(-1, 1, (9, 9))
    with pytest.raises(TypeError, match="f32-base numerics"):
        Dm.slice_sums_on_mesh(A, mesh, np.array([0], np.int32),
                              chunks_per_slice=4, chunk_size=16,
                              backend="pallas")
