"""Batched permanent engine vs naive oracle and the scalar engine.

Covers the tentpole paths: vmapped chunked Ryser, batched SpaRyser,
batch-grid Pallas kernel, and the bucketed ``permanent_batch`` dispatcher
(real / complex / binary stacks, mixed dense+sparse in one call, ragged
sizes, batch-of-one equivalence).
"""

import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import engine, oracle, ryser, sparyser
from repro.core import precision as P
from repro.core.precision import PRECISION_MODES
from repro.core.stepspace import Geometry as G
from repro.kernels import ops
from repro.utils import spans

RNG = np.random.default_rng(20260725)


def _rand_sparse(n, density, rng=RNG):
    return rng.uniform(0.5, 1.5, (n, n)) * (rng.uniform(0, 1, (n, n)) < density)


# ---------------------------------------------------------------------------
# core.ryser.perm_ryser_batched
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,B", [(1, 3), (2, 4), (5, 6), (8, 8), (10, 3)])
def test_ryser_batched_matches_oracle(n, B):
    As = RNG.uniform(-1, 1, (B, n, n))
    got = np.asarray(ryser.perm_ryser_batched(jnp.asarray(As), num_chunks=64))
    ref = np.array([oracle.perm_ryser_exact(A) for A in As])
    np.testing.assert_allclose(got, ref, rtol=1e-10, atol=1e-12)


def test_ryser_batched_equals_scalar_chunked():
    As = RNG.uniform(-1, 1, (5, 9, 9))
    got = np.asarray(ryser.perm_ryser_batched(jnp.asarray(As), num_chunks=32))
    for b in range(5):
        one = float(ryser.perm_ryser_chunked(jnp.asarray(As[b]),
                                             num_chunks=32))
        assert got[b] == one, "batched must reuse the scalar chunk body"


@pytest.mark.parametrize("precision", ["dd", "dq_fast", "dq_acc", "kahan"])
def test_ryser_batched_precision_modes(precision):
    As = RNG.uniform(-1, 1, (4, 8, 8))
    got = np.asarray(ryser.perm_ryser_batched(jnp.asarray(As), num_chunks=16,
                                              precision=precision))
    ref = np.array([oracle.perm_ryser_exact(A) for A in As])
    np.testing.assert_allclose(got, ref, rtol=1e-9, atol=1e-13)


def test_ryser_batched_complex_stack():
    As = RNG.uniform(-1, 1, (4, 7, 7)) + 1j * RNG.uniform(-1, 1, (4, 7, 7))
    got = np.asarray(ryser.perm_ryser_batched(jnp.asarray(As)))
    ref = np.array([oracle.perm_ryser_exact(A) for A in As])
    np.testing.assert_allclose(got, ref, rtol=1e-9)


def test_ryser_batched_complex_batch_shape_invariant():
    # the split-plane engine's values must not depend on the batch extent
    # (the basis of the sharded complex path's bit-identity contract)
    As = RNG.normal(size=(6, 7, 7)) + 1j * RNG.normal(size=(6, 7, 7))
    for prec in ("dd", "dq_fast", "dq_acc", "qq", "kahan"):
        full = np.asarray(ryser.perm_ryser_batched(jnp.asarray(As),
                                                   num_chunks=16,
                                                   precision=prec))
        for B in (1, 2, 5):
            sub = np.asarray(ryser.perm_ryser_batched(jnp.asarray(As[:B]),
                                                      num_chunks=16,
                                                      precision=prec))
            assert np.array_equal(sub, full[:B]), (prec, B)
        one = complex(np.asarray(ryser.perm_ryser_chunked(
            jnp.asarray(As[0]), num_chunks=16, precision=prec)))
        assert one == complex(full[0]), \
            "complex scalar straggler must match its bucket value"


def test_ryser_batched_rejects_non_stack():
    with pytest.raises(ValueError):
        ryser.perm_ryser_batched(jnp.zeros((3, 4, 5)))


# ---------------------------------------------------------------------------
# the complex batch program's dead slots
# ---------------------------------------------------------------------------

def _lax_map_values_complex(Ars, Ais, T, C, precision):
    """The complex batch body as a ``lax.map`` over every slot: what the
    engine ran before it took a live count, kept as the reference."""
    precision = ryser.complex_precision(precision)
    n = Ars.shape[1]

    def one(planes):
        ar, ai = planes
        parts_r, parts_i, (p0r, p0i) = ryser.chunk_partial_sums_complex(
            ar, ai, T, C, precision)
        rh, rl, ih, il, p0r, p0i = jax.lax.optimization_barrier(
            (parts_r.hi, parts_r.lo, parts_i.hi, parts_i.lo, p0r, p0i))
        hr, er = ryser.tf_tree_sum(rh, rl)
        hi_, ei = ryser.tf_tree_sum(ih, il)
        tot_r = P.tf_add_acc(P.TwoFloat(hr, er), p0r)
        tot_i = P.tf_add_acc(P.TwoFloat(hi_, ei), p0i)
        f = ryser._final_factor(n)
        return P.tf_value(tot_r) * f, P.tf_value(tot_i) * f

    return jax.lax.map(one, (Ars, Ais))


def _bits(x):
    return np.asarray(x).view(np.uint64)


@pytest.mark.parametrize("precision", PRECISION_MODES)
@pytest.mark.parametrize("B", [1, 3, 5, 8])
def test_complex_batch_bit_identical_to_lax_map_body(B, precision):
    n, chunks = 7, 16
    rng = np.random.default_rng(B)
    As = rng.normal(size=(B, n, n)) + 1j * rng.normal(size=(B, n, n))
    As[0, 1, ::2] = -0.0                   # signed zeros in added columns
    T, C, _ = ryser.chunk_geometry(n, chunks)
    ref = jax.jit(_lax_map_values_complex, static_argnums=(2, 3, 4))(
        As.real, As.imag, T, C, precision)
    got = ryser.perm_ryser_batched(As, num_chunks=chunks, precision=precision)
    assert got.shape == (B,)
    np.testing.assert_array_equal(_bits(got.real), _bits(ref[0]))
    np.testing.assert_array_equal(_bits(got.imag), _bits(ref[1]))


@pytest.mark.parametrize("live", [1, 5, 17, 32])
def test_dead_slots_read_zero_and_leave_live_values(live):
    rng = np.random.default_rng(live)
    Ar, Ai = rng.normal(size=(2, 32, 5, 5))
    full = ryser._batched_complex_jit(Ar, Ai, np.int32(32), 16, "dq_acc")
    part = ryser._batched_complex_jit(Ar, Ai, np.int32(live), 16, "dq_acc")
    for p, f in zip(part, full):
        np.testing.assert_array_equal(_bits(p[:live]), _bits(f[:live]))
        assert not np.any(_bits(p[live:]))     # +0.0, not a signed zero


def _gray_scans(jaxpr, lane_shape):
    """Scans whose carry holds the (n, T) row-sum state, at any depth."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            body = eqn.params["jaxpr"].jaxpr
            k0 = eqn.params["num_consts"]
            carry = body.invars[k0:k0 + eqn.params["num_carry"]]
            if any(tuple(v.aval.shape) == lane_shape for v in carry):
                found.append(eqn)
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                found += _gray_scans(sub, lane_shape)
    return found


def test_gray_scan_runs_under_the_live_bound():
    n, B = 6, 4
    T, C, _ = ryser.chunk_geometry(n, 4)
    planes = jnp.zeros((B, n, n))
    closed = jax.make_jaxpr(
        lambda r, i, live: ryser.batched_values_complex(r, i, live, T, C,
                                                        "dq_acc"))(
        planes, planes, jnp.int32(B))
    live = closed.jaxpr.invars[2]
    assert len(_gray_scans(closed.jaxpr, (n, T))) == 1
    (loop,) = [e for e in closed.jaxpr.eqns if e.primitive.name == "while"]
    assert _gray_scans(loop.params["body_jaxpr"].jaxpr, (n, T))
    # the loop's bound is the live count: it enters the carry the
    # loop's condition compares the slot index with
    assert live in loop.invars
    k = loop.params["cond_nconsts"] + loop.params["body_nconsts"]
    bound = loop.params["cond_jaxpr"].jaxpr.invars[
        loop.params["cond_nconsts"] + loop.invars[k:].index(live)]
    (lt,) = loop.params["cond_jaxpr"].jaxpr.eqns
    assert lt.primitive.name == "lt" and lt.invars[1] is bound


def test_one_compile_for_every_live_count():
    rng = np.random.default_rng(7)
    Ar, Ai = rng.normal(size=(2, 8, 5, 5))
    ryser._batched_complex_jit(Ar, Ai, np.int32(3), 16, "dq_acc")
    size = ryser._batched_complex_jit._cache_size()
    ryser._batched_complex_jit(Ar, Ai, np.int32(6), 16, "dq_acc")
    assert ryser._batched_complex_jit._cache_size() == size


def test_odd_stack_runs_in_its_rung_program():
    rng = np.random.default_rng(11)
    As = rng.normal(size=(8, 5, 5)) + 1j * rng.normal(size=(8, 5, 5))
    ryser.perm_ryser_batched(As, num_chunks=16)
    size = ryser._batched_complex_jit._cache_size()
    t0 = time.perf_counter()
    got = ryser.perm_ryser_batched(As[:5], num_chunks=16)
    assert ryser._batched_complex_jit._cache_size() == size
    (launch,) = [s for s in spans.recent(since=t0)
                 if s.name == "engine.launch"]
    assert (launch.attrs["slots"], launch.attrs["live"]) == (8, 5)
    ref = np.array([oracle.perm_ryser_exact(A) for A in As[:5]])
    np.testing.assert_allclose(got, ref, rtol=1e-10)


@pytest.mark.parametrize("backend,program,dtype", [
    ("jnp", ryser._batched_jit, float),
    ("pallas", ops._pallas_values_jit, float),
    ("pallas", ops._pallas_values_jit, complex),
])
def test_dense_bucket_runs_in_its_rung_program(backend, program, dtype):
    """A bucket of 3 runs in the program of 4: no compile of its own, and
    its values are those of the same matrices in a full bucket."""
    from repro.core.executor import get_backend
    rng = np.random.default_rng(5)
    As = rng.normal(size=(4, 5, 5))
    if dtype is complex:
        As = As + 1j * rng.normal(size=(4, 5, 5))
    run = get_backend(backend).dense_batch
    full = run(As, precision="dq_acc", num_chunks=16)
    size = program._cache_size()
    got = run(As[:3], precision="dq_acc", num_chunks=16)
    assert program._cache_size() == size
    assert got.shape == (3,)
    np.testing.assert_array_equal(got, full[:3])


# ---------------------------------------------------------------------------
# core.sparyser.perm_sparyser_batched
# ---------------------------------------------------------------------------

def test_sparyser_batched_matches_oracle():
    mats = [_rand_sparse(9, 0.25) for _ in range(6)]
    sps = [sparyser.SparseMatrix.from_dense(M) for M in mats]
    got = sparyser.perm_sparyser_batched(sps, num_chunks=64)
    ref = np.array([oracle.perm_ryser_exact(M) for M in mats])
    np.testing.assert_allclose(got, ref, rtol=1e-9, atol=1e-12)


def test_sparyser_batched_complex_matches_oracle():
    mats = [(RNG.normal(size=(8, 8)) + 1j * RNG.normal(size=(8, 8)))
            * (RNG.uniform(0, 1, (8, 8)) < 0.3) for _ in range(4)]
    sps = [sparyser.SparseMatrix.from_dense(M) for M in mats]
    got = sparyser.perm_sparyser_batched(sps, num_chunks=16)
    ref = np.array([oracle.perm_ryser_exact(M) for M in mats])
    np.testing.assert_allclose(got, ref, rtol=1e-9, atol=1e-12)
    # scalar complex straggler matches its bucket value bitwise
    one = sparyser.perm_sparyser_chunked(sps[0], num_chunks=16)
    assert one == sparyser.perm_sparyser_batched(sps[:1],
                                                 num_chunks=16)[0].item()


def test_sparyser_batched_mixed_degrees_pad_to_bucket_max():
    # very different column degrees in one bucket: padding must stay inert
    mats = [_rand_sparse(8, d) for d in (0.15, 0.5, 0.9)]
    sps = [sparyser.SparseMatrix.from_dense(M) for M in mats]
    got = sparyser.perm_sparyser_batched(sps, num_chunks=16)
    ref = np.array([oracle.perm_ryser_exact(M) for M in mats])
    np.testing.assert_allclose(got, ref, rtol=1e-9, atol=1e-12)


# ---------------------------------------------------------------------------
# kernels.ops.permanent_pallas_batched (batch-grid kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["baseline", "batched"])
def test_pallas_batched_matches_oracle(mode):
    As = RNG.uniform(-1, 1, (5, 8, 8))
    got = np.asarray(ops.permanent_pallas_batched(
        jnp.asarray(As), mode=mode, geometry=G(8, 8, 4)))
    ref = np.array([oracle.perm_ryser_exact(A) for A in As])
    np.testing.assert_allclose(got, ref, rtol=1e-9, atol=1e-12)


def test_pallas_batched_equals_scalar_kernel():
    As = RNG.uniform(-1, 1, (3, 9, 9))
    got = np.asarray(ops.permanent_pallas_batched(
        jnp.asarray(As), geometry=G(8, 8, 4)))
    for b in range(3):
        one = float(ops.permanent_pallas(As[b], mode="batched", geometry=G(8, 8, 4)))
        np.testing.assert_allclose(got[b], one, rtol=1e-12)


def test_pallas_batched_complex_matches_oracle():
    # ISSUE 4: complex stacks run the split-plane (batch, block) kernel
    Cs = RNG.uniform(-1, 1, (4, 8, 8)) + 1j * RNG.uniform(-1, 1, (4, 8, 8))
    got = np.asarray(ops.permanent_pallas_batched(
        jnp.asarray(Cs), geometry=G(8, 8, 4)))
    ref = np.array([oracle.perm_ryser_exact(C) for C in Cs])
    np.testing.assert_allclose(got, ref, rtol=1e-9)


def test_pallas_batched_complex_equals_scalar_complex_kernel():
    Cs = RNG.uniform(-1, 1, (3, 9, 9)) + 1j * RNG.uniform(-1, 1, (3, 9, 9))
    for prec in ("dd", "kahan", "dq_acc"):
        got = np.asarray(ops.permanent_pallas_batched(
            jnp.asarray(Cs), precision=prec, geometry=G(8, 8, 4)))
        for b in range(3):
            one = complex(np.asarray(ops.permanent_pallas(
                Cs[b], precision=prec, geometry=G(8, 8, 4))))
            assert got[b] == one, \
                "batch grid must reuse the scalar complex block body"


def test_pallas_batched_rejects_schedmat():
    with pytest.raises(ValueError):
        ops.permanent_pallas_batched(jnp.zeros((2, 5, 5)), mode="schedmat")


# ---------------------------------------------------------------------------
# engine.permanent_batch (the public bucketed dispatcher)
# ---------------------------------------------------------------------------

def test_batch_real_stack_matches_scalar_engine():
    As = RNG.uniform(-1, 1, (12, 8, 8))
    got = engine.permanent_batch(As)
    ref = np.array([engine.permanent(A) for A in As])
    assert got.dtype == np.float64
    np.testing.assert_allclose(got, ref, rtol=1e-10)


def test_batch_complex_stack():
    Cs = [RNG.normal(size=(7, 7)) + 1j * RNG.normal(size=(7, 7))
          for _ in range(5)]
    got = engine.permanent_batch(Cs)
    ref = np.array([engine.permanent(C) for C in Cs])
    assert got.dtype == np.complex128
    np.testing.assert_allclose(got, ref, rtol=1e-9)


def test_batch_binary_stack_exact_integers():
    Bs = (RNG.uniform(0, 1, (6, 10, 10)) < 0.5).astype(np.int64)
    got = engine.permanent_batch(Bs)
    ref = np.array([float(oracle.perm_bigint(b)) for b in Bs])
    np.testing.assert_allclose(np.round(got), ref)


def test_batch_mixed_density_one_call():
    # dense + sparse dispatch inside a single permanent_batch call
    mats = [RNG.uniform(-1, 1, (8, 8)) for _ in range(4)]
    mats += [_rand_sparse(9, 0.22) for _ in range(4)]
    got, reports = engine.permanent_batch(mats, return_report=True)
    ref = np.array([engine.permanent(M) for M in mats])
    np.testing.assert_allclose(got, ref, rtol=1e-9, atol=1e-12)
    tags = ",".join(t for r in reports for t in r.dispatch)
    assert "dense_batch" in tags


def test_batch_of_one_equals_scalar():
    A = RNG.uniform(-1, 1, (10, 10))
    assert engine.permanent_batch([A])[0] == engine.permanent(A)
    Ssp = _rand_sparse(9, 0.2)
    np.testing.assert_allclose(engine.permanent_batch([Ssp])[0],
                               engine.permanent(Ssp), rtol=1e-12)


def test_batch_ragged_sizes_fall_back_to_scalar():
    mats = [RNG.uniform(-1, 1, (n, n)) for n in (4, 6, 8, 8, 1, 2)]
    got = engine.permanent_batch(mats)
    ref = np.array([engine.permanent(M) for M in mats])
    np.testing.assert_allclose(got, ref, rtol=1e-10)


def test_batch_pallas_backend():
    As = RNG.uniform(-1, 1, (6, 8, 8))
    got = engine.permanent_batch(As, backend="pallas", preprocess=False)
    ref = np.array([engine.permanent(A, backend="pallas", preprocess=False)
                    for A in As])
    np.testing.assert_allclose(got, ref, rtol=1e-9)


def test_batch_dm_zeroed_matrix_gives_zero():
    # a matrix with an empty row has permanent 0; DM must short-circuit it
    A = RNG.uniform(-1, 1, (6, 6)) * (RNG.uniform(0, 1, (6, 6)) < 0.3)
    A[2, :] = 0.0
    mats = [A, RNG.uniform(-1, 1, (6, 6))]
    got = engine.permanent_batch(mats)
    assert got[0] == 0.0
    np.testing.assert_allclose(got[1], engine.permanent(mats[1]), rtol=1e-10)


def test_batch_rejects_bad_inputs():
    with pytest.raises(ValueError):
        engine.permanent_batch([np.zeros((3, 4))])


def test_batch_complex_distributed_without_mesh_downgrades():
    # complex distributed batches are allowed now (ISSUE 4); without a
    # mesh ctx they downgrade to jnp with a tag, exactly like real ones
    Cs = RNG.normal(size=(3, 6, 6)) + 1j * RNG.normal(size=(3, 6, 6))
    got, reports = engine.permanent_batch(Cs, backend="distributed",
                                          preprocess=False,
                                          return_report=True)
    ref = engine.permanent_batch(Cs, preprocess=False)
    np.testing.assert_allclose(got, ref, rtol=0)
    tags = [t for r in reports for t in r.dispatch]
    assert any("distributed->jnp" in t for t in tags), tags
