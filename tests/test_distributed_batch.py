"""Mesh-sharded batch execution (ISSUE 3 tentpole).

The ``distributed_batch`` strategy shards a same-size bucket's leading
axis over ``core.distributed``'s mesh; its contract is BIT-IDENTICAL
values to the ``jnp`` backend per precision mode.  Fast tests run on a
1-device mesh in-process (the smoke-test contract keeps this process at
1 device); real multi-device coverage runs in subprocesses with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (also exercised
directly by the CI multi-device job and ``benchmarks/batch_sharding``).
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from repro.core import distributed, engine, ryser, sparyser
from repro.core.executor import available_backends, get_backend
from repro.core.solver import PermanentSolver, SolverConfig

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
RNG = np.random.default_rng(20260726)

PRECISIONS = ("dd", "dq_fast", "dq_acc", "qq", "kahan")


def _mesh1():
    return jax.make_mesh((1,), ("data",))


def _rand_sparse(n, density, rng=RNG):
    return rng.uniform(0.5, 1.5, (n, n)) * (rng.uniform(0, 1, (n, n)) < density)


# ---------------------------------------------------------------------------
# entry points: bit-identity vs the jnp batched engines
# ---------------------------------------------------------------------------

def test_batch_on_mesh_bitwise_matches_jnp_per_precision():
    stack = RNG.uniform(-1, 1, (5, 9, 9))
    mesh = _mesh1()
    for prec in PRECISIONS:
        got = distributed.batch_permanents_on_mesh(stack, mesh,
                                                   precision=prec)
        ref = np.asarray(ryser.perm_ryser_batched(stack, precision=prec))
        assert np.array_equal(got, ref), prec


def test_sparse_batch_on_mesh_bitwise_matches_jnp():
    sps = [sparyser.SparseMatrix.from_dense(_rand_sparse(8, 0.25))
           for _ in range(3)]
    got = distributed.sparse_batch_permanents_on_mesh(sps, _mesh1())
    ref = np.asarray(sparyser.perm_sparyser_batched(sps))
    assert np.array_equal(got, ref)


def test_batch_on_mesh_tiny_n_closed_forms():
    stack = RNG.uniform(-1, 1, (4, 2, 2))
    got = distributed.batch_permanents_on_mesh(stack, _mesh1())
    ref = np.asarray(ryser.perm_ryser_batched(stack))
    np.testing.assert_allclose(got, ref, rtol=0)


@pytest.mark.parametrize("B,D,extent,live", [
    (17, 4, 8, [5, 4, 4, 4]),
    (21, 8, 4, [3, 3, 3, 3, 3, 2, 2, 2]),
    (32, 4, 8, [8, 8, 8, 8]),
    (3, 8, 1, [1, 1, 1, 0, 0, 0, 0, 0]),
    (5, 1, 8, [5]),
])
def test_mesh_bucket_is_dealt_evenly_into_rung_shards(B, D, extent, live):
    """Matrix i goes to shard i % D: the busiest shard holds ceil(B / D)
    live matrices, in a program whose extent is that count's rung."""
    got_extent, got_live = distributed._shard_layout(B, D)
    assert (got_extent, list(got_live)) == (extent, live)
    assert max(live) == -(-B // D) and sum(live) == B


def test_batch_on_mesh_validates_shape():
    with pytest.raises(ValueError):
        distributed.batch_permanents_on_mesh(np.zeros((3, 4, 5)), _mesh1())


# ---------------------------------------------------------------------------
# ISSUE 4 tentpole: complex is first-class at every distributed entry
# ---------------------------------------------------------------------------

def _rand_complex(B, n, rng=RNG):
    return rng.normal(size=(B, n, n)) + 1j * rng.normal(size=(B, n, n))


def test_complex_batch_on_mesh_bitwise_matches_jnp_per_precision():
    stack = _rand_complex(5, 8)
    mesh = _mesh1()
    for prec in PRECISIONS:
        got = distributed.batch_permanents_on_mesh(stack, mesh,
                                                   precision=prec)
        ref = np.asarray(ryser.perm_ryser_batched(stack, precision=prec))
        assert np.array_equal(got, ref), prec


def test_complex_sparse_batch_on_mesh_bitwise_matches_jnp():
    sps = [sparyser.SparseMatrix.from_dense(
        _rand_complex(1, 8)[0] * (RNG.uniform(0, 1, (8, 8)) < 0.3))
        for _ in range(3)]
    got = distributed.sparse_batch_permanents_on_mesh(sps, _mesh1())
    ref = np.asarray(sparyser.perm_sparyser_batched(sps))
    assert np.array_equal(got, ref)


def test_complex_accepted_at_every_distributed_entry():
    # no remaining "real-only" ValueError anywhere in core.distributed
    C = _rand_complex(1, 6)[0]
    mesh = _mesh1()
    ref = complex(engine.permanent(C))
    v = distributed.permanent_on_mesh(C, mesh)
    assert abs(complex(v) - ref) / abs(ref) < 1e-12
    r = distributed.DistributedPermanent(mesh).permanent(C)
    assert isinstance(r, complex)
    assert abs(r - ref) / abs(ref) < 1e-12
    for backend in ("distributed", "distributed_batch"):
        solver = PermanentSolver(backend=backend, distributed_ctx=mesh)
        assert solver.plan(C).is_complex
        assert solver.plan_batch([C]).is_complex
        req = solver.submit(C)
        solver.flush()
        assert abs(req.result() - ref) / abs(ref) < 1e-12
    vals = engine.permanent_batch([C, C], backend="distributed",
                                  distributed_ctx=mesh)
    np.testing.assert_allclose(vals, [ref, ref], rtol=1e-12)


def test_complex_solver_with_mesh_shards_bitwise_no_downgrade():
    mesh = _mesh1()
    mats = list(_rand_complex(4, 8)) \
        + [_rand_complex(1, 9)[0] * (RNG.uniform(0, 1, (9, 9)) < 0.25)
           for _ in range(3)]
    dist = PermanentSolver(SolverConfig(backend="distributed",
                                        preprocess=False),
                           distributed_ctx=mesh)
    jnp_s = PermanentSolver(SolverConfig(backend="jnp", preprocess=False))
    got, reports = dist.execute(dist.plan_batch(mats), return_report=True)
    ref = jnp_s.execute(jnp_s.plan_batch(mats))
    assert np.array_equal(got, ref), \
        "sharded complex buckets must be bit-identical to jnp"
    assert not dist.stats()["downgrades"]
    tags = [t for r in reports for t in r.dispatch]
    assert any(t.startswith("dense_batch") and "->" not in t for t in tags)


def test_complex_qq_plan_tags_precision_downgrade():
    C = _rand_complex(2, 6)
    solver = PermanentSolver(SolverConfig(precision="qq", preprocess=False))
    plan = solver.plan_batch(list(C))
    assert plan.precision == "kahan"
    assert plan.precision_downgrade == "qq->kahan"
    assert plan.to_json()["precision_downgrade"] == "qq->kahan"
    _, reports = solver.execute(plan, return_report=True)
    tags = [t for r in reports for t in r.dispatch]
    assert any("precision(qq->kahan)" in t for t in tags), tags
    assert any("precision(qq->kahan)" in t
               for t in solver.stats()["downgrades"])
    # real plans carry no such tag
    real_plan = solver.plan_batch([RNG.uniform(-1, 1, (6, 6))])
    assert real_plan.precision_downgrade is None


# ---------------------------------------------------------------------------
# executor routing: registry, sharded buckets, tagged downgrades
# ---------------------------------------------------------------------------

def test_registry_has_distributed_batch_strategy():
    assert "distributed_batch" in available_backends()
    be = get_backend("distributed_batch")
    assert be.name == "distributed_batch"
    # no mesh attached -> batch methods signal downgrade
    assert be.dense_batch(RNG.uniform(-1, 1, (3, 5, 5)),
                          precision="dq_acc", num_chunks=64) is None


def test_solver_without_mesh_downgrades_with_tag():
    mats = [RNG.uniform(-1, 1, (7, 7)) for _ in range(3)]
    solver = PermanentSolver(SolverConfig(backend="distributed",
                                          preprocess=False))
    vals, reports = solver.execute(solver.plan_batch(mats),
                                   return_report=True)
    ref = engine.permanent_batch(mats, preprocess=False)
    np.testing.assert_allclose(vals, ref, rtol=0)
    tags = [t for r in reports for t in r.dispatch]
    assert any("distributed->jnp" in t for t in tags), tags
    assert solver.stats()["downgrades"]


def test_solver_with_mesh_shards_buckets_bitwise():
    mesh = _mesh1()
    mats = [RNG.uniform(-1, 1, (8, 8)) for _ in range(4)] \
        + [_rand_sparse(9, 0.22) for _ in range(3)]
    dist = PermanentSolver(SolverConfig(backend="distributed",
                                        preprocess=False),
                           distributed_ctx=mesh)
    jnp_s = PermanentSolver(SolverConfig(backend="jnp", preprocess=False))
    got, reports = dist.execute(dist.plan_batch(mats), return_report=True)
    ref = jnp_s.execute(jnp_s.plan_batch(mats))
    assert np.array_equal(got, ref), "sharded buckets must be bit-identical"
    assert not dist.stats()["downgrades"]
    tags = [t for r in reports for t in r.dispatch]
    assert any(t.startswith("dense_batch") and "->" not in t for t in tags)


def test_bare_mesh_accepted_as_ctx_for_queue():
    mesh = _mesh1()
    solver = PermanentSolver(SolverConfig(backend="distributed",
                                          queue_max_batch=4,
                                          queue_max_delay_s=1e9),
                             distributed_ctx=mesh)
    mats = [RNG.uniform(-1, 1, (6, 6)) for _ in range(4)]
    reqs = [solver.submit(M) for M in mats]
    assert all(r.done for r in reqs)
    ref = engine.permanent_batch(mats)
    assert np.array_equal(np.array([r.result() for r in reqs]), ref)
    assert not solver.stats()["downgrades"]


# ---------------------------------------------------------------------------
# cache interaction: sharded values live under their own cache identity
# ---------------------------------------------------------------------------

def test_sharded_bucket_cache_roundtrip():
    mesh = _mesh1()
    mats = [RNG.uniform(-1, 1, (7, 7)) for _ in range(3)]
    solver = PermanentSolver(SolverConfig(backend="distributed",
                                          preprocess=False),
                             distributed_ctx=mesh)
    v1 = solver.execute(solver.plan_batch(mats))
    dispatches = solver.stats()["device_dispatches"]
    assert all(k[3] == "distributed_batch" for k in solver.cache._data), \
        "sharded values must be cached under the producing strategy"
    v2 = solver.execute(solver.plan_batch(mats))
    assert np.array_equal(v1, v2)
    assert solver.stats()["device_dispatches"] == dispatches, \
        "second pass must be all cache hits"


def test_singleton_bucket_under_mesh_stays_bitwise_and_cacheable():
    # a 1-leaf bucket must NOT fall back to the scalar step-space split
    # (not bit-identical to the batch engines, and its cache entry would
    # live under a key the batched probes never read)
    mesh = _mesh1()
    A = RNG.uniform(-1, 1, (8, 8))
    solver = PermanentSolver(SolverConfig(backend="distributed",
                                          preprocess=False),
                             distributed_ctx=mesh)
    v1 = solver.execute(solver.plan_batch([A]))
    jnp_solver = PermanentSolver(SolverConfig(backend="jnp",
                                              preprocess=False))
    ref = jnp_solver.execute(jnp_solver.plan_batch([A]))
    assert np.array_equal(v1, ref)
    assert all(k[3] == "distributed_batch" for k in solver.cache._data)
    dispatches = solver.stats()["device_dispatches"]
    v2 = solver.execute(solver.plan_batch([A]))
    assert np.array_equal(v1, v2)
    assert solver.stats()["device_dispatches"] == dispatches, \
        "singleton's cache entry must satisfy the batched probe"


def test_downgraded_and_sharded_values_use_distinct_cache_keys():
    # same solver config, with vs without a mesh: the no-mesh run caches
    # jnp numbers under "jnp", never under the distributed identity
    mats = [RNG.uniform(-1, 1, (6, 6)) for _ in range(3)]
    no_mesh = PermanentSolver(SolverConfig(backend="distributed",
                                           preprocess=False))
    no_mesh.execute(no_mesh.plan_batch(mats))
    assert all(k[3] == "jnp" for k in no_mesh.cache._data)


# ---------------------------------------------------------------------------
# multi-device subprocesses (XLA_FLAGS is init-time)
# ---------------------------------------------------------------------------

def _run_sub(code: str, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    full = textwrap.dedent("""
        import jax; jax.config.update("jax_enable_x64", True)
        import numpy as np
        from repro.core import distributed, engine, ryser, sparyser
        from repro.core.solver import PermanentSolver, SolverConfig
        mesh = jax.make_mesh((8,), ("data",))
    """) + textwrap.dedent(code)
    r = subprocess.run([sys.executable, "-c", full], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


@pytest.mark.slow
def test_eight_device_dense_bitwise_with_ragged_tail():
    out = _run_sub("""
        rng = np.random.default_rng(3)
        for n, B in ((10, 11), (13, 21)):   # B % 8 != 0: padded + masked
            stack = rng.uniform(-1, 1, (B, n, n))
            for prec in ("dd", "dq_fast", "dq_acc", "qq", "kahan"):
                got = distributed.batch_permanents_on_mesh(
                    stack, mesh, precision=prec)
                ref = np.asarray(ryser.perm_ryser_batched(
                    stack, precision=prec))
                assert np.array_equal(got, ref), (n, B, prec)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_eight_device_sparse_route_bitwise():
    out = _run_sub("""
        rng = np.random.default_rng(4)
        sps = [sparyser.SparseMatrix.from_dense(
                   rng.uniform(0.5, 1.5, (11, 11))
                   * (rng.uniform(0, 1, (11, 11)) < 0.25))
               for _ in range(13)]          # ragged over 8 devices
        got = distributed.sparse_batch_permanents_on_mesh(sps, mesh)
        ref = np.asarray(sparyser.perm_sparyser_batched(sps))
        assert np.array_equal(got, ref)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_eight_device_complex_dense_bitwise_with_ragged_tail():
    out = _run_sub("""
        rng = np.random.default_rng(6)
        for n, B in ((8, 11), (10, 21)):    # B % 8 != 0: padded + masked
            stack = rng.normal(size=(B, n, n)) \\
                + 1j * rng.normal(size=(B, n, n))
            for prec in ("dd", "dq_fast", "dq_acc", "qq", "kahan"):
                got = distributed.batch_permanents_on_mesh(
                    stack, mesh, precision=prec)
                ref = np.asarray(ryser.perm_ryser_batched(
                    stack, precision=prec))
                assert np.array_equal(got, ref), (n, B, prec)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_four_device_ragged_bucket_bitwise_and_balanced():
    out = _run_sub("""
        import time
        from repro.utils import spans
        mesh4 = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4])
        rng = np.random.default_rng(9)
        B, n = 17, 8
        real = rng.uniform(-1, 1, (B, n, n))
        cplx = rng.normal(size=(B, n, n)) + 1j * rng.normal(size=(B, n, n))
        for stack in (real, cplx):
            for prec in ("dd", "dq_fast", "dq_acc", "qq", "kahan"):
                t0 = time.perf_counter()
                got = distributed.batch_permanents_on_mesh(
                    stack, mesh4, precision=prec)
                launches = [s for s in spans.recent(since=t0)
                            if s.name == "engine.launch"]
                ref = np.asarray(ryser.perm_ryser_batched(
                    stack, precision=prec))
                assert np.array_equal(got, ref), (stack.dtype, prec)
                if stack is cplx:   # 4 shards of the rung of 5
                    (l,) = launches
                    assert (l.attrs["slots"], l.attrs["live"]) == (32, B)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_eight_device_complex_sparse_route_bitwise():
    out = _run_sub("""
        rng = np.random.default_rng(7)
        sps = [sparyser.SparseMatrix.from_dense(
                   (rng.normal(size=(9, 9)) + 1j * rng.normal(size=(9, 9)))
                   * (rng.uniform(0, 1, (9, 9)) < 0.3))
               for _ in range(13)]          # ragged over 8 devices
        got = distributed.sparse_batch_permanents_on_mesh(sps, mesh)
        ref = np.asarray(sparyser.perm_sparyser_batched(sps))
        assert np.array_equal(got, ref)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_eight_device_complex_solver_queue_and_cache():
    out = _run_sub("""
        rng = np.random.default_rng(8)
        pool = [rng.normal(size=(8, 8)) + 1j * rng.normal(size=(8, 8))
                for _ in range(6)]
        stream = [pool[i] for i in rng.integers(0, 6, 32)]
        dist = PermanentSolver(SolverConfig(backend="distributed",
                                            queue_max_batch=16,
                                            queue_max_delay_s=1e9),
                               distributed_ctx=mesh)
        reqs = [dist.submit(M) for M in stream]
        dist.flush()
        got = np.array([r.result() for r in reqs])
        ref = engine.permanent_batch(stream)
        assert np.array_equal(got, ref), np.abs(got - ref).max()
        st = dist.stats()
        assert not st["downgrades"], st["downgrades"]
        assert st["cache"]["hits"] > 0, "repeat pool must hit the cache"
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_eight_device_solver_queue_and_cache():
    out = _run_sub("""
        rng = np.random.default_rng(5)
        pool = [rng.uniform(-1, 1, (9, 9)) for _ in range(6)]
        stream = [pool[i] for i in rng.integers(0, 6, 32)]
        dist = PermanentSolver(SolverConfig(backend="distributed",
                                            queue_max_batch=16,
                                            queue_max_delay_s=1e9),
                               distributed_ctx=mesh)
        reqs = [dist.submit(M) for M in stream]
        dist.flush()
        got = np.array([r.result() for r in reqs])
        ref = engine.permanent_batch(stream)
        assert np.array_equal(got, ref), np.abs(got - ref).max()
        st = dist.stats()
        assert not st["downgrades"], st["downgrades"]
        assert st["cache"]["hits"] > 0, "repeat pool must hit the cache"
        print("OK")
    """)
    assert "OK" in out
