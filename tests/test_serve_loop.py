"""Always-on permanent service (ISSUE 7): lanes, SLOs, backpressure,
metrics schema, legacy parity, and the warm-compile-cache cold start.

Everything time-dependent runs against an injected FakeClock -- deadline
expiry, lane ordering, and log cadence are deterministic, never sleeps.
The compile-cache test is a real two-cold-subprocess comparison and is
marked slow (CI's multidevice job runs it).
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import jax

jax.config.update("jax_enable_x64", True)

from repro.core.engine import permanent
from repro.core.solver import PermanentSolver, SolverConfig, SolverError
from repro.serve import (DEFAULT_LANES, Histogram, LaneQueue, LaneSpec,
                         PermanentService, ServiceConfig,
                         ShedError, ShedReason, quantized_batches,
                         run_soak, start_metrics_server)
from repro.utils import spans


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def mk(rng, n=5, complex_entries=False):
    M = rng.uniform(-1, 1, (n, n))
    if complex_entries:
        M = M + 1j * rng.uniform(-1, 1, (n, n))
    return M


def service(clock, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("log_every_s", float("inf"))
    return PermanentService(SolverConfig(backend="jnp"),
                            ServiceConfig(**kw), clock=clock, log=None)


# -- lanes / priority ---------------------------------------------------------

class TestLanes:
    def test_interactive_preempts_bulk(self):
        """A later interactive request dispatches before earlier bulk
        traffic of the same shape."""
        clock = FakeClock()
        svc = service(clock, max_batch=2)
        rng = np.random.default_rng(0)
        bulk = [svc.submit(mk(rng), lane="bulk", deadline_s=None)
                for _ in range(3)]
        inter = svc.submit(mk(rng), lane="interactive", deadline_s=None)
        svc.step()                      # one bucket of 2
        assert inter.done
        # the interactive ticket took one slot; oldest bulk backfilled
        assert bulk[0].done and not bulk[1].done and not bulk[2].done
        svc.drain()
        assert all(t.done for t in bulk)

    def test_unknown_lane_rejected(self):
        svc = service(FakeClock())
        with pytest.raises(ValueError, match="unknown lane"):
            svc.submit(np.eye(3), lane="nope")

    def test_lane_queue_priority_order(self):
        q = LaneQueue(DEFAULT_LANES)
        assert [l.name for l in q.lanes] == ["interactive", "bulk"]
        assert q.lane(None).name == "interactive"

    def test_values_match_scalar_engine(self):
        """Continuous dispatch with pow2 padding stays bitwise equal to
        the scalar engine (batch-shape independence + discarded pad)."""
        clock = FakeClock()
        svc = service(clock, max_batch=4)
        rng = np.random.default_rng(1)
        mats = [mk(rng, n=6) for _ in range(5)]
        ts = [svc.submit(M, deadline_s=None) for M in mats]
        svc.drain()
        for t, M in zip(ts, mats):
            assert t.result() == permanent(M)

    def test_complex_bucket(self):
        clock = FakeClock()
        svc = service(clock, max_batch=2)
        rng = np.random.default_rng(2)
        mats = [mk(rng, n=5, complex_entries=True) for _ in range(3)]
        ts = [svc.submit(M, deadline_s=None) for M in mats]
        svc.drain()
        for t, M in zip(ts, mats):
            assert t.result() == permanent(M)


# -- deadlines / shedding -----------------------------------------------------

class TestShedding:
    def test_deadline_expiry_sheds_with_reason(self):
        clock = FakeClock()
        svc = service(clock)
        t = svc.submit(np.eye(4), deadline_s=1.0)
        clock.t = 1.5
        svc.step()
        assert t.shed and t.shed_reason is ShedReason.DEADLINE_EXPIRED
        with pytest.raises(ShedError) as ei:
            t.result()
        assert ei.value.reason is ShedReason.DEADLINE_EXPIRED

    def test_lane_slo_is_default_deadline(self):
        clock = FakeClock()
        svc = service(clock)            # interactive slo_s=2.0
        t = svc.submit(np.eye(4), lane="interactive")
        clock.t = 2.1
        svc.step()
        assert t.shed and t.shed_reason is ShedReason.DEADLINE_EXPIRED

    def test_queue_full_backpressure(self):
        clock = FakeClock()
        svc = service(clock, max_queue_depth=2)
        rng = np.random.default_rng(3)
        ts = [svc.submit(mk(rng), deadline_s=None) for _ in range(3)]
        assert not ts[0].shed and not ts[1].shed
        assert ts[2].shed and ts[2].shed_reason is ShedReason.QUEUE_FULL
        assert "queue depth" in ts[2].shed_detail
        svc.drain()
        assert ts[0].done and ts[1].done

    def test_cost_budget_backpressure(self):
        clock = FakeClock()
        svc = service(clock, max_pending_cost=100.0)
        rng = np.random.default_rng(4)
        a = svc.submit(mk(rng, n=5), deadline_s=None)   # cost 5*16 = 80
        b = svc.submit(mk(rng, n=5), deadline_s=None)   # 160 > 100
        assert not a.shed
        assert b.shed and b.shed_reason is ShedReason.COST_BUDGET

    def test_shutdown_sheds_typed(self):
        clock = FakeClock()
        svc = service(clock)
        t = svc.submit(np.eye(4), deadline_s=None)
        (shed,) = svc.shutdown()
        assert shed is t and t.shed_reason is ShedReason.SHUTDOWN

    def test_result_before_dispatch_raises(self):
        svc = service(FakeClock())
        t = svc.submit(np.eye(4), deadline_s=None)
        with pytest.raises(RuntimeError, match="still queued"):
            t.result()


# -- fill_first (legacy PR 6 semantics) --------------------------------------

class TestFillFirst:
    def test_dispatch_only_when_full_or_aged(self):
        clock = FakeClock()
        svc = service(clock, max_batch=3, fill_first=True, deadline_s=5.0,
                      quantize_buckets=False,
                      lanes=(LaneSpec("default", 0, slo_s=None),))
        rng = np.random.default_rng(5)
        a = svc.submit(mk(rng), deadline_s=None)
        assert svc.step() == 0          # 1 of 3: waits
        b = svc.submit(mk(rng), deadline_s=None)
        assert svc.step() == 0
        c = svc.submit(mk(rng), deadline_s=None)
        assert svc.step() == 3          # full bucket dispatches
        assert a.done and b.done and c.done
        d = svc.submit(mk(rng), deadline_s=None)
        assert svc.step() == 0
        clock.t = 6.0                   # ... until the age trigger
        assert svc.step() == 1
        assert d.done

    def test_full_bucket_beats_older_partial(self):
        """A full bucket dispatches even when an older, non-full bucket
        of another size sorts ahead of it."""
        clock = FakeClock()
        svc = service(clock, max_batch=2, fill_first=True, deadline_s=1e9,
                      quantize_buckets=False,
                      lanes=(LaneSpec("default", 0, slo_s=None),))
        rng = np.random.default_rng(6)
        older = svc.submit(mk(rng, n=6), deadline_s=None)
        full = [svc.submit(mk(rng, n=7), deadline_s=None) for _ in range(2)]
        assert svc.step() == 2
        assert all(t.done for t in full) and not older.done

    def test_legacy_wrapper_matches_direct_solver_queue(self):
        """run_permanent_serving over the service == driving the PR 6
        solver queue by hand, bitwise."""
        from repro.launch.serve import run_permanent_serving

        out = run_permanent_serving(n=6, batch=4, requests=10,
                                    repeat_pool=3, deadline_s=1e9, seed=11)
        # reference: the solver queue directly, same stream construction
        rng = np.random.default_rng(11)
        pool = [rng.uniform(-1, 1, (6, 6)) for _ in range(3)]
        mats = [pool[i] for i in rng.integers(0, 3, 10)]
        solver = PermanentSolver(SolverConfig(
            backend="jnp", queue_max_batch=4, queue_max_delay_s=1e9))
        reqs = [solver.submit(M) for M in mats]
        solver.flush()
        ref = np.array([r.result() for r in reqs])
        assert np.array_equal(out["values"], ref)
        assert out["batches"] == 3      # 2 full + ragged tail
        snap = out["snapshot"]
        assert snap["requests"]["completed"] == 10
        assert snap["requests"]["shed_total"] == 0


# -- metrics ------------------------------------------------------------------

class TestMetrics:
    def test_histogram_quantiles(self):
        """Each quantile is the upper edge of its sample's bucket: at or
        above the nearest-rank quantile, by less than 5%."""
        h = Histogram(lo=1e-3, hi=1e3)
        for v in [0.01] * 98 + [5.0, 8.0]:
            h.observe(v)
        assert h.count == 100
        assert 0.01 <= h.quantile(0.5) <= 0.01 * 1.05
        assert 5.0 <= h.quantile(0.99) <= 5.0 * 1.05
        assert h.to_json()["max"] == 8.0
        lat = np.random.default_rng(3).lognormal(-3.0, 1.0, 2000)
        h = Histogram()
        for v in lat:
            h.observe(float(v))
        ranked = np.sort(lat)
        for q in (0.5, 0.9, 0.95, 0.99):
            exact = ranked[int(np.ceil(q * len(ranked))) - 1]
            assert exact <= h.quantile(q) <= exact * 1.05

    def test_snapshot_schema_and_consistency(self):
        clock = FakeClock()
        svc = service(clock, max_queue_depth=3)
        rng = np.random.default_rng(7)
        for i in range(5):
            svc.submit(mk(rng), lane="bulk" if i % 2 else "interactive",
                       deadline_s=None if i != 1 else 0.0)
        clock.t = 0.5
        svc.drain()
        snap = svc.snapshot()
        assert snap["schema"] == "repro.serve.metrics/v1"
        req = snap["requests"]
        assert req["admitted"] == (req["completed"] + req["shed_total"]
                                   + req["pending"])
        assert req["pending"] == 0
        # depth cap 3: submits 4 and 5 bounce; submit 2 expires queued
        assert req["shed"] == {"deadline_expired": 1, "queue_full": 2}
        assert snap["latency_s"]["overall"]["count"] == req["completed"]
        assert "interactive" in snap["latency_s"]
        assert snap["queue_depth"]["count"] >= 1
        assert snap["dispatches"] >= 1
        # the solver's stats (incl. per-leaf timings) come through whole
        assert snap["solver"]["device_dispatches"] >= 1
        assert any(k.startswith("dense_batch(")
                   for k in snap["solver"]["leaf_timings"])
        json.dumps(snap)                # JSON-clean end to end

    def test_leaf_timing_shape(self):
        clock = FakeClock()
        svc = service(clock)
        svc.submit(np.random.default_rng(8).uniform(-1, 1, (5, 5)),
                   deadline_s=None)
        svc.drain()
        (key, t), *_ = svc.solver.stats()["leaf_timings"].items()
        assert set(t) == {"count", "leaves", "total_s", "max_s", "mean_s"}
        assert t["count"] >= 1 and t["total_s"] > 0

    def test_dispatch_spans_match_the_log(self):
        """A drained service's serve.dispatch spans carry the served and
        padded lane counts of its dispatch_log and ladder; each ticket
        has one serve.queue span naming the dispatch that took it."""
        clock = FakeClock()
        svc = service(clock, max_batch=8)
        rng = np.random.default_rng(21)
        t0 = time.perf_counter()
        tickets = [svc.submit(mk(rng), deadline_s=None) for _ in range(11)]
        svc.drain()
        got = spans.recent(since=t0)
        disp = [s for s in got if s.name == "serve.dispatch"]
        assert [(d.attrs["served"], d.attrs["lanes"]) for d in disp] == \
            [(8, 8), (3, 4)]
        assert [d.attrs["served"] for d in disp] == \
            [served for _, served, _, _ in svc.dispatch_log]
        assert all(d.attrs["lanes"] in quantized_batches(8) for d in disp)
        assert [(d.attrs["n"], d.attrs["trigger"]) for d in disp] == \
            [(5, "ready")] * 2
        queue = {s.attrs["ticket"]: s for s in got if s.name == "serve.queue"}
        assert sorted(queue) == sorted(t.id for t in tickets)
        for k, t in enumerate(tickets):
            d = disp[0] if k < 8 else disp[1]
            q = queue[t.id]
            assert q.attrs["dispatch"] == d.id
            assert q.t0 == t.t_queued and q.t1 == d.t0
        for d, (_, _, seconds, _) in zip(disp, svc.dispatch_log):
            kids = {s.name: s for s in got if s.parent == d.id}
            assert set(kids) == {"serve.pad", "solver.plan",
                                 "solver.execute", "serve.resolve"}
            plan, execute = kids["solver.plan"], kids["solver.execute"]
            assert plan.seconds + execute.seconds <= seconds
            assert seconds == kids["serve.resolve"].t0 - kids["serve.pad"].t1
        timings = svc.solver.stats()["leaf_timings"]
        assert timings["dense_batch(n=5,jnp)"]["count"] == 2
        assert timings["dense_batch(n=5,jnp)"]["leaves"] == 12

    def test_metrics_http_endpoint(self):
        clock = FakeClock()
        svc = service(clock)
        server = start_metrics_server(svc.snapshot, port=0)
        try:
            port = server.server_address[1]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=5) as r:
                snap = json.loads(r.read())
            assert snap["schema"] == "repro.serve.metrics/v1"
        finally:
            server.shutdown()

    def test_periodic_log_line(self):
        clock = FakeClock()
        lines = []
        svc = PermanentService(
            SolverConfig(backend="jnp"),
            ServiceConfig(max_batch=2, log_every_s=10.0),
            clock=clock, log=lines.append)
        svc.submit(np.eye(3), deadline_s=None)
        svc.step()
        assert not lines                # cadence not reached
        clock.t = 11.0
        svc.step()
        assert len(lines) == 1 and "p99=" in lines[0]


# -- solver-layer satellites --------------------------------------------------

class TestSolverSatellites:
    def test_solver_error_names_bucket_and_count(self, monkeypatch):
        solver = PermanentSolver(SolverConfig(backend="jnp",
                                              queue_max_batch=100))
        req = solver.submit(np.eye(4))
        monkeypatch.setattr(solver, "_flush_bucket", lambda n: 0)
        with pytest.raises(SolverError, match=r"n=4 left 1 request"):
            req.result()

    def test_solver_config_clock_injected(self):
        clock = FakeClock()
        solver = PermanentSolver(SolverConfig(
            backend="jnp", clock=clock, queue_max_batch=100,
            queue_max_delay_s=2.0))
        req = solver.submit(np.eye(3))
        assert solver.poll() == 0
        clock.t = 2.5
        assert solver.poll() == 1 and req.done

    def test_solver_config_clock_excluded_from_json(self):
        cfg = SolverConfig(backend="jnp", clock=FakeClock())
        plan = PermanentSolver(cfg).plan(np.eye(3))
        js = plan.to_json()              # dict; must be json-clean
        assert "clock" not in js["config"]
        json.dumps(js)
        # and the clock doesn't break plan equality/fingerprints
        assert cfg.replace(clock=None) == cfg

    def test_queue_flush_records_solver_spans(self):
        solver = PermanentSolver(SolverConfig(backend="jnp",
                                              queue_max_batch=2))
        t0 = time.perf_counter()
        solver.submit(np.eye(4))
        solver.submit(np.eye(4))        # fills the bucket -> flush
        got = [s for s in spans.recent(since=t0)
               if s.name in ("solver.plan", "solver.execute")]
        assert [s.name for s in got] == ["solver.plan", "solver.execute"]
        plan, execute = got
        assert plan.t1 <= execute.t0 and plan.parent == execute.parent
        assert solver.flushes == 1


# -- soak helper --------------------------------------------------------------

class TestSoak:
    def test_run_soak_deterministic_clock(self):
        """Open-loop soak under a fake clock: every request resolves or
        sheds, forced expiries land as typed deadline sheds."""
        clock = FakeClock()
        svc = service(clock, max_batch=4)
        out = run_soak(svc, requests=12, rate_hz=1000.0, n=5,
                       repeat_pool=3, seed=9, expire_every=4, sleep=None)
        snap = out["snapshot"]
        req = snap["requests"]
        assert req["admitted"] == 12 + 0
        assert req["shed"] == {"deadline_expired": 3}
        assert req["completed"] == 9 and req["pending"] == 0
        assert snap["solver"]["cache"]["hits"] > 0   # repeat pool
        statuses = [("shed" if t.shed else "done") for t in out["tickets"]]
        assert statuses.count("shed") == 3

    def test_quantized_ladder(self):
        assert quantized_batches(8) == (1, 2, 4, 8)
        assert quantized_batches(6) == (1, 2, 4, 8)
        assert quantized_batches(1) == (1,)
        with pytest.raises(ValueError):
            quantized_batches(0)


# -- cold start / compile cache ----------------------------------------------

_SUB = r"""
import sys

import jax
jax.config.update("jax_enable_x64", True)
import numpy as np

from repro.core.solver import SolverConfig
from repro.serve import PermanentService, ServiceConfig, compile_stats

svc = PermanentService(
    SolverConfig(backend="jnp"),
    ServiceConfig(max_batch=4, compile_cache_dir=sys.argv[1],
                  warmup_ns=(6,), log_every_s=float("inf")),
    log=None)
warm = svc.warmup_report["compile"]
s0 = compile_stats()
t = svc.submit(np.random.default_rng(0).uniform(-1, 1, (6, 6)),
               deadline_s=None)
svc.step()
assert t.done
s1 = compile_stats()
print(f"STATS,warm_misses={warm['persistent_misses']},"
      f"warm_hits={warm['persistent_hits']},"
      f"first_misses={s1['persistent_misses'] - s0['persistent_misses']}")
"""


@pytest.mark.slow
def test_warm_compile_cache_cold_start(tmp_path):
    """Two cold processes sharing a compilation-cache dir: the second
    warms up without a single XLA compile, and neither compiles anything
    for its first dispatched bucket."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep * bool(env.get("PYTHONPATH")) \
        + env.get("PYTHONPATH", "")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)   # the test needs its own

    def cold_run():
        r = subprocess.run(
            [sys.executable, "-c", _SUB, str(tmp_path / "xla-cache")],
            env=env, capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
        line = next(l for l in r.stdout.splitlines()
                    if l.startswith("STATS,"))
        return dict(kv.split("=") for kv in line[6:].split(","))

    run1, run2 = cold_run(), cold_run()
    assert int(run1["warm_misses"]) > 0          # cold cache: compiled
    assert int(run2["warm_misses"]) == 0         # warm cache: no compiles
    assert int(run2["warm_hits"]) > 0
    assert int(run1["first_misses"]) == 0        # warm-up covered the
    assert int(run2["first_misses"]) == 0        # first bucket's geometry


@pytest.mark.parametrize("env_dir", [False, True], ids=["default", "env"])
def test_enable_compile_cache_directory(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache and no other
    directory is configured; otherwise the cache sits at the fixed
    .jax_cache/ of the checkout, whatever the working directory."""
    import jax
    from repro.serve import compile_cache

    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.chdir(tmp_path)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "env-cache"))
        want = str(tmp_path / "env-cache")
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(repo, ".jax_cache")
    got = compile_cache.enable_compile_cache()
    assert got == want
    assert os.path.isdir(got)
    if env_dir:
        # an explicit path does not override the environment either
        assert compile_cache.enable_compile_cache(str(tmp_path / "x")) == want
        assert "jax_compilation_cache_dir" not in updates
    else:
        assert want == compile_cache.DEFAULT_CACHE_DIR
        assert updates["jax_compilation_cache_dir"] == want
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_campaign_backend_follows_solver_config(monkeypatch):
    """Regression (found by permlint's passthrough audit): the service's
    campaign waves must run under the solver's configured backend -- a
    pallas-configured service used to silently drop the kwarg and run
    jnp wave bodies."""
    from repro.core import distributed
    from repro.serve.loop import CampaignSpec

    captured = {}

    def fake_run_campaign(A, mesh, **kw):
        captured.update(kw)
        return 1.0, None

    monkeypatch.setattr(distributed, "run_campaign", fake_run_campaign)
    rng = np.random.default_rng(0)
    for solver_backend, expect in (("pallas", "pallas"), ("jnp", "jnp"),
                                   ("distributed", "jnp")):
        svc = PermanentService(
            SolverConfig(backend=solver_backend),
            ServiceConfig(max_batch=2, log_every_s=float("inf")),
            campaign=CampaignSpec(matrix=mk(rng, 8), waves=1),
            clock=FakeClock(), log=None)
        captured.clear()
        svc._advance_campaign(1)
        assert captured["backend"] == expect, solver_backend
        assert captured["precision"] == svc.solver.config.precision
