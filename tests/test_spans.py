"""The program's span recorder (``repro.utils.spans``): ring order,
parent ids, the fixed capacity, recorded spans, the profiler's host
plane, and the benchmark's readers of the spans."""

import glob
import os
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

import jax

jax.config.update("jax_enable_x64", True)

from repro.core.solver import PermanentSolver, SolverConfig
from repro.utils import spans

BENCH = os.path.join(os.path.dirname(__file__), "..", "bench")


@pytest.fixture
def ring(monkeypatch):
    """A fresh ring of 64 spans in place of the process's."""
    r = spans._Ring(64)
    monkeypatch.setattr(spans, "_RING", r)
    return r


def test_nesting_order_and_attrs(ring):
    with spans.span("a", k=1) as a:
        with spans.span("b") as b:
            b.attrs["late"] = 2
        with spans.span("c"):
            pass
    got = spans.recent()
    assert [s.name for s in got] == ["b", "c", "a"]      # as they closed
    sb, sc, sa = got
    assert sa.id == a.id and sb.id == b.id
    assert sa.parent is None and sb.parent == sc.parent == a.id
    assert sa.t0 <= sb.t0 <= sb.t1 <= sc.t0 <= sc.t1 <= sa.t1
    assert sa.attrs == {"k": 1} and sb.attrs == {"late": 2}
    assert b.seconds == sb.seconds == sb.t1 - sb.t0
    assert len({s.id for s in got}) == 3


def test_a_span_closes_when_its_body_raises(ring):
    with pytest.raises(ValueError):
        with spans.span("outer"):
            with spans.span("inner"):
                raise ValueError
    with spans.span("next") as nxt:
        pass
    assert [s.name for s in spans.recent()] == ["inner", "outer", "next"]
    assert nxt.parent is None


def test_threads_do_not_share_the_open_span(ring):
    seen = []

    def work():
        with spans.span("in_thread") as s:
            seen.append(s.parent)

    with spans.span("main"):
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=30)
    assert not t.is_alive() and seen == [None]


def test_record_takes_the_open_span_as_parent(ring):
    t0 = time.perf_counter()
    with spans.span("dispatch") as d:
        sid = spans.record("queue", t0 - 1.0, t0, ticket=7)
    q = next(s for s in spans.recent() if s.name == "queue")
    assert (q.t0, q.t1, q.id, q.parent) == (t0 - 1.0, t0, sid, d.id)
    assert q.attrs == {"ticket": 7} and q.seconds == 1.0


def test_fixed_capacity_and_dropped(monkeypatch):
    monkeypatch.setattr(spans, "_RING", spans._Ring(8))
    for i in range(11):
        spans.record(f"s{i}", float(i), float(i) + 0.5)
    got = spans.recent()
    assert [s.name for s in got] == [f"s{i}" for i in range(3, 11)]
    assert spans.dropped() == 3
    assert [s.name for s in spans.recent(since=9.0)] == ["s9", "s10"]


def test_process_ring_capacity():
    assert spans.CAPACITY >= 40_000          # a 51 s serve window
    assert spans._Ring(spans.CAPACITY).spans.maxlen == spans.CAPACITY


def _descendants(got, root, name):
    kids = {root.id}
    out = []
    for s in sorted(got, key=lambda s: s.t0):
        if s.parent in kids:
            kids.add(s.id)
            if s.name == name:
                out.append(s)
    return out


def test_one_engine_wait_per_device_program():
    solver = PermanentSolver(SolverConfig(backend="jnp", cache=False))
    rng = np.random.default_rng(5)
    mats = [rng.uniform(-1, 1, (6, 6)) for _ in range(3)] \
        + [rng.uniform(-1, 1, (5, 5)) + 1j * rng.uniform(-1, 1, (5, 5))
           for _ in range(2)]
    t0 = time.perf_counter()
    solver.execute(solver.plan_batch(mats))
    got = spans.recent(since=t0)
    dispatches = [s for s in got if s.name == "executor.dispatch"]
    assert sorted(s.attrs["key"] for s in dispatches) == \
        ["dense_batch(n=5,jnp)", "dense_batch(n=6,jnp)"]
    for d in dispatches:
        waits = _descendants(got, d, "engine.wait")
        launches = _descendants(got, d, "engine.launch")
        assert len(waits) == len(launches) == 1
        assert launches[0].t1 <= waits[0].t0
        assert waits[0].parent == d.id
    timings = solver.stats()["leaf_timings"]
    for d in dispatches:
        assert timings[d.attrs["key"]]["total_s"] == d.seconds
        assert timings[d.attrs["key"]]["leaves"] == d.attrs["leaves"]


def test_spans_land_on_the_profilers_host_plane(tmp_path):
    """Under the profiler, the spans of a plan_batch + execute are events
    of a host plane, nested as in the ring."""
    solver = PermanentSolver(SolverConfig(backend="jnp", cache=False))
    rng = np.random.default_rng(9)
    mats = [rng.uniform(-1, 1, (6, 6)) + 1j * rng.uniform(-1, 1, (6, 6))
            for _ in range(3)]
    solver.execute(solver.plan_batch(mats))            # compiled outside
    t0 = time.perf_counter()
    with jax.profiler.trace(str(tmp_path)):
        solver.execute(solver.plan_batch([0.5 * m for m in mats]))
    ring = {s.name: s for s in spans.recent(since=t0)}
    names = ("solver.execute", "executor.dispatch", "engine.wait")
    assert set(names) <= set(ring)
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    events = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    events[ev.name] = (ev.start_ns,
                                       ev.start_ns + ev.duration_ns)
    assert set(events) == set(names)
    for child, parent in zip(names[1:], names):
        assert ring[child].parent == ring[parent].id
        (c0, c1), (p0, p1) = events[child], events[parent]
        assert p0 <= c0 <= c1 <= p1


# -- the benchmark's readers of the spans ------------------------------------

def _reader(name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import harness
    return harness._reader(name)


def _run(start, seconds):
    return SimpleNamespace(window_start=start, seconds=seconds)


def _put(name, t0, t1, sid, parent=None, **attrs):
    spans._RING.append(spans.Span(name, t0, t1, sid, parent, attrs))


def test_readers_of_direct_calls(ring):
    """Two plan_batch + execute calls in the window: plan 3 ms, execute
    105 + k ms, whose one program waits 100 + k ms; the third call starts
    past the close."""
    for k, t in enumerate((10.0, 10.2, 11.5)):
        i = 10 * k
        _put("solver.plan", t, t + 0.003, i + 1)
        _put("engine.launch", t + 0.004, t + 0.005, i + 4, i + 3)
        _put("engine.wait", t + 0.005, t + 0.105 + 0.001 * k, i + 5, i + 3)
        _put("executor.dispatch", t + 0.0035, t + 0.106 + 0.001 * k, i + 3,
             i + 2)
        _put("solver.execute", t + 0.003, t + 0.108 + 0.001 * k, i + 2)
    run = _run(10.0, 1.0)
    assert _reader("device_wait_ms.batch")(run) == pytest.approx(100.5)
    assert _reader("host_ms.batch")(run) == pytest.approx(8.0)
    assert _reader("host_ms.batch")(_run(20.0, 1.0)) is None


def test_readers_of_dispatches(ring):
    """serve.dispatch rounds of 20 ms: 8 of 8 lanes served, then 3 of 4;
    each program waits 15 ms; tickets queued 4 ms and 6 ms."""
    for k, (t, served, lanes) in enumerate(((10.0, 8, 8), (10.1, 3, 4))):
        i = 10 * k
        _put("serve.pad", t, t + 0.001, i + 2, i + 1)
        _put("solver.plan", t + 0.001, t + 0.002, i + 3, i + 1)
        _put("engine.wait", t + 0.003, t + 0.018, i + 6, i + 5)
        _put("executor.dispatch", t + 0.0025, t + 0.0185, i + 5, i + 4)
        _put("solver.execute", t + 0.002, t + 0.019, i + 4, i + 1)
        _put("serve.dispatch", t, t + 0.020, i + 1, served=served,
             lanes=lanes, n=20, trigger="ready")
        _put("serve.queue", t - 0.004 - 0.002 * k, t, i + 7, ticket=k,
             dispatch=i + 1)
    run = _run(9.0, 2.0)
    assert _reader("serve.filler_share")(run) == pytest.approx(100 / 12)
    assert _reader("serve.queue_ms")(run) == pytest.approx(5.0)
    assert _reader("device_wait_ms.serve")(run) == pytest.approx(15.0)
    assert _reader("host_ms.serve")(run) == pytest.approx(5.0)


def test_readers_of_the_service():
    """A drained service, read by the readers through the process ring."""
    from repro.serve import PermanentService, ServiceConfig
    svc = PermanentService(SolverConfig(backend="jnp", cache=False),
                           ServiceConfig(max_batch=8,
                                         log_every_s=float("inf")),
                           log=None)
    rng = np.random.default_rng(13)
    t0 = time.perf_counter()
    for _ in range(11):
        svc.submit(rng.uniform(-1, 1, (5, 5)), deadline_s=None)
    svc.drain()
    run = _run(t0, time.perf_counter() - t0)
    # the service hands the solver only what it serves: 8, then 3
    assert _reader("serve.filler_share")(run) == 0.0
    assert _reader("serve.queue_ms")(run) > 0
    assert _reader("device_wait_ms.serve")(run) > 0
    got = spans.recent(since=t0)
    hosts = [1e3 * (d.seconds - sum(w.seconds for w in
                                    _descendants(got, d, "engine.wait")))
             for d in got if d.name == "serve.dispatch"]
    assert len(hosts) == 2
    assert _reader("host_ms.serve")(run) == pytest.approx(sum(hosts) / 2)


def test_readers_refuse_a_window_the_ring_lost(monkeypatch):
    monkeypatch.setattr(spans, "_RING", spans._Ring(8))
    for i in range(12):
        spans.record("engine.wait", 10.0 + i, 10.5 + i)
    assert spans.dropped() == 4
    assert _reader("device_wait_ms.batch")(_run(5.0, 30.0)) is None
    assert _reader("device_wait_ms.batch")(_run(14.0, 30.0)) == 500.0
