"""Campaign jobs on the normal path, as a deployment runs them back to
back: ``PermanentSolver.plan`` + ``execute`` routed to the step-space
campaign by the planner's threshold, a checkpoint after every wave under
one configured path, which the next job starts afresh over once the
job before has finished.

In-process tests run on the one device the test process keeps.  The
four-device tests share one subprocess (``XLA_FLAGS`` must be set before
JAX starts) that runs every job and prints its values and observations
as JSON; the tests compare them with ``core/oracle.py`` and the
benchmark's plain reference (``bench/reference.py``).
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from repro.core import distributed, oracle, resume
from repro.core.planner import ROUTE_CAMPAIGN, SolverConfig
from repro.core.solver import PermanentSolver
from repro.utils import spans

ROOT = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(ROOT, "src")
sys.path.insert(0, os.path.join(ROOT, "bench"))

import reference  # noqa: E402


def _cfg(**kw):
    base = dict(precision="dq_acc", campaign_threshold=1.0,
                campaign_slices=8, campaign_lanes=8)
    base.update(kw)
    return SolverConfig(**base)


def _job(solver, A):
    plan = solver.plan(A)
    assert [leaf.route for leaf in plan.leaves] == [ROUTE_CAMPAIGN]
    return solver.execute(plan)


def _uniform(seed, n):
    return np.random.default_rng(seed).random((n, n))


# ---------------------------------------------------------------------------
# one device, in process
# ---------------------------------------------------------------------------

def test_back_to_back_jobs_share_one_checkpoint_path(tmp_path):
    ckpt = str(tmp_path / "job.npz")
    solver = PermanentSolver(_cfg(campaign_checkpoint=ckpt))
    clean = PermanentSolver(_cfg())
    for seed in (1, 2, 3):
        A = _uniform(seed, 10)
        got = _job(solver, A)
        st = resume.JobState.load(ckpt)          # kept, finished, this job's
        leaf, = solver.plan(A).leaves
        assert st.finished
        assert st.fingerprint == resume.matrix_fingerprint(leaf.matrix)
        assert np.float64(got) == np.float64(_job(clean, A))


def test_paused_job_keeps_its_checkpoint_and_mismatch_still_raises(
        tmp_path):
    ckpt = str(tmp_path / "job.npz")
    A, B = _uniform(4, 10), _uniform(5, 10)
    paused = PermanentSolver(_cfg(campaign_checkpoint=ckpt,
                                  campaign_max_waves=2))
    with pytest.raises(distributed.CampaignPaused):
        _job(paused, A)
    st = resume.JobState.load(ckpt)
    assert not st.finished and 0 < st.fraction_done() < 1
    # an unfinished job's checkpoint is never taken for another job's
    other = PermanentSolver(_cfg(campaign_checkpoint=ckpt))
    with pytest.raises(ValueError, match="different matrix"):
        _job(other, B)
    with pytest.raises(ValueError, match="config mismatch"):
        _job(PermanentSolver(_cfg(campaign_checkpoint=ckpt,
                                  precision="kahan")), A)
    assert resume.JobState.load(ckpt).fraction_done() == st.fraction_done()
    # the job resumes, and its checkpoint stays, finished
    got = _job(other, A)
    assert resume.JobState.load(ckpt).finished
    assert np.float64(got) == np.float64(_job(PermanentSolver(_cfg()), A))
    assert np.float64(_job(other, B)) == \
        np.float64(_job(PermanentSolver(_cfg()), B))


def test_finished_checkpoint_of_another_job_is_replaced(tmp_path):
    """A finished job leaves its checkpoint; the same job reads its value
    back from it, and the next job starts afresh over it."""
    ckpt = str(tmp_path / "job.npz")
    A, B = _uniform(6, 9), _uniform(7, 9)
    plan = PermanentSolver(_cfg()).plan(A)
    spec = plan.leaves[0].campaign
    mesh = Mesh(np.array(jax.devices()[:1]), ("step",))
    args = dict(total_slices=spec.total_slices,
                chunks_per_slice=spec.chunks_per_slice,
                chunk_size=spec.chunk_size, checkpoint_path=ckpt)
    value_a, st = distributed.run_campaign(A, mesh, **args)
    assert st.finished and os.path.exists(ckpt)
    again, _ = distributed.run_campaign(A, mesh, **args)
    assert np.float64(again) == np.float64(value_a)
    value_b, st_b = distributed.run_campaign(B, mesh, **args)
    assert st_b.fingerprint == resume.matrix_fingerprint(B)
    clean, _ = distributed.run_campaign(B, mesh, **{**args,
                                                    "checkpoint_path": None})
    assert np.float64(value_b) == np.float64(clean)


def test_rerun_after_a_failed_leaf_reloads_the_finished_leaf(
        tmp_path, monkeypatch):
    """A plan of two campaign leaves, each under its own suffixed path:
    the second fails after the first has finished, and the rerun reads
    the first back from its checkpoint and runs only the second."""
    ckpt = str(tmp_path / "job.npz")
    cfg = _cfg(campaign_checkpoint=ckpt)
    mats = [_uniform(14, 9), _uniform(15, 9)]
    real, calls = distributed.slice_sums_on_mesh, []
    total_slices = 8                    # one device: a wave per slice

    def second_leaf_fails(*a, **kw):
        calls.append(1)
        if len(calls) > total_slices:
            raise RuntimeError("lost node")
        return real(*a, **kw)
    monkeypatch.setattr(distributed, "slice_sums_on_mesh", second_leaf_fails)
    failed = PermanentSolver(cfg)
    plan = failed.plan_batch(mats)
    assert [leaf.route for leaf in plan.leaves] == [ROUTE_CAMPAIGN] * 2
    with pytest.raises(RuntimeError, match="lost node"):
        failed.execute(plan)
    kept = [resume.JobState.load(str(f)) for f in tmp_path.iterdir()]
    assert [st.finished for st in kept] == [True]    # the first leaf's
    monkeypatch.setattr(distributed, "slice_sums_on_mesh", real)

    rerun = PermanentSolver(cfg)
    got = rerun.execute(rerun.plan_batch(mats))
    assert rerun.stats()["campaign"]["waves"] == total_slices
    clean = PermanentSolver(_cfg())
    want = clean.execute(clean.plan_batch(mats))
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


def test_wave_spans_and_counters(tmp_path):
    ckpt = str(tmp_path / "job.npz")
    solver = PermanentSolver(_cfg(campaign_checkpoint=ckpt))
    jobs = 2
    t0 = time.perf_counter()
    for seed in range(jobs):
        _job(solver, _uniform(10 + seed, 10))
    got = spans.recent(since=t0)
    waves = [s for s in got if s.name == "campaign.wave"]
    total_slices = 8                    # one device: a wave per slice
    assert len(waves) == jobs * total_slices
    assert [w.attrs["wave"] for w in waves] == list(range(total_slices)) * 2
    assert all(w.attrs["slices"] == 1 and w.attrs["chips"] == 1
               for w in waves)
    ids = {w.id for w in waves}
    for name in ("engine.launch", "engine.wait", "campaign.checkpoint"):
        kids = [s for s in got if s.name == name]
        assert sorted(s.parent for s in kids) == sorted(ids), name
    saves = [s for s in got if s.name == "campaign.checkpoint"]
    assert all(s.attrs["bytes"] > 0 for s in saves)
    reduces = [s for s in got if s.name == "campaign.reduce"]
    dispatches = [s for s in got if s.name == "executor.dispatch"]
    assert len(reduces) == len(dispatches) == jobs
    assert sorted(r.parent for r in reduces) == \
        sorted(d.id for d in dispatches)
    assert solver.stats()["campaign"] == {
        "waves": jobs * total_slices, "slices": jobs * total_slices,
        "retried_waves": 0,
        "checkpoint_bytes": sum(s.attrs["bytes"] for s in saves)}


def test_retried_wave_is_counted(monkeypatch):
    real, failed = distributed.slice_sums_on_mesh, []

    def flaky(*a, **kw):
        if not failed:
            failed.append(1)
            raise RuntimeError("preempted")
        return real(*a, **kw)
    monkeypatch.setattr(distributed, "slice_sums_on_mesh", flaky)
    solver = PermanentSolver(_cfg())
    A = _uniform(12, 9)
    got = _job(solver, A)
    c = solver.stats()["campaign"]
    assert c["retried_waves"] == 1 and c["slices"] == 8
    assert c["checkpoint_bytes"] == 0            # no checkpoint configured
    monkeypatch.setattr(distributed, "slice_sums_on_mesh", real)
    assert np.float64(got) == np.float64(_job(PermanentSolver(_cfg()), A))


def test_stats_of_a_solver_without_campaigns():
    solver = PermanentSolver(SolverConfig())
    solver.execute(solver.plan_batch([_uniform(13, 6)] * 2))
    assert solver.stats()["campaign"] == {
        "waves": 0, "slices": 0, "retried_waves": 0, "checkpoint_bytes": 0}


# ---------------------------------------------------------------------------
# four devices, one subprocess
# ---------------------------------------------------------------------------

SCRIPT = textwrap.dedent("""
    import json, os, shutil, sys, tempfile
    import numpy as np
    import jax
    jax.config.update("jax_enable_x64", True)
    from jax.sharding import Mesh
    from repro.core import distributed, resume
    from repro.core.planner import SolverConfig
    from repro.core.solver import PermanentSolver

    devs = jax.devices()
    mesh = lambda d: Mesh(np.array(devs[:d]), ("step",))
    cfg = SolverConfig(precision="dq_acc", campaign_threshold=1.0,
                       campaign_slices=16, campaign_lanes=8)
    out = {"devices": len(devs), "values": {}, "routes": {}}

    def finished_job(path, solver, A):
        st = resume.JobState.load(path)
        leaf, = solver.plan(A).leaves
        return (st.finished
                and st.fingerprint == resume.matrix_fingerprint(leaf.matrix))

    def job(solver, A):
        plan = solver.plan(A)
        out["routes"].setdefault(str(A.shape[0]),
                                 [l.route for l in plan.leaves])
        return float(solver.execute(plan))

    four = PermanentSolver(cfg, distributed_ctx=mesh(4))
    for n in (9, 10, 11, 12):
        A = np.random.default_rng(n).random((n, n))
        out["values"][f"uniform{n}"] = job(four, A).hex()
        out["values"][f"ones{n}"] = job(four, np.ones((n, n))).hex()
    out["stats"] = four.stats()["campaign"]

    d = tempfile.mkdtemp()
    ckpt = os.path.join(d, "job.npz")
    A = np.random.default_rng(21).random((12, 12))
    B = np.random.default_rng(22).random((12, 12))
    clean = PermanentSolver(cfg, distributed_ctx=mesh(4))
    out["clean"] = [job(clean, A).hex(), job(clean, B).hex()]
    shared = PermanentSolver(cfg.replace(campaign_checkpoint=ckpt),
                             distributed_ctx=mesh(4))
    out["back_to_back"] = [job(shared, A).hex(), job(shared, B).hex()]
    out["left_after_back_to_back"] = finished_job(ckpt, shared, B)

    paused = PermanentSolver(cfg.replace(campaign_checkpoint=ckpt,
                                         campaign_max_waves=1),
                             distributed_ctx=mesh(4))
    try:
        job(paused, A)
        out["paused"] = None
    except distributed.CampaignPaused:
        out["paused"] = resume.JobState.load(ckpt).fraction_done()
    shutil.copy(ckpt, ckpt + ".copy.npz")
    out["resumed"] = {}
    for dcount in (2, 4):
        shutil.copy(ckpt + ".copy.npz", ckpt)
        r = PermanentSolver(cfg.replace(campaign_checkpoint=ckpt),
                            distributed_ctx=mesh(dcount))
        out["resumed"][str(dcount)] = job(r, A).hex()
    out["left_after_resume"] = finished_job(ckpt, r, A)
    shutil.rmtree(d)
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    line, = [s for s in r.stdout.splitlines() if s.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


def _value(four, key):
    return float.fromhex(four["values"][key])


@pytest.mark.parametrize("n", [9, 10, 11, 12])
def test_four_device_campaign_matches_oracle_and_reference(four, n):
    assert four["devices"] == 4
    assert four["routes"][str(n)] == [ROUTE_CAMPAIGN]
    A = np.random.default_rng(n).random((n, n))
    got = _value(four, f"uniform{n}")
    assert abs(got - oracle.perm_ryser_exact(A)) <= 1e-12 * abs(got)
    assert abs(got - reference.permanent(A)) <= 1e-12 * abs(got)


@pytest.mark.parametrize("n", [9, 10, 11, 12])
def test_four_device_campaign_all_ones(four, n):
    got = _value(four, f"ones{n}")
    assert got == oracle.all_ones_permanent(n)
    assert abs(got - reference.permanent(np.ones((n, n)))) <= 1e-12 * got


def test_four_device_counters(four):
    # 16 slices over four devices: 4 waves of 4 slices a job, 8 jobs
    assert four["stats"] == {"waves": 32, "slices": 128, "retried_waves": 0,
                             "checkpoint_bytes": 0}


def test_four_device_back_to_back_jobs_complete(four):
    assert four["back_to_back"] == four["clean"]
    assert four["left_after_back_to_back"] is True


def test_four_device_pause_resumes_bitwise_at_two_and_four(four):
    assert 0 < four["paused"] < 1
    assert four["resumed"] == {"2": four["clean"][0], "4": four["clean"][0]}
    assert four["left_after_resume"] is True
