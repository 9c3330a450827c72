"""Soak the always-on permanent service under open-loop Poisson load.

ISSUE 7's acceptance gate: drive ``repro.serve.PermanentService`` with
seeded Poisson arrivals (n=12 dense requests over a forced 8-device host
mesh), twice, in two cold subprocesses sharing one persistent XLA
compilation-cache directory, and assert

* **SLO**: p99 admission->result latency under the gate;
* **typed shedding**: sheds happen (a slice of requests carries an
  already-expired deadline) and every one carries a typed reason --
  nothing is dropped silently;
* **metrics consistency**: admitted == completed + shed + pending with
  pending 0 after drain, the latency histogram counts every completion,
  and cache-hit + queue-depth metrics are nonzero;
* **correctness**: sampled service values bit-match a fresh scalar
  solver on the same matrices;
* **no cold-start retrace storm**: run 1 populates the compilation
  cache during its warm-up pass (persistent misses > 0); run 2 -- a cold
  process, warm disk cache -- warms up with ZERO persistent misses, and
  in both runs the first dispatched bucket compiles nothing new;
* **tuned cold start** (ISSUE 9): a second, pallas-backend service in
  the same worker is configured with ``SolverConfig.tuning_table``
  pointing at a persisted table whose dense/n winner is a NON-default
  geometry.  Its warm-up plans through the table, so the warmed bucket
  programs ARE the tuned ones: run 2's tuned warm-up loads everything
  from disk (zero persistent misses), the tuned first bucket compiles
  nothing in either run, the dispatched leaves carry the tuned geometry
  tag, and the value still matches a fresh scalar solver.

Because ``XLA_FLAGS`` must be set before jax initializes (and because
"cold process" is the point), measurement runs in subprocesses; the
parent parses their CSV.

    PYTHONPATH=src python -m benchmarks.serve_soak [--check] [--fast]
    PYTHONPATH=src python -m benchmarks.run --only soak --check
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import tempfile

P99_GATE_S = 10.0      # host-CPU CI boxes are slow + shared; real SLOs
                       # are config, this gate just proves the loop keeps up
DEVICES = 8
N = 12
MAX_BATCH = 8
REQUESTS = 64
RATE_HZ = 50.0
EXPIRE_EVERY = 8       # every 8th request arrives already expired
# The synthetic table's dense/n winner: deliberately NOT the kernel
# default (128x64x16), so a tuned pickup is observable; validated
# against the PL007 auditor before the table is written.
TUNED_GEOMETRY = (64, 32, 8)

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_WORKER = r"""
import sys

import jax
jax.config.update("jax_enable_x64", True)
import numpy as np

from repro.core.solver import PermanentSolver, SolverConfig
from repro.launch.mesh import make_batch_mesh
from repro.serve import (PermanentService, ServiceConfig, compile_stats,
                         run_soak)

n = {n}
mesh = make_batch_mesh({devices})
svc = PermanentService(
    SolverConfig(backend="distributed", precision="dq_acc"),
    ServiceConfig(max_batch={max_batch}, quantize_buckets=True,
                  compile_cache_dir={cache_dir!r}, warmup_ns=(n,),
                  log_every_s=2.0),
    distributed_ctx=mesh, log=lambda s: print(s, file=sys.stderr))
warm = svc.warmup_report["compile"]

# first bucket after warm-up: must compile nothing new
s0 = compile_stats()
t_first = svc.submit(np.random.default_rng(99).uniform(-1, 1, (n, n)),
                     deadline_s=None)
svc.step()
s1 = compile_stats()
first_misses = s1["persistent_misses"] - s0["persistent_misses"]
assert t_first.done

out = run_soak(svc, requests={requests}, rate_hz={rate_hz}, n=n,
               repeat_pool=6, seed={seed}, expire_every={expire_every})
snap = out["snapshot"]
req = snap["requests"]

# sampled values vs a fresh scalar solver (bitwise: batch-shape
# independence + the distributed_batch bit-identity contract)
ref = PermanentSolver(SolverConfig(backend="jnp", cache=False))
done = [t for t in out["tickets"] if t.done]
values_ok = all(t.result() == ref.execute(ref.plan(t.matrix))
                for t in done[:3] + done[-3:])

lat = snap["latency_s"]["overall"]
consistent = (req["admitted"] == req["completed"] + req["shed_total"]
              + req["pending"]
              and req["pending"] == 0
              and lat["count"] == req["completed"]
              and all(k in ("queue_full", "cost_budget",
                            "deadline_expired", "shutdown")
                      for k in req["shed"]))
cache = snap["solver"]["cache"]

# tuned cold start: a pallas service whose warm-up resolves the
# persisted tuning table -- the warmed programs are the tuned ones, so
# with a warm disk cache the tuned first bucket compiles nothing
tuned = PermanentService(
    SolverConfig(backend="pallas", precision="dq_acc", preprocess=False,
                 tuning_table={table!r}),
    ServiceConfig(max_batch={max_batch}, quantize_buckets=True,
                  compile_cache_dir={cache_dir!r}, warmup_ns=(n,),
                  log_every_s=2.0),
    log=lambda s: print(s, file=sys.stderr))
tuned_warm = tuned.warmup_report["compile"]
tmat = np.random.default_rng(5).uniform(-1, 1, (n, n))
tleaf = tuned.solver.plan_batch([tmat]).leaves[0]
tuned_tag = tleaf.geometry.tag() if tleaf.geometry is not None else "-"
s0 = compile_stats()
t_tuned = tuned.submit(tmat, deadline_s=None)
tuned.step()
s1 = compile_stats()
tuned_first = s1["persistent_misses"] - s0["persistent_misses"]
tuned_value_ok = t_tuned.done and bool(np.isclose(
    t_tuned.result(), ref.execute(ref.plan(tmat)), rtol=1e-9))

print(f"ROW,devices={devices},n={{n}},requests={{req['admitted']}},"
      f"completed={{req['completed']}},shed={{req['shed_total']}},"
      f"shed_deadline={{req['shed'].get('deadline_expired', 0)}},"
      f"p50_ms={{lat['p50'] * 1e3:.0f}},p99_ms={{lat['p99'] * 1e3:.0f}},"
      f"dispatches={{snap['dispatches']}},"
      f"occupancy={{snap['bucket_occupancy']['mean']:.2f}},"
      f"depth_samples={{snap['queue_depth']['count']}},"
      f"depth_max={{snap['queue_depth']['max']:.0f}},"
      f"cache_hits={{cache['hits']}},cache_hit_rate={{cache['hit_rate']:.2f}},"
      f"warm_misses={{warm['persistent_misses']}},"
      f"warm_hits={{warm['persistent_hits']}},"
      f"first_misses={{first_misses}},"
      f"tuned_geometry={{tuned_tag}},"
      f"tuned_warm_misses={{tuned_warm['persistent_misses']}},"
      f"tuned_warm_hits={{tuned_warm['persistent_hits']}},"
      f"tuned_first_misses={{tuned_first}},"
      f"tuned_value_ok={{int(tuned_value_ok)}},"
      f"consistent={{int(consistent)}},values_ok={{int(values_ok)}}")
"""


def _write_tuning_table(path: str, n: int) -> None:
    """Persist a minimal, VALID table whose dense/n winner is the
    non-default ``TUNED_GEOMETRY`` (wildcard device kind, so the CPU CI
    host resolves it).  Timings are placeholders -- this table exercises
    the pickup path, not the tuner."""
    from repro.core.stepspace import Geometry
    from repro.tune.table import TableEntry, TuningTable

    table = TuningTable()
    table.put(TableEntry(
        route="dense", n=n, density_bucket="1.00", dtype="<f8",
        precision="dq_acc", device_kind="any",
        geometry=Geometry(*TUNED_GEOMETRY),
        predicted_s=1.0, measured_s=1.0, default_s=1.0))
    bad = table.validate()
    if bad:
        raise RuntimeError(f"synthetic tuning entry violates PL007: {bad}")
    table.save(path)


def _run_once(cache_dir: str, *, devices: int, requests: int,
              rate_hz: float, seed: int, table: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # a CPU gate: never reach for a chip the parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _SRC + os.pathsep * bool(env.get("PYTHONPATH")) \
        + env.get("PYTHONPATH", "")
    code = _WORKER.format(n=N, devices=devices, max_batch=MAX_BATCH,
                          cache_dir=cache_dir, requests=requests,
                          rate_hz=rate_hz, seed=seed,
                          expire_every=EXPIRE_EVERY, table=table)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=1200)
    if r.returncode != 0:
        raise RuntimeError(f"serve_soak worker failed:\n"
                           f"{r.stdout[-2000:]}{r.stderr[-3000:]}")
    for line in r.stdout.splitlines():
        if line.startswith("ROW,"):
            return dict(kv.split("=", 1) for kv in line[4:].split(","))
    raise RuntimeError(f"serve_soak worker printed no ROW:\n"
                       f"{r.stdout[-2000:]}")


def run(devices: int = DEVICES, requests: int = REQUESTS,
        rate_hz: float = RATE_HZ, seed: int = 0, cache_dir: str | None = None):
    """Two cold subprocesses sharing one compilation-cache dir; returns
    [run1_row, run2_row] (run 1 cold cache, run 2 warm cache)."""
    ctx = tempfile.TemporaryDirectory() if cache_dir is None else None
    cdir = ctx.name if ctx else cache_dir
    try:
        table = os.path.join(cdir, "tuning_table.json")
        _write_tuning_table(table, N)
        rows = [_run_once(cdir, devices=devices, requests=requests,
                          rate_hz=rate_hz, seed=seed + i, table=table)
                for i in range(2)]
    finally:
        if ctx:
            ctx.cleanup()
    for i, row in enumerate(rows):
        row["run"] = str(i + 1)
    return rows


def check(rows, p99_gate_s: float = P99_GATE_S) -> bool:
    """The ISSUE-7 soak gate (see module docstring)."""
    ok = True

    def fail(msg):
        nonlocal ok
        print(f"# serve_soak: {msg} -- FAIL")
        ok = False

    for row in rows:
        tag = f"run {row['run']}"
        if row["consistent"] != "1":
            fail(f"{tag}: metrics inconsistent")
        if row["values_ok"] != "1":
            fail(f"{tag}: sampled values diverge from scalar solver")
        if int(row["shed"]) < 1 or int(row["shed_deadline"]) < 1:
            fail(f"{tag}: expected typed deadline sheds, got "
                 f"shed={row['shed']}")
        if int(row["cache_hits"]) < 1:
            fail(f"{tag}: result-cache hits = 0")
        if int(row["depth_samples"]) < 1:
            fail(f"{tag}: queue-depth histogram empty")
        p99 = float(row["p99_ms"]) / 1e3
        if p99 > p99_gate_s:
            fail(f"{tag}: p99 {p99:.2f}s over the {p99_gate_s:.1f}s gate")
        if int(row["first_misses"]) != 0:
            fail(f"{tag}: first bucket after warm-up recompiled "
                 f"({row['first_misses']} persistent misses)")
        want_tag = "x".join(str(v) for v in TUNED_GEOMETRY)
        if row["tuned_geometry"] != want_tag:
            fail(f"{tag}: tuned service planned geometry "
                 f"{row['tuned_geometry']}, table says {want_tag}")
        if int(row["tuned_first_misses"]) != 0:
            fail(f"{tag}: tuned first bucket recompiled "
                 f"({row['tuned_first_misses']} persistent misses)")
        if row["tuned_value_ok"] != "1":
            fail(f"{tag}: tuned service value diverged from scalar solver")
    if int(rows[0]["warm_misses"]) < 1:
        fail("run 1 warm-up compiled nothing (cache dir not cold?)")
    if int(rows[1]["warm_misses"]) != 0 or int(rows[1]["warm_hits"]) < 1:
        fail(f"run 2 (cold process, warm cache) recompiled during "
             f"warm-up: misses={rows[1]['warm_misses']} "
             f"hits={rows[1]['warm_hits']}")
    if int(rows[0]["tuned_warm_misses"]) < 1:
        fail("run 1 tuned warm-up compiled nothing -- the tuned bucket "
             "programs were already cached, gate is vacuous")
    if int(rows[1]["tuned_warm_misses"]) != 0 \
            or int(rows[1]["tuned_warm_hits"]) < 1:
        fail(f"run 2 tuned service recompiled during warm-up: "
             f"misses={rows[1]['tuned_warm_misses']} "
             f"hits={rows[1]['tuned_warm_hits']}")
    status = "OK" if ok else "FAIL"
    print(f"# serve_soak gate (n={rows[0]['n']} x{rows[0]['devices']} "
          f"devices, {rows[0]['requests']} reqs): run2 warm-up "
          f"misses={rows[1]['warm_misses']} hits={rows[1]['warm_hits']}, "
          f"tuned warm-up misses={rows[1]['tuned_warm_misses']} "
          f"hits={rows[1]['tuned_warm_hits']} "
          f"geometry={rows[1]['tuned_geometry']}, "
          f"p99={rows[0]['p99_ms']}/{rows[1]['p99_ms']}ms -- {status}")
    return ok


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=DEVICES)
    ap.add_argument("--requests", type=int, default=REQUESTS)
    ap.add_argument("--rate", type=float, default=RATE_HZ)
    ap.add_argument("--fast", action="store_true",
                    help="smoke sizing for CI")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent compile cache dir (default: fresh "
                         "tmpdir, removed afterwards)")
    ap.add_argument("--check", action="store_true",
                    help="enforce the ISSUE-7 soak gate")
    args = ap.parse_args()

    requests = 24 if args.fast else args.requests
    rows = run(devices=args.devices, requests=requests, rate_hz=args.rate,
               cache_dir=args.cache_dir)
    for r in rows:
        print("serve_soak," + ",".join(f"{k}={v}" for k, v in r.items()))
    if args.check and not check(rows):
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
