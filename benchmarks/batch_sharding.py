"""Batch-sharded bucket throughput vs the single-device jnp bucket path.

ISSUE 3's tentpole: ``permanent_batch`` buckets can shard their leading
axis over ``core.distributed``'s mesh (``distributed_batch`` strategy --
data parallelism over matrices, each device owning whole permanents).
This benchmark measures perms/sec of a same-size dense bucket executed

* **jnp**  -- one vmapped device program on one device;
* **dist** -- the same bucket batch-axis-sharded over a forced 8-device
  host CPU mesh (``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

and asserts the sharded values are BIT-IDENTICAL to the jnp ones (the
``distributed_batch`` contract).  Because XLA_FLAGS must be set before
jax initializes, the measurement runs in a subprocess; the parent parses
its CSV.

Acceptance gate (ISSUE 3): sharded throughput >= 0.9x the single-device
jnp path at the gated (n, B) -- parity-or-better; on real multi-chip
hardware (where devices do not share host cores) the expected regime is
>1x once buckets exceed the device count.

    PYTHONPATH=src python -m benchmarks.batch_sharding [--check]
    PYTHONPATH=src python -m benchmarks.run --only batch_sharding --check
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

SPEEDUP_GATE = 0.9
DEVICES = 8
# (n, bucket) pairs to measure; the LAST row is the gated one (buckets
# must exceed the device count, and per-matrix work must be large enough
# that one device's intra-op parallelism stops scaling -- n=14 shards at
# >2x even on a shared-core host mesh; tiny n=10 work is dispatch-bound)
SIZES = ((10, 64), (12, 64), (14, 64))

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_WORKER = r"""
import time

import jax
jax.config.update("jax_enable_x64", True)
import numpy as np

from repro.core.solver import PermanentSolver, SolverConfig
from repro.launch.mesh import make_batch_mesh

sizes = {sizes!r}
repeats = {repeats}
mesh = make_batch_mesh({devices})
rng = np.random.default_rng({seed})


def best_time(solver, plan):
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        solver.execute(plan)
        best = min(best, time.perf_counter() - t0)
    return best


for n, B in sizes:
    mats = [rng.uniform(-1, 1, (n, n)) for _ in range(B)]
    jnp_solver = PermanentSolver(SolverConfig(
        backend="jnp", cache=False, preprocess=False))
    dist_solver = PermanentSolver(SolverConfig(
        backend="distributed", cache=False, preprocess=False),
        distributed_ctx=mesh)
    jnp_plan = jnp_solver.plan_batch(mats)
    dist_plan = dist_solver.plan_batch(mats)
    vj = jnp_solver.execute(jnp_plan)       # warm / compile
    vd = dist_solver.execute(dist_plan)
    bitwise = bool(np.array_equal(vj, vd))
    stats = dist_solver.stats()
    assert not stats["downgrades"], stats["downgrades"]
    tj = best_time(jnp_solver, jnp_plan)
    td = best_time(dist_solver, dist_plan)
    print(f"ROW,n={{n}},bucket={{B}},devices={{{devices}}},"
          f"jnp_perms_per_s={{B / tj:.0f}},dist_perms_per_s={{B / td:.0f}},"
          f"speedup={{tj / td:.2f}},bitwise={{int(bitwise)}}")
"""


def run(sizes=SIZES, devices: int = DEVICES, repeats: int = 7,
        seed: int = 0):
    """Measure in a forced-multi-device subprocess; returns CSV rows."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # a CPU gate: never reach for a chip the parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _SRC + os.pathsep * bool(env.get("PYTHONPATH")) \
        + env.get("PYTHONPATH", "")
    code = _WORKER.format(sizes=tuple(sizes), repeats=repeats,
                          devices=devices, seed=seed)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=1200)
    if r.returncode != 0:
        raise RuntimeError(f"batch_sharding worker failed:\n"
                           f"{r.stdout[-2000:]}{r.stderr[-3000:]}")
    rows = []
    for line in r.stdout.splitlines():
        if not line.startswith("ROW,"):
            continue
        row = dict(kv.split("=", 1) for kv in line[4:].split(","))
        rows.append(row)
    if len(rows) != len(tuple(sizes)):
        raise RuntimeError(f"expected {len(tuple(sizes))} rows, parsed "
                           f"{len(rows)}:\n{r.stdout[-2000:]}")
    return rows


def check(rows) -> bool:
    """ISSUE-3 gate: sharded >= 0.9x jnp at the gated size, bit-identical
    everywhere."""
    ok = True
    for row in rows:
        if row["bitwise"] != "1":
            print(f"# batch_sharding: values NOT bit-identical at "
                  f"n={row['n']} bucket={row['bucket']} -- FAIL")
            ok = False
    gated = rows[-1]
    speedup = float(gated["speedup"])
    gate_ok = speedup >= SPEEDUP_GATE
    status = "OK" if gate_ok else "FAIL"
    print(f"# batch_sharding gate (n={gated['n']} bucket={gated['bucket']} "
          f"x{gated['devices']} devices): {speedup:.2f}x vs required "
          f"{SPEEDUP_GATE:.1f}x -- {status}")
    return ok and gate_ok


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=DEVICES)
    ap.add_argument("--repeats", type=int, default=7)
    ap.add_argument("--check", action="store_true",
                    help="enforce the >= 0.9x + bit-identity gate")
    args = ap.parse_args()

    rows = run(devices=args.devices, repeats=args.repeats)
    for r in rows:
        print("batch_sharding," + ",".join(f"{k}={v}" for k, v in r.items()))
    if args.check and not check(rows):
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
