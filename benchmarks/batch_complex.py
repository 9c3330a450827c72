"""Complex-bucket throughput: pallas and mesh-sharded vs the jnp path.

ISSUE 4's tentpole gate: complex permanents (boson-sampling amplitudes)
are first-class on every backend built in PRs 1-3.  This benchmark
measures perms/sec of a same-size dense COMPLEX bucket executed

* **jnp**    -- the split-plane complex engine on one device
  (``ryser.batched_values_complex``);
* **pallas** -- the split re/im plane (batch, block)-grid kernel
  (``ryser_complex.ryser_pallas_call_complex_batched``, interpret mode on
  CPU);
* **dist**   -- the same bucket batch-axis-sharded over a forced
  8-device host CPU mesh, re/im planes through the jnp engine's trace.

and asserts

* the sharded values are BIT-IDENTICAL to the jnp ones (the
  ``distributed_batch`` contract, complex included), and
* the pallas values agree with jnp to 1e-9 relative (the kernel carries
  its own cache identity, like the real kernel -- bitwise identity is
  jnp<->distributed's contract, not pallas's).

Acceptance gate (ISSUE 4): BOTH the pallas and the sharded bucket run at
>= 0.9x the single-device jnp complex path at the gated (n, B).
Measured on an 8-device host mesh: dist 2.2-2.8x, pallas 1.6-2.6x.

Because XLA_FLAGS must be set before jax initializes, the measurement
runs in a subprocess; the parent parses its CSV.

    PYTHONPATH=src python -m benchmarks.batch_complex [--check]
    PYTHONPATH=src python -m benchmarks.run --only batch_complex --check
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

SPEEDUP_GATE = 0.9
DEVICES = 8
# (n, bucket) pairs to measure; the LAST row is the gated one
SIZES = ((10, 64), (12, 64))

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
    mats = [rng.normal(size=(n, n)) + 1j * rng.normal(size=(n, n))
            for _ in range(B)]
    solvers = dict(
        jnp=PermanentSolver(SolverConfig(
            backend="jnp", cache=False, preprocess=False)),
        pallas=PermanentSolver(SolverConfig(
            backend="pallas", cache=False, preprocess=False)),
        dist=PermanentSolver(SolverConfig(
            backend="distributed", cache=False, preprocess=False),
            distributed_ctx=mesh),
    )
    vals, secs = dict(), dict()
    for name, s in solvers.items():
        plan = s.plan_batch(mats)
        vals[name] = s.execute(plan)        # warm / compile
        assert not s.stats()["downgrades"], (name, s.stats()["downgrades"])
        secs[name] = best_time(s, plan)
    bitwise = bool(np.array_equal(vals["jnp"], vals["dist"]))
    pallas_ok = bool(np.allclose(vals["jnp"], vals["pallas"], rtol=1e-9))
    print(f"ROW,n={{n}},bucket={{B}},devices={{{devices}}},"
          f"jnp_perms_per_s={{B / secs['jnp']:.0f}},"
          f"pallas_perms_per_s={{B / secs['pallas']:.0f}},"
          f"dist_perms_per_s={{B / secs['dist']:.0f}},"
          f"pallas_speedup={{secs['jnp'] / secs['pallas']:.2f}},"
          f"dist_speedup={{secs['jnp'] / secs['dist']:.2f}},"
          f"dist_bitwise={{int(bitwise)}},pallas_close={{int(pallas_ok)}}")
"""


def run(sizes=SIZES, devices: int = DEVICES, repeats: int = 5,
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
        raise RuntimeError(f"batch_complex worker failed:\n"
                           f"{r.stdout[-2000:]}{r.stderr[-3000:]}")
    rows = []
    for line in r.stdout.splitlines():
        if not line.startswith("ROW,"):
            continue
        rows.append(dict(kv.split("=", 1) for kv in line[4:].split(",")))
    if len(rows) != len(tuple(sizes)):
        raise RuntimeError(f"expected {len(tuple(sizes))} rows, parsed "
                           f"{len(rows)}:\n{r.stdout[-2000:]}")
    return rows


def check(rows) -> bool:
    """ISSUE-4 gate: pallas AND sharded complex buckets >= 0.9x jnp at the
    gated size; dist bit-identical and pallas 1e-9-close everywhere."""
    ok = True
    for row in rows:
        if row["dist_bitwise"] != "1":
            print(f"# batch_complex: sharded values NOT bit-identical at "
                  f"n={row['n']} bucket={row['bucket']} -- FAIL")
            ok = False
        if row["pallas_close"] != "1":
            print(f"# batch_complex: pallas values NOT 1e-9-close at "
                  f"n={row['n']} bucket={row['bucket']} -- FAIL")
            ok = False
    gated = rows[-1]
    for which in ("pallas", "dist"):
        speedup = float(gated[f"{which}_speedup"])
        gate_ok = speedup >= SPEEDUP_GATE
        status = "OK" if gate_ok else "FAIL"
        print(f"# batch_complex gate [{which}] (n={gated['n']} "
              f"bucket={gated['bucket']} x{gated['devices']} devices): "
              f"{speedup:.2f}x vs required {SPEEDUP_GATE:.1f}x -- {status}")
        ok = ok and gate_ok
    return ok


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=DEVICES)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--check", action="store_true",
                    help="enforce the >= 0.9x + identity gates")
    args = ap.parse_args()

    rows = run(devices=args.devices, repeats=args.repeats)
    for r in rows:
        print("batch_complex," + ",".join(f"{k}={v}" for k, v in r.items()))
    if args.check and not check(rows):
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
