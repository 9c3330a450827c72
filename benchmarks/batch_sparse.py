"""Sparse-bucket throughput: the SpaRyser kernel and mesh vs the jnp path.

ISSUE 5's tentpole gate: the sparse route no longer downgrades to the jnp
engine on any backend.  This benchmark measures perms/sec of a same-size
REAL sparse bucket (padded-CCS layout) executed

* **jnp**         -- the batched jnp SpaRyser engine on one device
  (``sparyser.perm_sparyser_batched``);
* **pallas**      -- the padded-CCS (batch, block)-grid SpaRyser kernel
  (``ops.permanent_pallas_sparse_batched``, interpret mode on CPU);
* **dist**        -- the same bucket batch-axis-sharded over a forced
  8-device host CPU mesh through the jnp engine's trace
  (``distributed.sparse_batch_permanents_on_mesh``);
* **mesh_pallas** -- the mesh path with ``backend="pallas"``: the SpaRyser
  kernel launched per device on its local sub-stack.

and asserts, per density of the 0.1 / 0.3 / 0.5 sweep,

* the sharded (jnp-body) values are BIT-IDENTICAL to the jnp ones (the
  ``distributed_batch`` contract), and
* the pallas and mesh_pallas values agree with jnp to 1e-9 relative (the
  kernel carries its own cache identity -- bitwise is jnp<->distributed's
  contract, not the kernel's),

re-checked for every precision mode at the gated density, plus a routing
probe: a sparse-routed bucket planned under ``backend="pallas"`` (and
under ``distributed`` with a mesh) must dispatch natively -- no
``pallas->jnp`` / ``distributed->jnp`` downgrade tag.

Acceptance gate (ISSUE 5): BOTH the pallas kernel and the sharded bucket
run at >= 0.9x the single-device jnp sparse path at the gated (last)
density.  Measured on an 8-device host mesh: pallas 5-15x, dist 1.3-3x.

Because XLA_FLAGS must be set before jax initializes, the measurement
runs in a subprocess; the parent parses its CSV.

    PYTHONPATH=src python -m benchmarks.batch_sparse [--check]
    PYTHONPATH=src python -m benchmarks.run --only batch_sparse --check
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

SPEEDUP_GATE = 0.9
DEVICES = 8
N = 12
BUCKET = 64
# pattern densities to measure; the LAST one is the gated row
DENSITIES = (0.1, 0.3, 0.5)

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_WORKER = r"""
import time

import jax
jax.config.update("jax_enable_x64", True)
import numpy as np

from repro.core import distributed, sparyser
from repro.core.solver import PermanentSolver, SolverConfig
from repro.kernels import ops
from repro.launch.mesh import make_batch_mesh

n = {n}
B = {bucket}
densities = {densities!r}
repeats = {repeats}
precisions = ("dd", "dq_fast", "dq_acc", "qq", "kahan")
mesh = make_batch_mesh({devices})
rng = np.random.default_rng({seed})


def sparse_bucket(d, route_sparse=False):
    sps = []
    while len(sps) < B:
        mask = (rng.uniform(0, 1, (n, n)) < d) | np.eye(n, dtype=bool)
        if route_sparse and mask.sum() / (n * n) >= 0.29:
            continue                 # keep every leaf under DENSITY_SWITCH
        sps.append(sparyser.SparseMatrix.from_dense(
            rng.uniform(0.5, 1.5, (n, n)) * mask))
    return sps


ENGINES = dict(
    jnp=lambda sps, prec: np.asarray(
        sparyser.perm_sparyser_batched(sps, precision=prec)),
    pallas=lambda sps, prec: np.asarray(
        ops.permanent_pallas_sparse_batched(sps, precision=prec)),
    dist=lambda sps, prec: distributed.sparse_batch_permanents_on_mesh(
        sps, mesh, precision=prec),
    mesh_pallas=lambda sps, prec:
        distributed.sparse_batch_permanents_on_mesh(
            sps, mesh, precision=prec, backend="pallas"),
)


def best_time(fn, sps):
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(sps, "dq_acc")
        best = min(best, time.perf_counter() - t0)
    return best


def rel_close(a, b, tol=1e-9):
    return bool(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-300)) < tol)


for d in densities:
    sps = sparse_bucket(d)
    vals = {{name: fn(sps, "dq_acc") for name, fn in ENGINES.items()}}
    secs = {{name: best_time(fn, sps) for name, fn in ENGINES.items()}}
    print(f"ROW,kind=perf,n={{n}},bucket={{B}},density={{d}},"
          f"devices={{{devices}}},"
          f"jnp_perms_per_s={{B / secs['jnp']:.0f}},"
          f"pallas_perms_per_s={{B / secs['pallas']:.0f}},"
          f"dist_perms_per_s={{B / secs['dist']:.0f}},"
          f"mesh_pallas_perms_per_s={{B / secs['mesh_pallas']:.0f}},"
          f"pallas_speedup={{secs['jnp'] / secs['pallas']:.2f}},"
          f"dist_speedup={{secs['jnp'] / secs['dist']:.2f}},"
          f"mesh_pallas_speedup={{secs['jnp'] / secs['mesh_pallas']:.2f}},"
          f"pallas_close={{int(rel_close(vals['pallas'], vals['jnp']))}},"
          f"dist_bitwise={{int(np.array_equal(vals['dist'], vals['jnp']))}},"
          f"mesh_pallas_close="
          f"{{int(rel_close(vals['mesh_pallas'], vals['jnp']))}}")

# identity per precision mode at the gated density (fresh bucket)
sps = sparse_bucket(densities[-1])
for prec in precisions:
    vj = ENGINES["jnp"](sps, prec)
    vp = ENGINES["pallas"](sps, prec)
    vd = ENGINES["dist"](sps, prec)
    print(f"ROW,kind=prec,precision={{prec}},density={{densities[-1]}},"
          f"pallas_close={{int(rel_close(vp, vj))}},"
          f"dist_bitwise={{int(np.array_equal(vd, vj))}}")

# routing probe: a sparse-routed bucket dispatches natively on the kernel
# and on the mesh -- the pallas->jnp sparse downgrade tag is gone
mats = [sp.to_dense() for sp in sparse_bucket(0.1, route_sparse=True)]
flags = []
for backend, ctx in (("pallas", None), ("distributed", mesh)):
    s = PermanentSolver(SolverConfig(backend=backend, cache=False,
                                     preprocess=False),
                        distributed_ctx=ctx)
    _, reports = s.execute(s.plan_batch(mats), return_report=True)
    tags = [t for r in reports for t in r.dispatch]
    native = (not s.stats()["downgrades"]
              and all(t.startswith("sparse_batch") and "->" not in t
                      for t in tags))
    flags.append(f"{{backend}}_native={{int(native)}}")
print("ROW,kind=route," + ",".join(flags))
"""


def run(densities=DENSITIES, devices: int = DEVICES, repeats: int = 5,
        seed: int = 0):
    """Measure in a forced-multi-device subprocess; returns CSV rows."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # a CPU gate: never reach for a chip the parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _SRC + os.pathsep * bool(env.get("PYTHONPATH")) \
        + env.get("PYTHONPATH", "")
    code = _WORKER.format(n=N, bucket=BUCKET, densities=tuple(densities),
                          repeats=repeats, devices=devices, seed=seed)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=1200)
    if r.returncode != 0:
        raise RuntimeError(f"batch_sparse worker failed:\n"
                           f"{r.stdout[-2000:]}{r.stderr[-3000:]}")
    rows = []
    for line in r.stdout.splitlines():
        if not line.startswith("ROW,"):
            continue
        rows.append(dict(kv.split("=", 1) for kv in line[4:].split(",")))
    want = len(tuple(densities)) + 5 + 1   # perf rows + precisions + route
    if len(rows) != want:
        raise RuntimeError(f"expected {want} rows, parsed {len(rows)}:\n"
                           f"{r.stdout[-2000:]}")
    return rows


def check(rows) -> bool:
    """ISSUE-5 gate: pallas AND mesh-sharded sparse buckets >= 0.9x the
    jnp sparse path at the gated density; dist bit-identical and the
    kernels 1e-9-close on every row (all precision modes); no sparse
    downgrade tags on native routes."""
    ok = True
    for row in rows:
        kind = row.get("kind")
        if kind in ("perf", "prec"):
            where = f"density={row.get('density')}" + (
                f" precision={row['precision']}" if kind == "prec" else "")
            if row.get("pallas_close") != "1":
                print(f"# batch_sparse: pallas NOT 1e-9-close ({where})"
                      f" -- FAIL")
                ok = False
            if row.get("dist_bitwise") != "1":
                print(f"# batch_sparse: sharded values NOT bit-identical "
                      f"({where}) -- FAIL")
                ok = False
            if row.get("mesh_pallas_close", "1") != "1":
                print(f"# batch_sparse: mesh pallas NOT 1e-9-close "
                      f"({where}) -- FAIL")
                ok = False
        if kind == "route":
            for key, val in row.items():
                if key.endswith("_native") and val != "1":
                    print(f"# batch_sparse: sparse bucket downgraded under "
                          f"{key[:-7]} -- FAIL")
                    ok = False
    gated = [r for r in rows if r.get("kind") == "perf"][-1]
    for which in ("pallas", "dist"):
        speedup = float(gated[f"{which}_speedup"])
        gate_ok = speedup >= SPEEDUP_GATE
        status = "OK" if gate_ok else "FAIL"
        print(f"# batch_sparse gate [{which}] (n={gated['n']} "
              f"bucket={gated['bucket']} density={gated['density']} "
              f"x{gated['devices']} devices): {speedup:.2f}x vs required "
              f"{SPEEDUP_GATE:.1f}x -- {status}")
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
        print("batch_sparse," + ",".join(f"{k}={v}" for k, v in r.items()))
    if args.check and not check(rows):
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
