"""Smoke test of the permanent solver on a TPU, at sizes users run.

    python chip_smoke.py            # one chip: the main path, five phases
    python chip_smoke.py --chips 4  # four chips: the multi-chip paths only

Everything runs in this one process; no child process touches JAX.  The
script drives the entry points a user calls -- ``PermanentSolver`` and
``PermanentService`` on the default jnp route at the default precision
``dq_acc`` -- and checks every value against a reference computed off
the chip: a closed form, the exact host oracle, or the same program on
the host CPU device.  Each phase prints its value, reference, relative
error, the bound it is held to, and its compile and run times.

TPU v5e has no float64 unit: XLA emulates it with float32 pieces, which
keeps float32's exponent range and is not correctly rounded, so the
compensated sums of ``core/precision.py`` are no longer error-free
there.  Each bound below is therefore stated twice: what the tests hold
the CPU to, and what the chip meets.

The last line of stdout is ``{"ok": true, "device": {...}}`` and is
printed only when every phase met its bound.  Without a TPU the script
exits non-zero before running anything.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# relative-error bounds: (what the tests hold dq_acc to on the CPU, what
# the chip meets).  On TPU v5e this script measured 1.1e-12 (all-ones,
# n=32; the CPU gives 5.7e-12), 3.2e-14 (dense, n=17), 1.4e-12 (service)
# and 7.1e-13 (complex campaign): the chip meets the CPU contract
# although its emulated float64 is not correctly rounded.
BOUNDS = {
    "allones": (1e-10, 1e-10),
    "dense": (1e-10, 1e-10),
    "service": (1e-10, 1e-10),
    "complex_campaign": (1e-10, 1e-10),
}

# phase sizes (n, and the service's request count and batch)
SIZES = {
    "allones": 32,          # paper Table 3 family; campaign route from n=31
    "dense": 17,            # the exact Fraction oracle takes ~15 s here
    "service": (20, 48, 16),
    "complex_campaign": 31,
    "pallas": 16,
    "campaign4": 30,
    "bucket4": (16, 64),
}


class Report:
    """Phase lines on stdout, and the failures that withhold ``ok``."""

    def __init__(self):
        self.failed: list[str] = []

    def line(self, phase: str, **kv) -> None:
        body = " ".join(f"{k}={_fmt(v)}" for k, v in kv.items())
        print(f"[{phase}] {body}", flush=True)

    def check(self, phase: str, value, ref, bound: tuple[float, float],
              **kv) -> None:
        err = _rel_err(value, ref)
        ok = bool(err <= bound[1])
        shown = {"value": value, "ref": ref} if np.ndim(value) == 0 \
            else {"values": np.size(value)}
        self.line(phase, **shown, rel_err=err, bound_chip=bound[1],
                  bound_cpu=bound[0], **kv, result="ok" if ok else "FAIL")
        if not ok:
            self.failed.append(phase)

    def equal(self, phase: str, a, b, **kv) -> None:
        same = bool(np.array_equal(np.asarray(a), np.asarray(b)))
        self.line(phase, bitwise_equal=same, **kv,
                  result="ok" if same else "FAIL")
        if not same:
            self.failed.append(phase)


def _fmt(v) -> str:
    if isinstance(v, float):
        return repr(v)
    if isinstance(v, complex):
        return f"({v.real!r}{v.imag:+})j"
    return str(v)


def _rel_err(value, ref) -> float:
    value = np.asarray(value, dtype=np.complex128)
    ref = np.asarray(ref, dtype=np.complex128)
    return float(np.max(np.abs(value - ref) / np.abs(ref)))


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _twice(fn):
    """(value, compile_s, run_s): the first call compiles and runs, the
    second only runs (compile_s is their difference)."""
    _, first = _timed(fn)
    value, run = _timed(fn)
    return value, max(first - run, 0.0), run


def _haar_unitary(m: int, rng) -> np.ndarray:
    z = (rng.standard_normal((m, m))
         + 1j * rng.standard_normal((m, m))) / math.sqrt(2)
    q, r = np.linalg.qr(z)
    d = np.diag(r)
    return q * (d / np.abs(d))


def _mesh(devices):
    from jax.sharding import Mesh
    return Mesh(np.array(devices), ("step",))


def _solver(ctx=None, **cfg):
    from repro.core.solver import PermanentSolver, SolverConfig
    # the result cache stays off so the second (timed) call computes again
    return PermanentSolver(SolverConfig(cache=False, **cfg),
                           distributed_ctx=ctx)


def phase_allones(rep: Report, tpu, cpu) -> None:
    from repro.core.oracle import all_ones_permanent
    n = SIZES["allones"]
    A = np.ones((n, n))
    ref = all_ones_permanent(n)
    solver = _solver(_mesh([tpu]))
    plan = solver.plan(A)
    routes = sorted({leaf.route for leaf in plan.leaves})
    value, comp, run = _twice(lambda: solver.execute(plan))
    rep.check("allones", value, ref, BOUNDS["allones"], n=n,
              route="+".join(routes), compile_s=comp, run_s=run)
    # the same program, same plan, on the host CPU device
    cpu_solver = _solver(_mesh([cpu]))
    cpu_value, cpu_s = _timed(lambda: cpu_solver.execute(plan))
    rep.line("allones_cpu", n=n, value=cpu_value, ref=ref,
             rel_err=_rel_err(cpu_value, ref), run_s=cpu_s,
             chip_rel_err=_rel_err(value, ref))


def phase_dense(rep: Report, tpu, cpu, rng) -> None:
    import jax
    from repro.core import ryser as R
    from repro.core.oracle import perm_ryser_exact
    n = SIZES["dense"]
    A = rng.uniform(-1.0, 1.0, (n, n))
    solver = _solver()
    plan = solver.plan(A)
    value, comp, run = _twice(lambda: solver.execute(plan))
    ref, ref_s = _timed(lambda: perm_ryser_exact(A))
    rep.check("dense", value, ref, BOUNDS["dense"], n=n,
              compile_s=comp, run_s=run, oracle_s=ref_s)
    # one jitted engine, placed on the chip and on the host CPU
    on_tpu = float(R.perm_ryser_chunked(jax.device_put(A, tpu)))
    on_cpu = float(R.perm_ryser_chunked(jax.device_put(A, cpu)))
    rep.line("dense_same_program", n=n, tpu=on_tpu, cpu=on_cpu,
             bitwise_equal=on_tpu == on_cpu,
             rel_diff=_rel_err(on_tpu, on_cpu))


def phase_service(rep: Report, cpu, rng) -> None:
    import jax
    from repro.core import ryser as R
    from repro.core.solver import SolverConfig
    from repro.serve import PermanentService, ServiceConfig
    n, requests, batch = SIZES["service"]
    U = _haar_unitary(n * n, rng)            # boson sampling: m = n^2 modes
    mats = [U[np.sort(rng.choice(n * n, n, replace=False))][:, :n]
            for _ in range(requests)]
    svc = PermanentService(SolverConfig(),
                           ServiceConfig(max_batch=batch,
                                         log_every_s=float("inf")),
                           log=None)
    t0 = time.perf_counter()
    tickets = [svc.submit(M, deadline_s=None) for M in mats]
    svc.drain()
    wall = time.perf_counter() - t0
    values = np.array([t.result() for t in tickets])
    with jax.default_device(cpu):
        refs = np.asarray(R.perm_ryser_batched(np.stack(mats)))
    dispatch_s = [round(dt, 4) for _, _, dt, _ in svc.dispatch_log]
    rep.check("service", values, refs, BOUNDS["service"], n=n,
              requests=requests, batch=batch, dispatches=len(dispatch_s),
              dispatch_s=dispatch_s, wall_s=wall)


def phase_complex_campaign(rep: Report, tpu, rng) -> None:
    n = SIZES["complex_campaign"]
    # rank one, unit-modulus entries: perm(u w^T) = n! prod(u) prod(w)
    u = np.exp(2j * np.pi * rng.uniform(size=n))
    w = np.exp(2j * np.pi * rng.uniform(size=n))
    A = np.outer(u, w)
    ref = complex(math.factorial(n) * np.prod(u) * np.prod(w))
    solver = _solver(_mesh([tpu]))
    plan = solver.plan(A)
    routes = sorted({leaf.route for leaf in plan.leaves})
    value, comp, run = _twice(lambda: solver.execute(plan))
    rep.check("complex_campaign", value, ref, BOUNDS["complex_campaign"],
              n=n, route="+".join(routes), compile_s=comp, run_s=run)


def phase_pallas(rep: Report, rng) -> None:
    """The dense Pallas kernel, compiled by Mosaic, in float32 (the solver
    does not route here: its leaves are float64).  Informative: the
    error is printed, not gated."""
    import jax
    from repro.core import ryser as R
    from repro.kernels import ops
    n = SIZES["pallas"]
    A = rng.uniform(-1.0, 1.0, (n, n))
    f32 = jax.device_put(A.astype(np.float32))
    value, comp, run = _twice(
        lambda: float(ops.permanent_pallas(f32, mode="batched")))
    ref = float(R.perm_ryser_chunked(A))
    rep.line("pallas_f32", n=n, value=value, ref_f64=ref,
             rel_err=_rel_err(value, ref), compile_s=comp, run_s=run,
             gated=False)


def run_one_chip(rep: Report) -> None:
    import jax
    tpu, cpu = jax.devices()[0], jax.devices("cpu")[0]
    rng = np.random.default_rng(2025)
    phase_allones(rep, tpu, cpu)
    phase_dense(rep, tpu, cpu, rng)
    phase_service(rep, cpu, rng)
    phase_complex_campaign(rep, tpu, rng)
    phase_pallas(rep, rng)


def run_four_chips(rep: Report) -> None:
    """Step-space campaign across chips and batch sharding (``--mesh``),
    each compared bitwise with the same work on one chip."""
    from repro.core import distributed as Dm
    from repro.core import ryser as R
    import jax
    devs = jax.devices()[:4]
    one, four = _mesh(devs[:1]), _mesh(devs)
    rng = np.random.default_rng(4)
    for name, mesh in (("one", one), ("four", four)):
        rep.line("mesh", name=name, devices=[d.id for d in mesh.devices.flat])

    n = SIZES["campaign4"]
    A = rng.uniform(-1.0, 1.0, (n, n))
    plan = _solver(campaign_threshold=-1.0).plan(A)   # force the route
    spec = plan.leaves[0].campaign
    vals = {}
    for name, mesh in (("one", one), ("four", four)):
        solver = _solver(mesh, campaign_threshold=-1.0)
        vals[name], comp, run = _twice(lambda: solver.execute(plan))
        rep.line("campaign", mesh=name, n=n, value=vals[name],
                 slices=spec.total_slices, compile_s=comp, run_s=run)
    rep.equal("campaign_4_vs_1", vals["four"], vals["one"], n=n)

    n, B = SIZES["bucket4"]
    stack = (rng.uniform(-1.0, 1.0, (B, n, n))
             + 1j * rng.uniform(-1.0, 1.0, (B, n, n)))
    sharded, comp, run = _twice(
        lambda: Dm.batch_permanents_on_mesh(stack, four))
    rep.line("bucket", mesh="four", n=n, batch=B, compile_s=comp, run_s=run)
    with jax.default_device(devs[0]):
        local, comp, run = _twice(lambda: R.perm_ryser_batched(stack))
    rep.line("bucket", mesh="one", n=n, batch=B, compile_s=comp, run_s=run)
    rep.equal("bucket_4_vs_1", sharded, local, n=n, batch=B)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip phase")
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_enable_x64", True)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), found "
              f"{len(devs)} {devs[0].platform} device(s); nothing run",
              file=sys.stderr)
        return 1
    from repro.serve import compile_stats, enable_compile_cache
    cache_dir = enable_compile_cache()
    print(f"devices: {len(devs)} x {devs[0].device_kind}; "
          f"compile cache: {cache_dir}", flush=True)

    rep = Report()
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(rep)
    else:
        run_one_chip(rep)
    rep.line("compile_cache", **compile_stats())
    rep.line("total", seconds=time.perf_counter() - t0,
             failed=",".join(rep.failed) or "-")
    if rep.failed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
