"""Campaign throughput vs direct mesh permanent + kill/resume identity.

ISSUE 6's tentpole: a single huge permanent routes through the planner's
``step_sharded`` campaign route -- checkpointed, preemption-safe waves of
``slice_sums_on_mesh`` -- instead of the one-shot ``permanent_on_mesh``
psum.  The resilience cannot be free, but it must be nearly free: the
campaign re-forms waves on the host and checkpoints twofloat partials
after each one, so its throughput is gated at >= 0.9x the direct
mesh path at the same forced device count.

Two measurements, both in subprocesses (XLA_FLAGS must be set before jax
initializes):

* **throughput** -- ``permanent_on_mesh`` vs ``run_campaign`` on the same
  8-device host mesh, same (lanes, slices) step-space geometry;
* **resume**     -- the ``repro.launch.campaign`` CLI is SIGKILLed
  mid-wave on a 2-device mesh and resumed on 8; the printed value must be
  bitwise-identical to an uninterrupted 8-device run (real and complex).

    PYTHONPATH=src python -m benchmarks.campaign_resume [--check] [--fast]
    PYTHONPATH=src python -m benchmarks.run --only campaign --check
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import tempfile

SPEEDUP_GATE = 0.9
DEVICES = 8
N_FULL = 18
N_FAST = 14

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_WORKER = r"""
import math
import time

import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from jax.sharding import Mesh

from repro.core import distributed as Dm
from repro.core.stepspace import plan_slices

n = {n}
repeats = {repeats}
devices = {devices}
mesh = Mesh(np.array(jax.devices()[:devices]), ("step",))
rng = np.random.default_rng({seed})
A = rng.uniform(0.2, 1.2, (n, n))

# identical step-space budget for both paths: the campaign's
# (slices x chunks) product equals the direct path's lane count
ts, cps, C = plan_slices(n, devices, 8, 128)
lanes = ts * cps // devices


def best(fn):
    b = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        b = min(b, time.perf_counter() - t0)
    return b


v_direct = float(Dm.permanent_on_mesh(A, mesh, slices_per_device=8,
                                      lanes_per_device=lanes))
v_campaign, _ = Dm.run_campaign(A, mesh, total_slices=ts,
                                chunks_per_slice=cps, chunk_size=C)
t_direct = best(lambda: Dm.permanent_on_mesh(
    A, mesh, slices_per_device=8, lanes_per_device=lanes))
t_campaign = best(lambda: Dm.run_campaign(
    A, mesh, total_slices=ts, chunks_per_slice=cps, chunk_size=C))
rel = abs(v_campaign - v_direct) / abs(v_direct)
print(f"ROW,kind=throughput,n={{n}},devices={{devices}},waves={{ts // devices}},"
      f"t_direct_s={{t_direct:.4f}},t_campaign_s={{t_campaign:.4f}},"
      f"ratio={{t_direct / t_campaign:.3f}},rel_err={{rel:.2e}}")
"""


def _env(devices: int):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # a CPU gate: never reach for a chip the parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _SRC + os.pathsep * bool(env.get("PYTHONPATH")) \
        + env.get("PYTHONPATH", "")
    return env


def _throughput_row(n: int, devices: int, repeats: int, seed: int):
    code = _WORKER.format(n=n, repeats=repeats, devices=devices, seed=seed)
    r = subprocess.run([sys.executable, "-c", code], env=_env(devices),
                       capture_output=True, text=True, timeout=1200)
    if r.returncode != 0:
        raise RuntimeError(f"campaign_resume worker failed:\n"
                           f"{r.stdout[-2000:]}{r.stderr[-3000:]}")
    for line in r.stdout.splitlines():
        if line.startswith("ROW,"):
            return dict(kv.split("=", 1) for kv in line[4:].split(","))
    raise RuntimeError(f"no ROW in worker output:\n{r.stdout[-2000:]}")


def _cli_value(out: str) -> str:
    for line in out.splitlines():
        if "perm(A) =" in line:
            return line.split("perm(A) =")[1].split("  (")[0].strip()
    raise RuntimeError(f"no value line:\n{out[-2000:]}")


def _resume_row(n: int, devices: int, use_complex: bool, seed: int):
    """SIGKILL the campaign CLI mid-wave on 2 devices, resume on
    ``devices``; report whether the value is bitwise-identical to an
    uninterrupted run."""
    kind = "resume_complex" if use_complex else "resume_real"
    with tempfile.TemporaryDirectory() as tmp:
        ckpt = os.path.join(tmp, "job.npz")
        base = [sys.executable, "-m", "repro.launch.campaign",
                "--n", str(n), "--slices", "64", "--lanes", "8",
                "--seed", str(seed)]
        if use_complex:
            base.append("--complex")
        ref = subprocess.run(
            [*base, "--checkpoint", os.path.join(tmp, "ref.npz")],
            env=_env(devices), capture_output=True, text=True, timeout=1200)
        if ref.returncode != 0:
            raise RuntimeError(ref.stdout + ref.stderr[-3000:])
        v_ref = _cli_value(ref.stdout)

        p = subprocess.Popen([*base, "--checkpoint", ckpt,
                              "--devices", "2"],
                             env=_env(devices), stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT, text=True)
        try:
            for line in p.stdout:
                if "[campaign] wave" in line:
                    os.kill(p.pid, signal.SIGKILL)
                    break
            p.wait(timeout=300)
        finally:
            p.stdout.close()
            if p.poll() is None:
                p.kill()
                p.wait(timeout=300)

        res = subprocess.run([*base, "--checkpoint", ckpt],
                             env=_env(devices), capture_output=True,
                             text=True, timeout=1200)
        if res.returncode != 0:
            raise RuntimeError(res.stdout + res.stderr[-3000:])
        v_res = _cli_value(res.stdout)
        return {"kind": kind, "n": str(n), "devices": str(devices),
                "bitwise": str(int(v_res == v_ref))}


def run(n: int = N_FULL, devices: int = DEVICES, repeats: int = 3,
        seed: int = 0):
    rows = [_resume_row(max(12, n - 4), devices, False, seed),
            _resume_row(max(12, n - 4), devices, True, seed),
            _throughput_row(n, devices, repeats, seed)]
    return rows


def check(rows) -> bool:
    """ISSUE-6 gate: campaign >= 0.9x direct mesh throughput at equal
    device count; killed-and-resumed values bitwise-identical."""
    ok = True
    for row in rows:
        if row["kind"].startswith("resume"):
            if row["bitwise"] != "1":
                print(f"# campaign_resume: {row['kind']} NOT "
                      f"bitwise-identical -- FAIL")
                ok = False
            continue
        ratio = float(row["ratio"])
        gate_ok = ratio >= SPEEDUP_GATE
        status = "OK" if gate_ok else "FAIL"
        print(f"# campaign gate (n={row['n']} x{row['devices']} devices, "
              f"{row['waves']} waves): {ratio:.2f}x vs required "
              f"{SPEEDUP_GATE:.1f}x direct-mesh throughput -- {status}")
        if float(row["rel_err"]) > 1e-10:
            print(f"# campaign_resume: campaign/direct values diverge "
                  f"(rel_err={row['rel_err']}) -- FAIL")
            ok = False
        ok = ok and gate_ok
    return ok


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=DEVICES)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--fast", action="store_true",
                    help=f"smaller matrix (n={N_FAST}) for quick checks")
    ap.add_argument("--check", action="store_true",
                    help="enforce the >= 0.9x + bitwise-resume gate")
    args = ap.parse_args()

    n = args.n if args.n is not None else (N_FAST if args.fast else N_FULL)
    rows = run(n=n, devices=args.devices, repeats=args.repeats)
    for r in rows:
        print("campaign_resume," + ",".join(f"{k}={v}"
                                            for k, v in r.items()))
    if args.check and not check(rows):
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
