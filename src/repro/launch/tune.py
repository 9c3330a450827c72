"""Autotuner CLI: fill the on-disk kernel-geometry tuning table.

    PYTHONPATH=src python -m repro.launch.tune \
        --routes dense,sparse,complex --n 8..16 --out table.json
    PYTHONPATH=src python -m repro.launch.tune \
        --routes dense --n 8,10,12 --out table.json --interpret  # CPU CI

One line prints per tuned key (winner geometry, speedup over the
default, predicted-vs-measured ratio); the table lands at ``--out`` in
the versioned, kernel-source-hashed format of ``repro.tune.table`` and
is picked up by the planner via ``SolverConfig.tuning_table`` (or the
``REPRO_TUNING_TABLE`` audit hook).  ``--report`` additionally writes
the per-candidate mispredict rows as JSON for
``benchmarks/roofline_report.py``.

The ``campaign`` route tunes the per-device wave body of
``slice_sums_on_mesh`` and needs more than one visible device to be
meaningful -- combine with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
on CPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

__all__ = ["parse_ns", "tune_main"]


def parse_ns(spec: str) -> list[int]:
    """``"8..16"`` (inclusive range) or ``"8,10,12"`` (list) -> sizes."""
    spec = spec.strip()
    if ".." in spec:
        lo, hi = spec.split("..", 1)
        lo, hi = int(lo), int(hi)
        if lo > hi:
            raise ValueError(f"empty size range {spec!r}")
        return list(range(lo, hi + 1))
    return [int(tok) for tok in spec.split(",") if tok]


def tune_main(argv=None) -> int:
    import jax
    jax.config.update("jax_enable_x64", True)
    from ..tune.search import ROUTES, tune_table
    from ..utils.roofline import detect_hw

    ap = argparse.ArgumentParser()
    ap.add_argument("--routes", default="dense",
                    help=f"comma list of {','.join(ROUTES)}")
    ap.add_argument("--n", default="8..12", dest="sizes",
                    help='matrix sizes: "8..16" or "8,10,12"')
    ap.add_argument("--out", required=True, help="tuning table JSON path")
    ap.add_argument("--report", default=None,
                    help="also write per-candidate mispredict rows (JSON)")
    ap.add_argument("--precision", default="dq_acc",
                    choices=("dd", "dq_fast", "dq_acc", "qq", "kahan"))
    ap.add_argument("--density", type=float, default=0.5,
                    help="sparse-route density (bucketed in the table)")
    ap.add_argument("--batch", type=int, default=16,
                    help="measurement batch size")
    ap.add_argument("--top-k", type=int, default=3,
                    help="model-ranked candidates to measure per key")
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed repeats per candidate (median kept)")
    ap.add_argument("--interpret", action="store_true",
                    help="interpret-mode kernels (CPU CI; no accelerator)")
    ap.add_argument("--hw", default=None,
                    help="override the hardware spec (utils/roofline.py "
                         "registry name; default: autodetect)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    routes = [r for r in args.routes.split(",") if r]
    for r in routes:
        if r not in ROUTES:
            raise SystemExit(f"unknown route {r!r}; choose from {ROUTES}")
    ns = parse_ns(args.sizes)

    mesh = None
    if "campaign" in routes:
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()), ("step",))

    hw = detect_hw(args.hw) if args.hw else detect_hw()
    print(f"[tune] routes={','.join(routes)} n={ns} hw={hw.name} "
          f"interpret={args.interpret}", flush=True)
    t0 = time.time()

    def progress(entry):
        print(f"[tune] {entry.key()} -> {entry.geometry.tag()} "
              f"speedup={entry.speedup:.2f}x "
              f"pred/meas={entry.mispredict_ratio:.2f} "
              f"({entry.measured_s * 1e3:.2f}ms)", flush=True)

    table, report = tune_table(
        routes, ns, density=args.density, precision=args.precision,
        batch=args.batch, top_k=args.top_k, repeats=args.repeats,
        interpret=True if args.interpret else None, seed=args.seed,
        mesh=mesh,
        progress=progress)
    table.save(args.out)
    print(f"[tune] {len(table.entries)} entr(ies) -> {args.out} "
          f"({time.time() - t0:.1f}s)", flush=True)
    if args.report:
        with open(args.report, "w") as f:
            json.dump({"hw": hw.name, "rows": report}, f, indent=1)
        print(f"[tune] mispredict report -> {args.report}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(tune_main())
