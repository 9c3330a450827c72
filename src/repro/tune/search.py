"""Cost-model-seeded kernel geometry search.

Pipeline per ``(route, n, density_bucket, dtype, precision)`` key:

1. **Enumerate** every ``(lanes, steps_per_chunk, window)`` candidate on
   a power-of-two grid, validated by the PR 8 geometry auditor
   (``analysis/geometry.py::validate_tiling``) and deduplicated by the
   clamped ``(TB, C, Wu, num_blocks)`` it resolves to at this n -- no
   candidate can violate the VMEM / step-space / window invariants.
2. **Prune** with the analytic roofline model (:func:`model_cost`,
   ``utils/roofline.py`` hardware specs): rank by modeled time, keep the
   top-k.  The default geometry is always kept, so the winner can never
   measure slower than untuned.
3. **Measure** survivors through the existing public kernel entry points
   (``kernels/ops.py``): compile once, one warm-up call, then
   median-of-repeats wall time.  The compiled module's HLO feeds
   ``utils/hlo_cost.py::analyze_hlo`` for the *refined* prediction that
   is persisted next to the measurement -- the predicted-vs-measured
   ratio is the mispredict report consumed by
   ``benchmarks/roofline_report.py``.
4. **Persist** the winner as a :class:`~repro.tune.table.TableEntry`.

Kernels run in interpret mode on CPU and compiled on an accelerator
(``kernels.ops.pallas_interpret`` decides from the platform); the table
records which via ``device_kind``.
"""

from __future__ import annotations

import statistics
import time

from ..analysis.geometry import validate_tiling
from ..core.stepspace import DEFAULT_GEOMETRY, Geometry
from ..utils.roofline import HwSpec, detect_hw
from .table import TableEntry, TuningTable, density_bucket, host_device_kind

__all__ = ["enumerate_candidates", "model_cost", "measure_candidate",
           "tune_key", "tune_table", "ROUTES"]

ROUTES = ("dense", "complex", "sparse", "campaign")

# Power-of-two candidate grid (requested knobs; kernel_geometry clamps
# them per n, enumerate_candidates dedups the clamped results).
LANES_GRID = (32, 64, 128, 256)
SPC_GRID = (32, 64, 128, 256)
WINDOW_GRID = (8, 16, 32)

_SUBLANE = 8

# In-kernel accumulation cost multipliers relative to plain adds
# (dd = 2-op twofloat-lite, kahan = 4 ops, dq = 7-op two_sum chains).
_PREC_MULT = {"dd": 1.0, "kahan": 2.0, "dq_fast": 2.5, "dq_acc": 3.5,
              "qq": 1.0}


def _pad(n: int) -> int:
    return max(_SUBLANE, -(-n // _SUBLANE) * _SUBLANE)


def enumerate_candidates(n: int) -> list[Geometry]:
    """Valid, deduplicated candidates for matrix size n.

    The default geometry is always first; every other candidate passed
    ``validate_tiling`` and resolves to a distinct clamped
    ``(TB, C, Wu, num_blocks)``.
    """
    out = [DEFAULT_GEOMETRY]
    seen = {DEFAULT_GEOMETRY.kernel_geometry(n)}
    for lanes in LANES_GRID:
        for spc in SPC_GRID:
            for window in WINDOW_GRID:
                if validate_tiling(n, lanes, spc, window):
                    continue
                g = Geometry(lanes, spc, window)
                resolved = g.kernel_geometry(n)
                if resolved in seen:
                    continue
                seen.add(resolved)
                out.append(g)
    return out


def model_cost(geometry: Geometry, n: int, *, route: str = "dense",
               density: float = 1.0, batch: int = 1, chips: int = 1,
               hw: HwSpec | None = None) -> float:
    """Analytic roofline time (seconds) for one kernel launch.

    Per Gray step each lane does the CEG column update (~2 n_pad VPU
    flops, density-scaled on the sparse route), the running-product
    accumulation (~2 n_pad flops, precision-multiplied), and an
    amortized share of the window-boundary one-hot matmul
    (2 n_pad^2 / Wu MXU flops).  HBM traffic is the per-block working
    set (A / schedule / state planes) streamed once per block, and each
    block pays a fixed launch overhead.  This is a *ranking* model --
    the persisted prediction is refined from compiled HLO
    (:func:`measure_candidate`); the mispredict report tracks how far
    off both are.
    """
    hw = hw or detect_hw()
    TB, C, Wu, nb = geometry.kernel_geometry(n)
    n_pad = _pad(n)
    space = TB * C * nb
    cplx = 4.0 if route == "complex" else 1.0
    dens = density if route == "sparse" else 1.0
    prec = _PREC_MULT.get("dq_acc", 3.5)

    update_flops = 2.0 * n_pad * dens
    accum_flops = 2.0 * n_pad * prec
    boundary_flops = 2.0 * n_pad * n_pad / Wu
    flops = batch * space * cplx * (update_flops + accum_flops)
    dot = batch * space * cplx * boundary_flops

    # VPU-class elementwise stream vs MXU dot stream (v5e VPU ~= MXU/32)
    t_vpu = flops / (chips * hw.peak_flops / 32.0)
    t_mxu = dot / (chips * hw.peak_flops)

    from ..analysis.geometry import block_vmem_bytes
    block_bytes = block_vmem_bytes(n, TB, Wu, complex_planes=(cplx > 1))
    t_mem = batch * nb * block_bytes / (chips * hw.hbm_bw)

    launch_overhead = 2e-6
    return max(t_vpu, t_mxu, t_mem) + batch * nb * launch_overhead / chips


def _hlo_predicted_s(compiled, *, chips: int, hw: HwSpec) -> float:
    """Refined prediction from the compiled module's HLO text."""
    from ..utils.hlo_cost import analyze_hlo
    try:
        cost = analyze_hlo(compiled.as_text())
    except Exception:  # noqa: BLE001 -- prediction is best-effort
        return 0.0
    t_vpu = cost.elementwise_flops / (chips * hw.peak_flops / 32.0)
    t_mxu = cost.dot_flops / (chips * hw.peak_flops)
    t_mem = cost.bytes_accessed / (chips * hw.hbm_bw)
    return max(t_vpu, t_mxu, t_mem)


def _median_time(call, args, repeats: int) -> float:
    import jax
    jax.block_until_ready(call(*args))      # warm (compile + first run)
    times = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(call(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def _route_callable(route: str, n: int, *, density: float, batch: int,
                    precision: str, interpret: bool | None, seed: int,
                    mesh=None):
    """(jitted fn, concrete args) measuring one launch of ``route``.

    dense / complex / sparse go through the public batched entries in
    ``kernels/ops.py``; ``campaign`` measures one
    ``slice_sums_on_mesh`` wave body (the distributed kernel shape).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    if route in ("dense", "complex"):
        As = rng.uniform(-1, 1, (batch, n, n))
        if route == "complex":
            As = As + 1j * rng.uniform(-1, 1, (batch, n, n))
        As = jnp.asarray(As)
        from ..kernels import ops as K

        def call(geometry):
            f = jax.jit(lambda xs: K.permanent_pallas_batched(
                xs, precision=precision, geometry=geometry,
                interpret=interpret))
            return f, (As,)
        return call

    if route == "sparse":
        from ..core.sparyser import SparseMatrix, pack_padded_ccs
        from ..kernels import ops as K
        sps = []
        for _ in range(batch):
            A = rng.uniform(0.1, 1, (n, n))
            mask = rng.uniform(size=(n, n)) < density
            np.fill_diagonal(mask, True)    # keep the permanent nonzero
            sps.append(SparseMatrix.from_dense(A * mask))
        A_stack, rows_stack, vals_stack = pack_padded_ccs(sps)
        args = (jnp.asarray(A_stack), jnp.asarray(rows_stack),
                jnp.asarray(vals_stack))

        def call(geometry):
            f = jax.jit(lambda a, r, v: K.sparse_batched_values_pallas(
                a, r, v, precision=precision, geometry=geometry,
                interpret=interpret))
            return f, args
        return call

    if route == "campaign":
        if mesh is None:
            raise ValueError("campaign route requires a mesh")
        from ..core.distributed import slice_sums_on_mesh
        from ..core.stepspace import plan_slices
        A = jnp.asarray(rng.uniform(-1, 1, (n, n)))
        D = mesh.devices.size
        ts, cps, cs = plan_slices(n, D)
        ids = jnp.arange(D, dtype=jnp.int32)

        def call(geometry):
            def f(slice_ids):
                return slice_sums_on_mesh(
                    A, mesh, slice_ids, chunks_per_slice=cps,
                    chunk_size=cs, precision=precision, backend="pallas",
                    geometry=geometry)
            return f, (ids,)
        return call

    raise ValueError(f"unknown tuning route {route!r}")


def measure_candidate(call_factory, geometry: Geometry, *, repeats: int,
                      chips: int, hw: HwSpec):
    """(measured_s, hlo_predicted_s) for one candidate geometry."""
    import jax
    f, args = call_factory(geometry)
    predicted = 0.0
    try:
        compiled = jax.jit(f).lower(*args).compile()
        predicted = _hlo_predicted_s(compiled, chips=chips, hw=hw)
        runner, rargs = compiled, args
    except Exception:  # noqa: BLE001 -- shard_map bodies may not re-jit
        runner, rargs = f, args
    measured = _median_time(runner, rargs, repeats)
    return measured, predicted


def tune_key(route: str, n: int, *, density: float = 1.0,
             dtype: str = "<f8", precision: str = "dq_acc",
             batch: int = 16, top_k: int = 3, repeats: int = 3,
             interpret: bool | None = None, seed: int = 0, mesh=None,
             hw: HwSpec | None = None):
    """Tune one table key; returns (TableEntry, candidate report rows).

    The report rows carry every *measured* candidate's modeled,
    HLO-predicted and measured times -- the raw material of the
    mispredict report.
    """
    hw = hw or detect_hw()
    chips = mesh.devices.size if (mesh is not None
                                  and route == "campaign") else 1
    cands = enumerate_candidates(n)
    ranked = sorted(
        cands, key=lambda g: model_cost(g, n, route=route, density=density,
                                        batch=batch, chips=chips, hw=hw))
    survivors = ranked[:max(1, top_k)]
    if DEFAULT_GEOMETRY not in survivors:
        survivors.append(DEFAULT_GEOMETRY)   # tuned >= untuned floor

    call_factory = _route_callable(route, n, density=density, batch=batch,
                                   precision=precision,
                                   interpret=interpret, seed=seed,
                                   mesh=mesh)
    report = []
    results = {}
    for g in survivors:
        measured, hlo_pred = measure_candidate(
            call_factory, g, repeats=repeats, chips=chips, hw=hw)
        modeled = model_cost(g, n, route=route, density=density,
                             batch=batch, chips=chips, hw=hw)
        predicted = hlo_pred or modeled
        results[g] = (measured, predicted)
        report.append({"route": route, "n": n, "geometry": g.tag(),
                       "modeled_s": modeled, "hlo_predicted_s": hlo_pred,
                       "predicted_s": predicted, "measured_s": measured,
                       "mispredict_ratio": (predicted / measured
                                            if measured else 0.0)})

    winner = min(results, key=lambda g: results[g][0])
    measured_s, predicted_s = results[winner]
    default_s = results[DEFAULT_GEOMETRY][0]
    # planner route names: complex matrices travel the dense route with a
    # complex dtype; campaign wave bodies are the step_sharded route
    plan_route = {"campaign": "step_sharded", "complex": "dense"}.get(
        route, route)
    entry = TableEntry(
        route=plan_route,
        n=n, density_bucket=density_bucket(density), dtype=dtype,
        precision=precision, device_kind=host_device_kind(),
        geometry=winner, predicted_s=predicted_s, measured_s=measured_s,
        default_s=default_s)
    return entry, report


def tune_table(routes, ns, *, density: float = 1.0,
               precision: str = "dq_acc", batch: int = 16, top_k: int = 3,
               repeats: int = 3, interpret: bool | None = None, seed: int = 0,
               mesh=None, table: TuningTable | None = None,
               progress=None):
    """Tune every (route, n) pair into a TuningTable.

    Routes map to dtypes: ``dense``/``sparse``/``campaign`` tune the
    ``<f8`` key, ``complex`` the ``<c16`` key.  Returns
    (table, report rows).
    """
    table = table or TuningTable()
    report = []
    for route in routes:
        dtype = "<c16" if route == "complex" else "<f8"
        dens = density if route == "sparse" else 1.0
        for n in ns:
            if n < 4:       # below the kernel floor (executor falls back)
                continue
            entry, rows = tune_key(
                route, n, density=dens, dtype=dtype, precision=precision,
                batch=batch, top_k=top_k, repeats=repeats,
                interpret=interpret, seed=seed, mesh=mesh)
            table.put(entry)
            report.extend(rows)
            if progress:
                progress(entry)
    return table, report
