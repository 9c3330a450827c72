"""One run of one benchmark cell: set-up, measured window, check, metrics.

``bench/run.py`` is the command; this module does the work, so that the
tests can drive a run at a tiny size on the CPU through the same code.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its
configuration (``bench/configs/<config>.json``) names its request kind,
``bench/entries/<entries>.py``, which says what a request is; its traffic
(``bench/traffic/<traffic>.json``) names the loop that drives the
program, ``bench/loops/<loop>.py``, and how work arrives.  Each metric is
computed by its own reader from the :class:`Run` record:
``bench/metrics/<metric>.py``, or for a metric ``<family>.<cell>`` that
has no file of its own, ``bench/metrics/<family>.py``.  Nothing here
names a cell, a configuration, a loop or a metric, so a new one is new
files and ``BENCHMARK.json`` entries.

The loops use the program only through the entry points its users
call: ``PermanentService.submit``/``step``,
``PermanentSolver.plan_batch``/``execute``, ``serve.warmup`` and
``serve.enable_compile_cache``.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# a fixed path inside the checkout: the path is part of the cache key
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

import traffic  # noqa: E402

__all__ = ["Cell", "load_cell", "Run", "run_cell", "plugin",
           "request_source", "solver_config", "sample"]


# -- cells ------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its configuration,
    traffic and the metrics it reports."""
    spec = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in spec["workloads"]:
        if w["name"] == name:
            break
    else:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load_json(os.path.join(ROOT, cfg["file"])),
        traffic=_load_json(os.path.join(BENCH, "traffic",
                                        w["traffic"] + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


# -- what a run records -----------------------------------------------------

class Spans:
    """The benchmark's own host spans, around its calls into the program.

    Each is kept as ``(name, start, end)`` on ``time.perf_counter`` and,
    while the profiler runs, written into its trace as
    ``bench.<name>`` so that idle gaps can be named by the open span.
    """

    def __init__(self):
        self.items: list[tuple[str, float, float]] = []
        self._annotate = None

    def annotate(self, on: bool) -> None:
        if on:
            import jax
            self._annotate = jax.profiler.TraceAnnotation
        else:
            self._annotate = None

    @contextmanager
    def span(self, name: str):
        ann = self._annotate(f"bench.{name}") if self._annotate else None
        if ann is not None:
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.items.append((name, t0, t1))

    def durations(self, name: str) -> list[float]:
        return [t1 - t0 for n, t0, t1 in self.items if n == name]


@dataclass
class Run:
    """Everything a metric reader may read."""
    cell: Cell
    seed: int
    seconds: float
    setup_s: float = 0.0
    window_start: float = 0.0
    spans: Spans = field(default_factory=Spans)
    counters: dict = field(default_factory=dict)
    # open loops: (arrival, start of the resolving step, done, answered)
    requests: list[tuple[float, float, float, bool]] = \
        field(default_factory=list)
    # closed loops: (start, end, permanents) of each completed call
    calls: list[tuple[float, float, int]] = field(default_factory=list)
    # algorithmic flops of everything run while the profiler traced
    traced_flops: float = 0.0
    trace: object = None             # tracered.TraceSummary, --trace 1


# -- plug-ins -----------------------------------------------------------------

_PLUGINS: dict[tuple[str, str], object] = {}


def plugin(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``: a request kind
    (``entries``), a loop (``loops``) or a metric reader (``metrics``)."""
    key = (kind, name)
    if key not in _PLUGINS:
        path = os.path.join(BENCH, kind, name + ".py")
        if not os.path.exists(path):
            raise KeyError(f"no {kind} named {name!r} ({path})")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name}".replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _PLUGINS[key] = mod
    return _PLUGINS[key]


def request_source(config: dict, seed: int):
    """The configuration's request kind, drawn from the seed: an object
    with ``n``, ``is_complex`` and ``draw(count, rng)``."""
    return plugin("entries", config["entries"]).Source(config, seed)


def solver_config(config: dict):
    from repro.core.solver import SolverConfig
    return SolverConfig(**config.get("solver", {}))


def sample(count: int, k: int, seed: int) -> list[int]:
    """k indices of range(count), drawn from the seed, the last one (the
    latest answer) always among them."""
    if count <= k:
        return list(range(count))
    rng = traffic.stream(seed, "sample")
    pick = set(rng.choice(count - 1, k - 1, replace=False).tolist())
    return sorted(pick | {count - 1})


def _rel_errs(values, refs) -> list[float]:
    return [abs(complex(v) - complex(r)) / abs(complex(r))
            for v, r in zip(values, refs)]


# -- metrics --------------------------------------------------------------------

def _reader(name: str):
    """The reader of ``name``, or of its family ``name`` less its last
    ``.<part>``."""
    try:
        return plugin("metrics", name).read
    except KeyError:
        if "." not in name:
            raise
        return plugin("metrics", name.rsplit(".", 1)[0]).read


def read_metrics(run: Run, metrics: list[dict]) -> dict:
    out = {}
    for m in metrics:
        value = _reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- one run --------------------------------------------------------------------

def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class GcPauses:
    """The host's garbage-collector pauses while installed in
    ``gc.callbacks``, in seconds."""

    def __init__(self):
        self.pauses: list[float] = []
        self._t0 = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append(time.perf_counter() - self._t0)
            self._t0 = None

    def counters(self) -> dict:
        return {"gc_collections": len(self.pauses),
                "gc_ms_max": 1e3 * max(self.pauses, default=0.0),
                "gc_ms_total": 1e3 * sum(self.pauses)}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             *, t_start: float, log=print) -> dict:
    """Set up, measure, check; returns the result line's object."""
    import jax
    import reference
    from repro.serve import compile_stats

    run = Run(cell=cell, seed=seed, seconds=float(seconds))
    driver = plugin("loops", cell.traffic["loop"]).Driver(run, devices)
    driver.setup()
    used = list(devices[:cell.chips])
    before = compile_stats()
    run.setup_s = time.perf_counter() - t_start
    run.counters.update(setup_cache_hits=before["persistent_hits"],
                        setup_cache_misses=before["persistent_misses"])

    if trace:
        import tracered
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        run.spans.annotate(True)
        # host side: the benchmark's spans and the runtime's top events
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t_trace = time.perf_counter()
    gc_pauses = GcPauses()
    gc.callbacks.append(gc_pauses)
    try:
        driver.window()
    finally:
        gc.callbacks.remove(gc_pauses)
    run.counters.update(gc_pauses.counters())
    if trace:
        t_trace = time.perf_counter() - t_trace
        jax.profiler.stop_trace()
        run.spans.annotate(False)
    after = compile_stats()
    run.counters["compiles_in_window"] = after["requests"] - before["requests"]
    log(f"compiles_in_window: {run.counters['compiles_in_window']}")
    driver.record()
    peak = memory_peak(used)

    attempted, failed, lost, answers = driver.answers()
    driver.release()
    if trace:
        run.trace = tracered.summarize(
            tracered.find_xplane(trace_dir), devices=[d.id for d in used],
            window_s=t_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log("programs_busy_s: " + json.dumps(run.trace.modules_s))
    gc.collect()

    # the comparison, once the window has closed and the program's
    # state is freed
    t_ref = time.perf_counter()
    refs = reference.permanents([m for m, _ in answers])
    errs = _rel_errs([v for _, v in answers], refs)
    run.counters["reference_s"] = time.perf_counter() - t_ref
    limit = float(cell.config["limits"]["max_rel_err"])
    checks = {
        "max_rel_err": {"value": max(errs) if errs else math.inf,
                        "limit": limit},
        "unanswered": {"value": lost, "limit": 0},
    }
    correct = (bool(errs) and max(errs) <= limit and lost == 0)

    result = {
        "correct": correct, "attempted": attempted, "failed": failed,
        "metrics": read_metrics(run, cell.per_layer if trace
                                else cell.end_to_end),
        "device": {"platform": used[0].platform,
                   "kind": used[0].device_kind, "count": len(used),
                   "memory_peak_bytes": peak},
    }
    if trace:
        result["device"]["busy_s"] = run.trace.busy_s
        result["device"]["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    log("counters: " + json.dumps(run.counters, sort_keys=True))
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    result["checks"] = checks
    return result
