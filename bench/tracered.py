"""Reduction of a profiler trace to the benchmark's device numbers.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  On a TPU each chip is a plane ``/device:TPU:<id>`` whose line
``XLA Ops`` holds one event per device operation and ``XLA Modules`` one
per program run.  The benchmark's own host spans are events named
``bench.<span>`` on the host plane.  All of them share one clock.

From these :func:`summarize` computes, for the chips used:

* busy time per chip: the union of the intervals in which one of its
  programs ran, and the idle share 1 - mean busy / traced window;
* the busy time of each program (module), summed over chips;
* the device ops that took the most self time, summed over chips;
* the longest idle gaps of each chip, each named by the innermost
  benchmark span open at its midpoint (``-`` where none was).
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

__all__ = ["TraceSummary", "summarize", "find_xplane", "merge",
           "gaps_named", "self_times"]

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps_named(busy, spans, lo: float, hi: float):
    """Idle gaps of merged ``busy`` intervals inside [lo, hi], each as
    (span name, start, length); ``spans`` are (start, end, name)."""
    out = []
    edges = [(lo, lo)] + [iv for iv in busy if lo < iv[1] and iv[0] < hi] \
        + [(hi, hi)]
    for (_, a), (b, _) in zip(edges, edges[1:]):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        open_ = [(s, e, n) for s, e, n in spans if s <= mid <= e]
        name = min(open_, key=lambda x: x[1] - x[0])[2] if open_ else "-"
        out.append((name, a, b - a))
    return out


def self_times(events) -> dict[str, float]:
    """Self time per op name: device ops nest (a while loop's event
    holds its body's), so each op's time less its children's."""
    out: dict[str, float] = defaultdict(float)
    stack: list[list] = []          # [end, name, duration, children]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][0]:
            _, n, dd, ch = stack.pop()
            out[n] += dd - ch
        if stack:
            stack[-1][3] += d
        stack.append([s + d, name, d, 0.0])
    for _, n, dd, ch in stack:
        out[n] += dd - ch
    return dict(out)


@dataclass
class TraceSummary:
    window_s: float
    busy_by_device: dict[int, float]
    ops_s: dict[str, float]
    modules_s: dict[str, float]
    gaps: list[tuple[str, float]] = field(default_factory=list)

    @property
    def busy_total_s(self) -> float:
        return sum(self.busy_by_device.values())

    @property
    def busy_s(self) -> float:
        n = len(self.busy_by_device)
        return self.busy_total_s / n if n else 0.0

    @property
    def idle_share(self) -> float | None:
        if not self.busy_by_device or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _op_name(hlo: str) -> str:
    return hlo.split(" = ", 1)[0]


def summarize(path: str, *, devices=None,
              window_s: float | None = None) -> TraceSummary:
    """Reduce the trace at ``path`` over the chips ``devices`` (ids; all
    TPU planes when None).  ``window_s`` is the traced window as the
    host timed it; without it, the extent of the benchmark's spans.

    A chip is busy while one of its programs runs (``XLA Modules``).
    Device and host clocks agree to about a millisecond, so a gap is
    named by the span open at its midpoint.
    """
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    spans, runs, ops = [], {}, {}
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.append((s, s + ev.duration_ns * 1e-9,
                                      ev.name[len(SPAN_PREFIX):]))
            continue
        dev = int(m.group(1))
        if devices is not None and dev not in devices:
            continue
        runs[dev], ops[dev] = [], []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                runs[dev] = [(ev.name, ev.start_ns * 1e-9,
                              ev.duration_ns * 1e-9) for ev in line.events]
            elif line.name == OPS_LINE:
                ops[dev] = [(_op_name(ev.name), ev.start_ns * 1e-9,
                             ev.duration_ns * 1e-9) for ev in line.events]
    if spans:
        lo = min(s for s, _, _ in spans)
        hi = max(e for _, e, _ in spans)
    else:
        lo = hi = 0.0
    ops_s: dict[str, float] = defaultdict(float)
    modules: dict[str, float] = defaultdict(float)
    busy, gaps = {}, []
    for dev in sorted(runs):
        for name, secs in self_times(ops[dev]).items():
            ops_s[name] += secs
        for name, _, d in runs[dev]:
            modules[name] += d
        ivs = merge((s, s + d) for _, s, d in runs[dev])
        busy[dev] = sum(e - s for s, e in ivs)
        if spans:
            gaps += [(name, length)
                     for name, _, length in gaps_named(ivs, spans, lo, hi)]
    return TraceSummary(window_s=window_s if window_s is not None
                        else hi - lo,
                        busy_by_device=busy, ops_s=dict(ops_s),
                        modules_s=dict(modules), gaps=gaps)
