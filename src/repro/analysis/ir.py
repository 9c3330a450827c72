"""permprove: IR-level verification of the determinism & precision
contracts, with golden-trace drift gating (ISSUE 10).

Traces every public permanent entry -- dense/sparse x real/complex x
scalar/batch x jnp/pallas engines plus the campaign wave bodies -- per
precision mode via ``jax.make_jaxpr`` over abstract avals (no device
work, the same discipline as the PR 8 geometry auditor), renders each
jaxpr into a *canonical* text form (stable variable names, sorted
params, recursively inlined sub-jaxprs, const digests -- no memory
addresses or source locations), and:

* checks the PLI-series contracts from ``contracts.py`` on the walks
  (PLI101 batch-axis reductions, PLI102 dtype truncation, PLI103
  batch-extent invariance, PLI104 collective audit on the compiled
  sharded programs);
* fingerprints the canonical text per (route, engine, dtype, arity,
  precision) against goldens under ``tests/ir_goldens/`` -- any
  numerics-affecting IR change becomes an explicit, reviewed diff
  (``--bless`` regenerates; see docs/INVARIANTS.md for etiquette).

CLI::

    python -m repro.analysis.ir --check [--json] [--report PATH]
    python -m repro.analysis.ir --bless
    python -m repro.analysis.ir --check --entries 'dense_jnp.*'

Importing this module is jax-free; jax loads on first trace.  The CLI
forces 8 host devices (before jax import) so the PLI104 collective
audit sees a real mesh on CPU; in-process callers with a single device
get a loud "skipped" marker for PLI104 instead of a silent pass.
"""

from __future__ import annotations

import argparse
import dataclasses
import difflib
import fnmatch
import hashlib
import json
import os
import re
import sys
from dataclasses import dataclass

from . import contracts
from .rules import Finding

__all__ = ["ENTRIES", "Entry", "canonical_lines", "canonical_walk",
           "fingerprint", "trace_entry", "run_check", "bless",
           "golden_path", "GOLDEN_DIR", "PRECISIONS", "main"]

VERSION = "permprove/1"
PRECISIONS = ("dd", "dq_fast", "dq_acc", "kahan", "qq")

# Trace geometry: small enough to trace fast, big enough that every
# schedule/kernel arm is live.  2^(n-1) = 32 = T*C.
N = 6
NUM_CHUNKS = 16
MAXDEG = 3                    # padded-CCS column degree for sparse entries
CPS, CHUNK = 2, 16            # campaign wave: chunks_per_slice, chunk_size
CANON_B = 5                   # canonical batch extent (golden traces)
ALT_B = 7                     # second extent for PLI101/PLI103 (coprime)
TEXT_PRECISION = "dq_acc"     # the precision whose canonical text is
                              # stored verbatim in goldens (diffable);
                              # other precisions gate on fingerprints

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
GOLDEN_DIR = os.path.join(_REPO, "tests", "ir_goldens")


# ---------------------------------------------------------------------------
# Entry registry
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Entry:
    route: str     # dense | sparse | campaign
    engine: str    # jnp | pallas
    dtype: str     # f64 | c128
    arity: str     # scalar | batch | wave

    @property
    def name(self) -> str:
        return f"{self.route}_{self.engine}.{self.dtype}.{self.arity}"

    @property
    def batched(self) -> bool:
        return self.arity == "batch"


ENTRIES: tuple[Entry, ...] = tuple(
    Entry(route, engine, dtype, arity)
    for route in ("dense", "sparse")
    for engine in ("jnp", "pallas")
    for dtype in ("f64", "c128")
    for arity in ("scalar", "batch")
) + tuple(
    Entry("campaign", engine, dtype, "wave")
    for engine in ("jnp", "pallas")
    for dtype in ("f64", "c128")
)


def _sds(shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype)


def _build(entry: Entry, precision: str, B: int):
    """(fn, abstract args) for one entry: the *production* traced body
    behind the matching public API, not a test double."""
    import numpy as np

    n = N
    f64, c128, i32 = np.float64, np.complex128, np.int32
    dt = f64 if entry.dtype == "f64" else c128
    from ..core.ryser import chunk_geometry
    T, C, _ = chunk_geometry(n, NUM_CHUNKS)

    if entry.route == "dense" and entry.engine == "jnp":
        from ..core import ryser
        if entry.dtype == "c128":
            # complex runs as split planes, the scalar entry as the B=1
            # batch program (perm_ryser_chunked -> _complex_batched)
            Bc = 1 if entry.arity == "scalar" else B
            fn = lambda Ar, Ai: ryser.batched_values_complex(
                Ar, Ai, T, C, precision)
            return fn, (_sds((Bc, n, n), f64), _sds((Bc, n, n), f64))
        if entry.arity == "scalar":
            fn = lambda A: ryser.perm_ryser_chunked(
                A, num_chunks=NUM_CHUNKS, precision=precision)
            return fn, (_sds((n, n), dt),)
        fn = lambda As: ryser.perm_ryser_batched(
            As, num_chunks=NUM_CHUNKS, precision=precision)
        return fn, (_sds((B, n, n), dt),)

    if entry.route == "dense" and entry.engine == "pallas":
        from ..kernels.ops import _pallas_values
        from ..core.stepspace import DEFAULT_GEOMETRY
        mode = "batched" if entry.batched else "baseline"
        fn = lambda As: _pallas_values(
            As, batched=entry.batched, precision=precision, mode=mode,
            geometry=DEFAULT_GEOMETRY, interpret=True)
        shape = (B, n, n) if entry.batched else (n, n)
        return fn, (_sds(shape, dt),)

    if entry.route == "sparse" and entry.engine == "jnp":
        from ..core import sparyser
        if entry.dtype == "f64":
            if entry.arity == "scalar":
                fn = lambda A, r, v: sparyser.sparse_chunked_value(
                    A, r, v, T, C, precision)
                return fn, (_sds((n, n), f64), _sds((n, MAXDEG), i32),
                            _sds((n, MAXDEG), f64))
            fn = lambda As, rs, vs: sparyser.sparse_batched_values(
                As, rs, vs, T, C, precision)
            return fn, (_sds((B, n, n), f64), _sds((B, n, MAXDEG), i32),
                        _sds((B, n, MAXDEG), f64))
        # complex scalar runs as a B=1 batch program in production
        # (perm_sparyser_chunked -> perm_sparyser_batched), so the
        # scalar entry IS the B=1 trace of the batched body.
        Bc = 1 if entry.arity == "scalar" else B
        fn = lambda Ar, Ai, rs, vr, vi: \
            sparyser.sparse_batched_values_complex(
                Ar, Ai, rs, vr, vi, T, C, precision)
        return fn, (_sds((Bc, n, n), f64), _sds((Bc, n, n), f64),
                    _sds((Bc, n, MAXDEG), i32),
                    _sds((Bc, n, MAXDEG), f64), _sds((Bc, n, MAXDEG), f64))

    if entry.route == "sparse" and entry.engine == "pallas":
        from ..kernels.ops import _pallas_sparse_values
        from ..core.stepspace import DEFAULT_GEOMETRY
        fn = lambda As, rs, vs: _pallas_sparse_values(
            As, rs, vs, batched=entry.batched, precision=precision,
            geometry=DEFAULT_GEOMETRY, interpret=True)
        if entry.batched:
            return fn, (_sds((B, n, n), dt), _sds((B, n, MAXDEG), i32),
                        _sds((B, n, MAXDEG), dt))
        return fn, (_sds((n, n), dt), _sds((n, MAXDEG), i32),
                    _sds((n, MAXDEG), dt))

    # campaign wave bodies: the per-device program run under shard_map
    # by slice_sums_on_mesh/permanent_on_mesh, with a *traced* chunk
    # base -- one program for every device.  A complex matrix travels
    # as its (re, im) real planes.
    from ..core import distributed
    planes = 1 if entry.dtype == "f64" else 2
    body = distributed._dyn_chunk_partials if entry.engine == "jnp" \
        else distributed._pallas_device_partials
    fn = lambda planes, fc: body(planes, fc, CPS, CHUNK, precision)
    return fn, ((_sds((n, n), f64),) * planes, _sds((), i32))


def trace_entry(entry: Entry, precision: str, B: int = CANON_B):
    """ClosedJaxpr of one entry at one precision/batch extent.  Abstract
    tracing only -- no device buffers, no compilation."""
    import jax
    fn, args = _build(entry, precision, B)
    return jax.make_jaxpr(fn)(*args)


# ---------------------------------------------------------------------------
# Canonical rendering
# ---------------------------------------------------------------------------

_DTYPE_SHORT = {
    "float16": "f16", "bfloat16": "bf16", "float32": "f32",
    "float64": "f64", "complex64": "c64", "complex128": "c128",
    "int8": "i8", "int16": "i16", "int32": "i32", "int64": "i64",
    "uint8": "u8", "uint16": "u16", "uint32": "u32", "uint64": "u64",
    "bool": "pred",
}

_ADDR = re.compile(r"0x[0-9a-fA-F]+")
# pallas NameAndSrcInfo embeds "at <abs path>:<line>" -- a source
# location whose spelling depends on sys.path/checkout and whose line
# shifts on unrelated edits; canonical text must carry neither.
_SRC_INFO = re.compile(r"\bat [^\s']+\.py:\d+")


def _short_dtype(dtype) -> str:
    import numpy as np
    name = np.dtype(dtype).name
    return _DTYPE_SHORT.get(name, name)


def _aval_str(aval) -> str:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return _ADDR.sub("<addr>", str(aval))
    dims = ",".join(str(d) for d in shape)
    return f"{_short_dtype(aval.dtype)}[{dims}]"


def _is_jaxpr(v) -> bool:
    from jax.extend import core
    return isinstance(v, (core.Jaxpr, core.ClosedJaxpr))


def _sanitize(v, subs: list) -> str:
    """Deterministic, address-free rendering of one eqn param value.
    Sub-jaxprs are collected into ``subs`` and rendered beneath the
    eqn; callables render by name only."""
    import numpy as np
    if _is_jaxpr(v):
        subs.append(v)
        return f"jaxpr<{len(subs) - 1}>"
    if v is None or isinstance(v, (bool, np.bool_)):
        return str(v)
    if isinstance(v, (int, np.integer)):
        return str(int(v))
    if isinstance(v, (float, np.floating)):
        return repr(float(v))
    if isinstance(v, complex):
        return repr(v)
    if isinstance(v, str):
        return repr(_SRC_INFO.sub("at <src>", v))
    if isinstance(v, np.dtype):
        return _short_dtype(v)
    if isinstance(v, type) and issubclass(v, np.generic):
        return _short_dtype(v)
    if isinstance(v, np.ndarray):
        digest = hashlib.sha256(
            np.ascontiguousarray(v).tobytes()).hexdigest()[:12]
        return (f"ndarray({_short_dtype(v.dtype)}"
                f"[{','.join(map(str, v.shape))}] sha={digest})")
    if isinstance(v, (tuple, list)):
        body = ",".join(_sanitize(x, subs) for x in v)
        return f"({body})"
    if isinstance(v, dict):
        body = ",".join(f"{k}:{_sanitize(x, subs)}"
                        for k, x in sorted(v.items(), key=lambda kv:
                                           str(kv[0])))
        return "{" + body + "}"
    if isinstance(v, (set, frozenset)):
        body = ",".join(sorted(_sanitize(x, subs) for x in v))
        return "{" + body + "}"
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        body = ",".join(
            f"{f.name}={_sanitize(getattr(v, f.name), subs)}"
            for f in sorted(dataclasses.fields(v), key=lambda f: f.name))
        return f"{type(v).__name__}({body})"
    if callable(v):
        return f"fn:{getattr(v, '__name__', type(v).__name__)}"
    clean = _SRC_INFO.sub("at <src>", _ADDR.sub("<addr>", repr(v)))
    return f"<{type(v).__name__}:{clean}>"


class _Walk:
    """Accumulates canonical lines plus the contract records."""

    def __init__(self):
        self.lines: list[str] = []
        self.reduces: list[contracts.ReduceRecord] = []
        self.converts: list[contracts.ConvertRecord] = []
        self._eqn_index = 0


def _reduced_extents(eqn) -> tuple[int, ...]:
    """Extents of the contracted axes of a reduce/dot eqn."""
    name = eqn.primitive.name
    shape = tuple(eqn.invars[0].aval.shape)
    if name in ("reduce_sum", "reduce_prod", "reduce_max", "reduce_min"):
        axes = eqn.params.get("axes", ())
        return tuple(shape[a] for a in axes)
    if name == "dot_general":
        (lhs_c, _), _ = eqn.params["dimension_numbers"]
        return tuple(shape[a] for a in lhs_c)
    return ()


def _render_jaxpr(jaxpr, consts, walk: _Walk, depth: int):
    import numpy as np
    from jax.extend.core import ClosedJaxpr, Literal
    pad = "  " * depth
    names: dict = {}

    def vname(v):
        if isinstance(v, Literal):
            return f"lit({_sanitize(np.asarray(v.val).item() if np.ndim(v.val) == 0 else np.asarray(v.val), [])}:{_aval_str(v.aval)})"
        if type(v).__name__ == "DropVar":
            return "_"
        if v not in names:
            names[v] = f"v{len(names)}"
        return names[v]

    for cv, cval in zip(jaxpr.constvars, consts):
        if cval is None:
            walk.lines.append(f"{pad}const {vname(cv)}:{_aval_str(cv.aval)}")
        else:
            arr = np.asarray(cval)
            digest = hashlib.sha256(
                np.ascontiguousarray(arr).tobytes()).hexdigest()[:12]
            walk.lines.append(
                f"{pad}const {vname(cv)}:{_aval_str(cv.aval)} sha={digest}")
    walk.lines.append(pad + "in " + " ".join(
        f"{vname(v)}:{_aval_str(v.aval)}" for v in jaxpr.invars))

    for eqn in jaxpr.eqns:
        subs: list = []
        params = ",".join(f"{k}={_sanitize(v, subs)}"
                          for k, v in sorted(eqn.params.items()))
        ins = " ".join(vname(v) for v in eqn.invars)
        outs = " ".join(f"{vname(v)}:{_aval_str(v.aval)}"
                        for v in eqn.outvars)
        name = eqn.primitive.name
        idx = walk._eqn_index
        walk._eqn_index += 1
        walk.lines.append(f"{pad}{outs} = {name}[{params}] {ins}")

        if eqn.invars and not isinstance(eqn.invars[0], Literal):
            in_aval = eqn.invars[0].aval
            short = _short_dtype(getattr(in_aval, "dtype", np.int32)) \
                if hasattr(in_aval, "dtype") else "?"
            ext = _reduced_extents(eqn)
            if ext and contracts._is_floatish(short):
                walk.reduces.append(contracts.ReduceRecord(
                    index=idx, primitive=name, dtype=short,
                    reduced_extents=ext))
            if name == "convert_element_type":
                walk.converts.append(contracts.ConvertRecord(
                    index=idx, src=short,
                    dst=_short_dtype(eqn.outvars[0].aval.dtype)))

        for sub in subs:
            if isinstance(sub, ClosedJaxpr):
                _render_jaxpr(sub.jaxpr, sub.consts, walk, depth + 1)
            else:
                _render_jaxpr(sub, [None] * len(sub.constvars), walk,
                              depth + 1)

    walk.lines.append(pad + "out " + " ".join(
        vname(v) for v in jaxpr.outvars))


def canonical_walk(closed) -> _Walk:
    walk = _Walk()
    _render_jaxpr(closed.jaxpr, closed.consts, walk, 0)
    return walk


def canonical_lines(closed) -> list[str]:
    return canonical_walk(closed).lines


def fingerprint(lines: list[str]) -> str:
    return hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# Goldens
# ---------------------------------------------------------------------------

def golden_path(entry: Entry, golden_dir: str | None = None) -> str:
    return os.path.join(golden_dir or GOLDEN_DIR, entry.name + ".golden")


def _jax_version() -> str:
    import jax
    return jax.__version__


def render_golden(entry: Entry,
                  sections: dict[str, tuple[str, list[str] | None]]) -> str:
    """Golden file text: per-precision fingerprints, plus the canonical
    trace verbatim for TEXT_PRECISION (the diffable precision)."""
    head = [
        "# permprove golden -- machine-generated; regenerate with",
        "#   PYTHONPATH=src python -m repro.analysis.ir --bless",
        f"version: {VERSION}",
        f"jax: {_jax_version()}",
        f"entry: {entry.name}",
        f"n: {N} num_chunks: {NUM_CHUNKS} batch: {CANON_B} "
        f"maxdeg: {MAXDEG} wave: {CPS}x{CHUNK}",
    ]
    body = []
    for prec in PRECISIONS:
        fp, lines = sections[prec]
        body.append(f"== precision={prec} fingerprint={fp}")
        if lines is not None:
            body.extend(lines)
    return "\n".join(head + body) + "\n"


def parse_golden(text: str) -> dict:
    """-> {"jax": str, "sections": {prec: (fingerprint, lines|None)}}"""
    jax_ver = None
    sections: dict[str, tuple[str, list[str] | None]] = {}
    cur = None
    for line in text.splitlines():
        if line.startswith("jax: "):
            jax_ver = line[len("jax: "):].strip()
        m = re.match(r"== precision=(\S+) fingerprint=(\S+)", line)
        if m:
            cur = m.group(1)
            sections[cur] = (m.group(2), [])
            continue
        if cur is not None:
            fp, lines = sections[cur]
            lines.append(line)
    sections = {p: (fp, lines if lines else None)
                for p, (fp, lines) in sections.items()}
    return {"jax": jax_ver, "sections": sections}


# ---------------------------------------------------------------------------
# The prove pass
# ---------------------------------------------------------------------------

def _select(pattern: str | None) -> list[Entry]:
    if not pattern:
        return list(ENTRIES)
    return [e for e in ENTRIES if fnmatch.fnmatch(e.name, pattern)]


def _entry_walks(entry: Entry, log=None):
    """{precision: walk} at CANON_B plus {precision: walk} at ALT_B for
    batch entries (None otherwise)."""
    walks, alt_walks = {}, {}
    for prec in PRECISIONS:
        walks[prec] = canonical_walk(trace_entry(entry, prec, CANON_B))
        if entry.batched:
            alt_walks[prec] = canonical_walk(
                trace_entry(entry, prec, ALT_B))
    if log:
        log(f"  traced {entry.name} ({len(walks[TEXT_PRECISION].lines)} "
            f"canonical lines)")
    return walks, (alt_walks if entry.batched else None)


def _contract_findings(entry: Entry, walks, alt_walks) -> list[Finding]:
    found: list[Finding] = []
    for prec, w in walks.items():
        found += contracts.pli102_dtype_flow(entry.name, w.converts, prec)
        if alt_walks is not None:
            aw = alt_walks[prec]
            found += contracts.pli103_batch_invariance(
                entry.name, prec, w.lines, aw.lines, CANON_B, ALT_B)
            found += contracts.pli101_reductions(
                entry.name, prec, w.reduces, aw.reduces, CANON_B, ALT_B)
    return found


def _mesh_programs(log=None):
    """Compiled HLO of every sharded program + its sanctioned collective
    budget, or None (-> PLI104 skipped) when <2 devices are visible.

    Abstract ``.lower().compile()`` only -- no data touches a device.
    """
    import numpy as np
    import jax
    devs = jax.devices()
    if len(devs) < 2:
        return None
    from jax.sharding import Mesh
    from ..core import distributed
    from ..core.ryser import chunk_geometry

    D = len(devs[:8])
    mesh = Mesh(np.asarray(devs[:8]), ("d",))
    T, C, _ = chunk_geometry(N, NUM_CHUNKS)
    f64, i32 = np.float64, np.int32
    A = (_sds((N, N), f64),)
    Ac = (_sds((N, N), f64),) * 2      # a complex matrix's real planes
    sl = _sds((D, 1), i32)

    progs = []

    def lower(name, fn, args, sanctioned):
        if log:
            log(f"  compiling mesh program {name}")
        txt = fn.lower(*args).compile().as_text()
        progs.append((name, txt, sanctioned))

    ONE_PSUM = {"all-reduce": 2}      # one (hi, lo) twofloat psum pair
    NONE = {}
    lower("mesh.wave_jnp",
          distributed._wave_fn(mesh, CPS, CHUNK, "dq_acc", "jnp", None),
          (A, sl), NONE)
    lower("mesh.wave_pallas",
          distributed._wave_fn(mesh, CPS, CHUNK, "dq_acc", "pallas", None),
          (A, sl), NONE)
    lower("mesh.oneshot_jnp",
          distributed._oneshot_mesh_fn(mesh, 1, CPS, CHUNK, "dq_acc",
                                       "jnp"),
          (A, sl, _sds((D, 1), f64)), ONE_PSUM)
    lower("mesh.oneshot_pallas",
          distributed._oneshot_mesh_fn(mesh, 1, CPS, CHUNK, "dq_acc",
                                       "pallas"),
          (Ac, sl, _sds((D, 1), f64)), ONE_PSUM)
    lower("mesh.dense_batch",
          distributed._dense_batch_mesh_fn(mesh, T, C, "dq_acc"),
          (_sds((D, N, N), f64),), NONE)
    lower("mesh.sparse_batch",
          distributed._sparse_batch_mesh_fn(mesh, T, C, "dq_acc"),
          (_sds((D, N, N), f64), _sds((D, N, MAXDEG), i32),
           _sds((D, N, MAXDEG), f64)),
          NONE)
    return progs


def run_check(entries_pattern: str | None = None,
              golden_dir: str | None = None, bless_mode: bool = False,
              with_mesh: bool = True, log=None) -> dict:
    """Trace, check contracts, and gate (or bless) goldens.

    Returns the report dict (``version``/``entries``/``findings``/
    ``suppressions``/``goldens``/``mesh``).
    """
    import jax
    jax.config.update("jax_enable_x64", True)

    gdir = golden_dir or GOLDEN_DIR
    selected = _select(entries_pattern)
    findings: list[Finding] = []
    drifted: list[dict] = []
    missing: list[str] = []
    blessed: list[str] = []
    golden_skip = None

    for entry in selected:
        walks, alt_walks = _entry_walks(entry, log)
        findings += _contract_findings(entry, walks, alt_walks)

        sections = {
            p: (fingerprint(w.lines),
                w.lines if p == TEXT_PRECISION else None)
            for p, w in walks.items()}
        gpath = golden_path(entry, gdir)
        if bless_mode:
            os.makedirs(gdir, exist_ok=True)
            with open(gpath, "w", encoding="utf-8") as f:
                f.write(render_golden(entry, sections))
            blessed.append(entry.name)
            continue
        if not os.path.exists(gpath):
            missing.append(entry.name)
            continue
        with open(gpath, encoding="utf-8") as f:
            gold = parse_golden(f.read())
        if gold["jax"] != _jax_version():
            golden_skip = (f"goldens blessed under jax {gold['jax']} "
                           f"but running {_jax_version()}; fingerprint "
                           f"gate skipped (contract rules still ran)")
            continue
        for prec in PRECISIONS:
            got_fp, got_lines = sections[prec]
            want_fp, want_lines = gold["sections"].get(prec, (None, None))
            if want_fp == got_fp:
                continue
            diff = None
            if want_lines is not None and got_lines is not None:
                diff = "\n".join(difflib.unified_diff(
                    want_lines, got_lines, fromfile=f"golden/{prec}",
                    tofile=f"traced/{prec}", lineterm="", n=2))
            drifted.append({"entry": entry.name, "precision": prec,
                            "want": want_fp, "got": got_fp,
                            "diff": diff})

    mesh_report: dict = {"checked": 0, "skipped": None}
    if with_mesh and not bless_mode:
        progs = _mesh_programs(log)
        if progs is None:
            mesh_report["skipped"] = ("single visible device; run via "
                                      "the CLI (forces 8 host devices) "
                                      "for the PLI104 collective audit")
        else:
            for name, txt, sanctioned in progs:
                findings += contracts.pli104_collectives(
                    name, txt, sanctioned)
            mesh_report["checked"] = len(progs)

    pre_suppressed = [f for f in findings if f.suppressed]
    active, suppressed = contracts.apply_sanctions(
        [f for f in findings if not f.suppressed])
    suppressed += pre_suppressed
    return {
        "version": VERSION,
        "entries": [e.name for e in selected],
        "findings": active,
        "suppressions": suppressed,
        "goldens": {"dir": gdir, "drifted": drifted, "missing": missing,
                    "blessed": blessed, "skipped": golden_skip},
        "mesh": mesh_report,
    }


def bless(entries_pattern: str | None = None,
          golden_dir: str | None = None, log=None) -> dict:
    return run_check(entries_pattern, golden_dir, bless_mode=True,
                     with_mesh=False, log=log)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _report_json(report: dict) -> dict:
    out = dict(report)
    out["findings"] = [f.to_json() for f in report["findings"]]
    out["suppressions"] = [f.to_json() for f in report["suppressions"]]
    return out


def _print_report(report: dict) -> None:
    for f in report["findings"]:
        print(f.render())
    g = report["goldens"]
    for d in g["drifted"]:
        print(f"GOLDEN DRIFT {d['entry']} precision={d['precision']}: "
              f"fingerprint {d['want']} -> {d['got']}")
        if d["diff"]:
            print(d["diff"])
        else:
            print(f"  (canonical text stored for "
                  f"precision={TEXT_PRECISION} only; re-run with "
                  f"--bless in a scratch tree to inspect)")
    for name in g["missing"]:
        print(f"GOLDEN MISSING {name}: no {golden_path_name(name)} -- "
              f"run --bless and commit the result")
    if g["skipped"]:
        print(f"note: {g['skipped']}")
    if report["mesh"]["skipped"]:
        print(f"note: PLI104 {report['mesh']['skipped']}")
    n_f, n_s = len(report["findings"]), len(report["suppressions"])
    n_d = len(g["drifted"]) + len(g["missing"])
    print(f"permprove: {len(report['entries'])} entries x "
          f"{len(PRECISIONS)} precisions, {n_f} finding(s), "
          f"{n_s} sanctioned suppression(s), {n_d} golden problem(s), "
          f"{report['mesh']['checked']} mesh program(s) audited")


def golden_path_name(entry_name: str) -> str:
    return os.path.join("tests", "ir_goldens", entry_name + ".golden")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.ir", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--check", action="store_true",
                    help="trace all entries, check PLI contracts and "
                         "golden fingerprints")
    ap.add_argument("--bless", action="store_true",
                    help="regenerate the goldens from the current tree")
    ap.add_argument("--entries", default=None, metavar="PATTERN",
                    help="fnmatch filter over entry names "
                         "(e.g. 'dense_jnp.*')")
    ap.add_argument("--goldens", default=None, metavar="DIR",
                    help=f"golden directory (default {GOLDEN_DIR})")
    ap.add_argument("--no-mesh", action="store_true",
                    help="skip the PLI104 compiled-mesh collective audit")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as JSON on stdout")
    ap.add_argument("--report", default=None, metavar="PATH",
                    help="also write the JSON report to PATH")
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)

    if not (args.check or args.bless):
        ap.print_usage()
        return 2
    if args.entries and not _select(args.entries):
        print(f"no entries match {args.entries!r}", file=sys.stderr)
        return 2

    log = None if (args.quiet or args.json) else print
    if args.bless:
        report = bless(args.entries, args.goldens, log=log)
    else:
        report = run_check(args.entries, args.goldens,
                           with_mesh=not args.no_mesh, log=log)

    payload = _report_json(report)
    if args.report:
        with open(args.report, "w", encoding="utf-8") as f:
            json.dump(payload, f, indent=2)
    if args.json:
        json.dump(payload, sys.stdout, indent=2)
        print()
    else:
        if args.bless:
            for name in report["goldens"]["blessed"]:
                print(f"blessed {golden_path_name(name)}")
        else:
            _print_report(report)

    bad = (report["findings"] or report["goldens"]["drifted"]
           or report["goldens"]["missing"])
    return 1 if bad else 0


if __name__ == "__main__":
    # Force a multi-device host platform BEFORE jax loads so the PLI104
    # collective audit compiles against a real mesh on CPU.
    if "jax" not in sys.modules \
            and "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8").strip()
    sys.exit(main())
