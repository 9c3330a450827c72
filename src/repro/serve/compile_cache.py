"""Persistent XLA compilation cache wiring + kernel-geometry warm-up.

A cold serving process pays a retrace storm: every (batch, n) bucket
geometry it meets traces and XLA-compiles before the first result comes
back.  Two layers fix that:

* :func:`enable_compile_cache` points ``jax``'s persistent compilation
  cache (thresholds zeroed so every executable persists) at an on-disk
  directory -- ``JAX_COMPILATION_CACHE_DIR`` when the environment sets
  it, else a fixed directory in the checkout -- keyed the same way
  ``core/cache.py`` keys results: by content, here the HLO + compile
  options, so identical programs across process restarts load their
  executable from disk instead of re-invoking XLA.
* :func:`warmup` runs the serve plan's kernel geometries -- every
  (n, device-batch, dtype) bucket program the loop can dispatch -- through
  a throwaway solver before traffic is admitted.  Tracing happens once,
  up front; with a warm disk cache the XLA compile step is a cache hit,
  so a restarted process serves its first bucket with zero compiles.
  The throwaway solver plans with the *serving* config, so when
  ``SolverConfig.tuning_table`` is set the planner resolves the tuned
  kernel geometry per bucket and the warmed programs ARE the tuned
  ones -- a tuned service serves its first bucket with zero XLA
  compiles, same as an untuned one (``benchmarks/serve_soak.py`` gates
  this across two cold processes).

:func:`compile_stats` exposes jax's compilation-cache monitoring events
(requests / persistent hits / persistent misses) as plain counters; the
soak benchmark compares them across two cold starts to prove the
first-bucket-without-recompiling property, and ``serve/metrics.py``
embeds them in its snapshot schema.
"""

from __future__ import annotations

import os
import time
from typing import Sequence

import numpy as np

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache",
           "install_compile_listener",
           "compile_stats", "reset_compile_stats", "warmup",
           "quantized_batches"]

# jax monitoring event names -> our counter keys
_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "persistent_hits",
    "/jax/compilation_cache/cache_misses": "persistent_misses",
}

_counts = {v: 0 for v in _EVENTS.values()}
_installed = False


def _listener(event: str, **kwargs) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        _counts[key] += 1


def install_compile_listener() -> None:
    """Idempotently register the jax monitoring listener backing
    :func:`compile_stats`."""
    global _installed
    if _installed:
        return
    from jax._src import monitoring
    monitoring.register_event_listener(_listener)
    _installed = True


def compile_stats() -> dict:
    """Cumulative persistent-compilation-cache counters for this process.

    ``requests`` counts XLA compiles that consulted the persistent
    cache; each was either a ``persistent_hits`` (executable loaded from
    disk) or a ``persistent_misses`` (really compiled, then stored).
    All zero until :func:`enable_compile_cache` ran.
    """
    return dict(_counts)


def reset_compile_stats() -> None:
    for k in _counts:
        _counts[k] = 0


# The default cache directory: fixed (a cache that moves never hits) and
# inside the checkout (listed in .gitignore), so nothing is read or
# written outside the tree.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache(path: str | None = None) -> str:
    """Turn on jax's persistent compilation cache and start counting
    cache events.  Returns the cache directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: jax reads
    the variable itself, and no other directory is configured in its
    place (``path`` is ignored).  Otherwise the cache lives at ``path``,
    or by default at :data:`DEFAULT_CACHE_DIR`.
    """
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        path = os.path.abspath(os.path.expanduser(env))
        os.makedirs(path, exist_ok=True)
    else:
        path = os.path.abspath(os.path.expanduser(path or DEFAULT_CACHE_DIR))
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # persist everything: the bucket programs this service compiles are
    # small and hot, and the default thresholds would skip exactly the
    # tiny-n programs the retrace storm is made of
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    install_compile_listener()
    return path


def quantized_batches(max_batch: int) -> tuple[int, ...]:
    """The device-batch sizes the serve loop dispatches: powers of two up
    to (and including, when itself a power of two) ``max_batch``, capped
    at the next power of two otherwise.

    Quantizing dispatch sizes bounds the trace space -- continuous
    batching produces arbitrary partial buckets, and every distinct
    (B, n, n) shape is its own trace+compile.  The loop pads a partial
    bucket up to the next size in this ladder.
    """
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    out = []
    b = 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(b)                    # next pow2 >= max_batch
    return tuple(out)


def warmup(config, geometries: Sequence[tuple], *,
           distributed_ctx=None, seed: int = 0,
           progress=None) -> dict:
    """Trace + compile every bucket program in ``geometries`` before
    traffic arrives.

    ``config`` is the serving :class:`~repro.core.planner.SolverConfig`;
    ``geometries`` is an iterable of ``(n, batch)`` or
    ``(n, batch, is_complex)`` tuples -- typically every ``n`` the
    service expects crossed with :func:`quantized_batches`.  Runs each
    geometry once through a throwaway solver (result cache off, so the
    synthetic warm-up matrices never pollute the serving cache; the jit
    and persistent-compile caches warmed here are process/disk-global).
    The solver keeps the serving config's ``geometry`` override and
    ``tuning_table`` -- bucket programs are planned with the same
    resolved kernel geometry the live loop will dispatch, so tuning
    never reintroduces a first-bucket compile.
    Returns ``{"geometries", "seconds", "compile"}`` where ``compile`` is
    the :func:`compile_stats` delta of the pass.
    """
    from ..core.solver import PermanentSolver

    solver = PermanentSolver(config.replace(cache=False),
                             distributed_ctx=distributed_ctx)
    rng = np.random.default_rng(seed)
    before = compile_stats()
    t0 = time.perf_counter()
    done = 0
    for geom in geometries:
        n, batch = geom[0], geom[1]
        is_complex = bool(geom[2]) if len(geom) > 2 else False
        mats = rng.uniform(-1.0, 1.0, (batch, n, n))
        if is_complex:
            mats = mats + 1j * rng.uniform(-1.0, 1.0, (batch, n, n))
        solver.execute(solver.plan_batch(list(mats)))
        done += 1
        if progress is not None:
            progress(n, batch, is_complex)
    after = compile_stats()
    return {"geometries": done,
            "seconds": time.perf_counter() - t0,
            "compile": {k: after[k] - before[k] for k in after}}
