"""The benchmark's general generator: random streams and arrivals.

Everything a run draws comes from ``--seed`` through :func:`stream`, one
independent stream per purpose, so the same seed gives the same inputs
and arrivals, and a different seed the same amount of work in another
order.  What one request is comes from the configuration's request kind
(``bench/entries/<entries>.py``); how requests arrive, from the traffic
file's loop (``bench/loops/<loop>.py``) and its numbers.
"""

from __future__ import annotations

import zlib

import numpy as np

__all__ = ["stream", "arrivals"]


def stream(seed: int, purpose: str) -> np.random.Generator:
    """The seed's stream for ``purpose``: a stream per name, so adding
    draws to one never shifts another, and a new name needs no table."""
    return np.random.default_rng([int(seed) % 2 ** 63,
                                  zlib.crc32(purpose.encode())])


def arrivals(rate_hz: float, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """Open-loop Poisson arrivals in [0, seconds), as offsets in seconds.

    The count is fixed at ``round(rate_hz * seconds)`` and the times are
    sorted uniforms: a Poisson process conditioned on its count.  Every
    seed then offers the same amount of work, in another order.
    """
    count = max(1, round(rate_hz * seconds))
    return np.sort(rng.uniform(0.0, seconds, count))
