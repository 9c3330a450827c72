"""Requests of record-scale single permanents: dense real matrices with
entries uniform on [0, 1), one fresh matrix per job.

Entries of one sign keep every permanent far from zero, so a relative
error says how well it was computed; a sign-symmetric distribution now
and then draws a matrix whose permanent nearly cancels.  The work per
Gray step does not depend on the values.  Real whatever the
configuration says.
"""

from __future__ import annotations

import numpy as np


class Source:
    is_complex = False

    def __init__(self, config: dict, seed: int):
        self.n = int(config["n"])

    def draw(self, count: int, rng: np.random.Generator) -> np.ndarray:
        """(count, n, n) request matrices."""
        return rng.random((count, self.n, self.n))
