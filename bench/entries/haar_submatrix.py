"""Requests of boson sampling: submatrices of a Haar-random interferometer.

One Haar-random ``modes``-mode unitary per seed; a request is its first
``n`` columns at ``n`` distinct rows drawn uniformly, the amplitude
matrix of one collision-free output pattern.  Complex whatever the
configuration says.
"""

from __future__ import annotations

import math

import numpy as np

import traffic


def haar_unitary(m: int, rng: np.random.Generator) -> np.ndarray:
    """Haar-random m x m unitary: QR of a complex Gaussian matrix with the
    phases of R's diagonal moved into Q (Mezzadri 2007)."""
    z = (rng.standard_normal((m, m))
         + 1j * rng.standard_normal((m, m))) / math.sqrt(2)
    q, r = np.linalg.qr(z)
    d = np.diag(r)
    return q * (d / np.abs(d))


class Source:
    is_complex = True

    def __init__(self, config: dict, seed: int):
        self.n = int(config["n"])
        m = int(config["modes"])
        if m < self.n:
            raise ValueError(f"need modes >= n, got {m} < {self.n}")
        self.columns = haar_unitary(m, traffic.stream(seed, "data"))[:, :self.n]

    def draw(self, count: int, rng: np.random.Generator) -> np.ndarray:
        """(count, n, n) request matrices."""
        n = self.n
        # n distinct rows per request, uniform: the n smallest of m keys
        keys = rng.random((count, self.columns.shape[0]))
        rows = np.sort(np.argpartition(keys, n - 1, axis=1)[:, :n], axis=1)
        return self.columns[rows]
