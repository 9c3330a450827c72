"""Algorithmic work of a permanent, whatever code computes it.

Ryser's formula over a Gray code visits 2^(n-1) sign patterns (the
Nijenhuis-Wilf half of the 2^n subsets) and at each one updates n row
sums (n additions) and multiplies them together (n - 1 multiplications).
A real addition or multiplication is one flop; a complex addition is 2
real flops and a complex multiplication 6.  Precision does not enter:
emulated or compensated arithmetic does more machine work for the same
algorithmic work, and that is what a rate over this count shows.
"""

from __future__ import annotations

__all__ = ["ryser_flops"]


def ryser_flops(n: int, is_complex: bool) -> float:
    """Algorithmic flops of one n x n permanent."""
    if n < 1:
        raise ValueError(f"n must be >= 1, got {n}")
    add, mul = (2, 6) if is_complex else (1, 1)
    return float(2 ** (n - 1)) * (n * add + (n - 1) * mul)
