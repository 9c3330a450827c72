"""Work counts, the peaks table and the plain reference."""

import itertools
import math

import numpy as np
import pytest

import peaks
import reference
import workcount


def _glynn_loops(A) -> complex:
    """Glynn's formula written out term by term, for tiny n."""
    n = A.shape[0]
    total = 0
    for rest in itertools.product((1, -1), repeat=n - 1):
        d = (1,) + rest
        term = math.prod(d)
        for i in range(n):
            term *= sum(d[j] * A[i, j] for j in range(n))
        total += term
    return total / 2 ** (n - 1)


@pytest.mark.parametrize("n,is_complex,flops", [
    (1, False, 1), (2, False, 2 * (2 + 1)), (3, False, 4 * (3 + 2)),
    (2, True, 2 * (2 * 2 + 6 * 1)), (20, True, 2 ** 19 * 154),
    (31, False, 2 ** 30 * 61)])
def test_ryser_flops_hand_counts(n, is_complex, flops):
    assert workcount.ryser_flops(n, is_complex) == flops


def test_ryser_flops_rejects_empty():
    with pytest.raises(ValueError):
        workcount.ryser_flops(0, False)


def test_peaks_table_v5e_and_unknown_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["vpu_f32_flops"] is None and "TPU v5e" in p["source"]
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")


@pytest.mark.parametrize("n", [1, 2, 3, 5, 7])
@pytest.mark.parametrize("cplx", [False, True])
def test_reference_matches_term_by_term(n, cplx):
    rng = np.random.default_rng(n)
    A = rng.uniform(-1, 1, (n, n))
    if cplx:
        A = A + 1j * rng.uniform(-1, 1, (n, n))
    want = _glynn_loops(A)
    got = reference.permanent(A, low_bits=2)
    assert abs(got - want) <= 1e-13 * max(1.0, abs(want))


def test_reference_known_values():
    # perm(J_n) = n!, perm of a permutation matrix = 1
    assert reference.permanent(np.ones((9, 9))) == math.factorial(9)
    P = np.eye(8)[np.random.default_rng(0).permutation(8)]
    assert reference.permanent(P) == 1.0


def test_control_precision_is_float32():
    rng = np.random.default_rng(3)
    A = rng.uniform(-1, 1, (12, 12))
    ref = reference.permanent(A)
    ctl = reference.permanent(A, dtype=np.float32)
    gap = abs(ctl - ref) / abs(ref)
    assert 1e-9 < gap < 1e-3


@pytest.mark.parametrize("cell", ["boson_c20.serve", "boson_c20.batch"])
def test_control_fails_the_cells_limit(cell):
    """The float32 control, put in the program's place under a tiny run
    of the cell, makes the run's own comparison come out not correct."""
    import control
    import rehearse
    with control.in_program_place():
        out = rehearse.run_tiny(cell, seed=2 ** 31 + 3)
    assert out["correct"] is False
    c = out["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]
    assert out["checks"]["unanswered"]["value"] == 0
