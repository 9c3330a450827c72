"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.  A kind that is not here is an error,
never a default.

TPU v5e (JAX names it "TPU v5 lite"), from Google Cloud's documentation
page "TPU v5e": 197 TFLOP/s bf16 and 393 TOP/s int8 on the matrix
units, 16 GB of HBM2 at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect.  No peak is published for float32 or emulated float64
work on the vector units, which is the work of Ryser's product chains;
so no roofline share of that work is reported (``vpu_f32_flops`` is
None and a reader that would need it returns nothing).
"""

from __future__ import annotations

__all__ = ["PEAKS", "peaks_for", "UnknownDevice"]

PEAKS = {
    "TPU v5 lite": {
        "source": "Google Cloud documentation, 'TPU v5e' "
                  "(cloud.google.com/tpu/docs/v5e)",
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "vpu_f32_flops": None,
    },
}


class UnknownDevice(LookupError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in the peaks table "
            f"({sorted(PEAKS)}); add its published figures to "
            f"bench/peaks.py") from None
