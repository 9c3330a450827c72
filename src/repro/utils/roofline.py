"""Three-term roofline model over a small hardware-spec registry.

    compute term    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory term     = HLO_bytes / (chips * HBM_bw)
    collective term = collective_bytes / (chips * link_bw)

FLOPs / bytes come from ``compiled.cost_analysis()`` (whole-program, i.e.
all devices together -- divided by the chip count here); collective bytes
from utils/hlo.py (per-participant already -- NOT divided again).

Hardware is resolved by name through :data:`HW_SPECS`:
:func:`detect_hw` maps ``jax.devices()[0].device_kind`` onto a registered
spec (explicitly overridable via its argument or the ``REPRO_HW``
environment variable), so the tuner's pruning model and the roofline
report stop assuming v5e.  An unknown kind or name raises: no code path
picks a device spec by default.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, asdict, field

__all__ = ["HwSpec", "HW_SPECS", "HW_V5E", "detect_hw", "get_hw",
           "register_hw", "Roofline", "roofline_from_analysis"]


@dataclass(frozen=True)
class HwSpec:
    name: str
    peak_flops: float      # FLOP/s per chip (bf16)
    hbm_bw: float          # bytes/s per chip
    ici_bw: float          # bytes/s per link


HW_V5E = HwSpec(name="tpu-v5e", peak_flops=197e12, hbm_bw=819e9,
                ici_bw=50e9)

# Registered specs, keyed by canonical name.  Numbers are public
# per-chip peaks (bf16 matmul FLOP/s, HBM bytes/s, per-link ICI
# bytes/s); "cpu" is a deliberately rough host-interpreter stand-in so
# interpret-mode tuning still ranks geometry by arithmetic/byte volume.
HW_SPECS: dict[str, HwSpec] = {
    "tpu-v4": HwSpec(name="tpu-v4", peak_flops=275e12, hbm_bw=1228e9,
                     ici_bw=50e9),
    "tpu-v5e": HW_V5E,
    "tpu-v5p": HwSpec(name="tpu-v5p", peak_flops=459e12, hbm_bw=2765e9,
                      ici_bw=100e9),
    "tpu-v6e": HwSpec(name="tpu-v6e", peak_flops=918e12, hbm_bw=1640e9,
                      ici_bw=100e9),
    "cpu": HwSpec(name="cpu", peak_flops=100e9, hbm_bw=20e9, ici_bw=10e9),
}

# device_kind substrings -> registry keys, checked in order (the kind
# strings vary across jax versions: "TPU v5e", "TPU v5 lite", ...).
_KIND_PATTERNS = (
    ("v5 lite", "tpu-v5e"), ("v5e", "tpu-v5e"), ("v5p", "tpu-v5p"),
    ("v6", "tpu-v6e"), ("trillium", "tpu-v6e"), ("v4", "tpu-v4"),
    ("cpu", "cpu"),
)


def register_hw(spec: HwSpec) -> None:
    HW_SPECS[spec.name] = spec


def get_hw(name: str) -> HwSpec:
    """Spec by registry name; an unknown name raises ``KeyError``."""
    try:
        return HW_SPECS[name]
    except KeyError:
        raise KeyError(f"no hardware spec named {name!r}; registered: "
                       f"{sorted(HW_SPECS)}") from None


def detect_hw(device_kind: str | None = None) -> HwSpec:
    """Resolve the HwSpec for this host.

    Precedence: explicit ``device_kind`` argument > ``REPRO_HW``
    environment override (a registry name) > ``jax.devices()[0]``
    autodetection.  A kind no pattern matches raises ``ValueError``
    (register its spec with :func:`register_hw`): a roofline against
    another chip's peaks is wrong, not approximate.
    """
    override = os.environ.get("REPRO_HW")
    if device_kind is None and override:
        return get_hw(override)
    kind = device_kind
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    low = str(kind).lower()
    if low in HW_SPECS:
        return HW_SPECS[low]
    for pat, name in _KIND_PATTERNS:
        if pat in low:
            return HW_SPECS[name]
    raise ValueError(f"no hardware spec for device kind {kind!r}; "
                     f"register one with register_hw()")


@dataclass
class Roofline:
    flops: float               # whole-program HLO flops (all chips)
    bytes_accessed: float      # whole-program HLO bytes (unfused upper bd)
    collective_bytes: float    # per-participant collective bytes
    chips: int
    model_flops: float = 0.0   # 6 N D (dense) / 6 N_active D (MoE)
    bytes_min: float = 0.0     # per-device argument+output traffic
                               # (fusion-optimal lower bound)
    hw: str = field(kw_only=True)   # HW_SPECS name

    @property
    def spec(self) -> HwSpec:
        return get_hw(self.hw)

    @property
    def compute_s(self) -> float:
        return self.flops / (self.chips * self.spec.peak_flops)

    @property
    def memory_s(self) -> float:
        """Fusion-optimal bound: every input/output buffer touched once.
        (the unfused-HLO upper bound is memory_s_hlo)"""
        if self.bytes_min:
            return self.bytes_min / self.spec.hbm_bw
        return self.memory_s_hlo

    @property
    def memory_s_hlo(self) -> float:
        return self.bytes_accessed / (self.chips * self.spec.hbm_bw)

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / self.spec.ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """No-overlap upper bound: max of the three terms (perfect overlap)
        is the roofline; we report the max term as the bound."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def mfu_bound(self) -> float:
        """Model-FLOPs utilization at the roofline bound."""
        t = self.step_time_s
        if not t:
            return 0.0
        return self.model_flops / (t * self.chips * self.spec.peak_flops)

    def to_dict(self) -> dict:
        d = asdict(self)
        d.update(compute_s=self.compute_s, memory_s=self.memory_s,
                 memory_s_hlo=self.memory_s_hlo,
                 collective_s=self.collective_s, dominant=self.dominant,
                 step_time_s=self.step_time_s,
                 useful_flops_ratio=self.useful_flops_ratio,
                 mfu_bound=self.mfu_bound)
        return d


def roofline_from_analysis(cost: dict, coll_bytes: float, chips: int,
                           model_flops: float, bytes_min: float = 0.0,
                           hw: str | None = None) -> Roofline:
    return Roofline(
        flops=float(cost.get("flops", 0.0)),
        bytes_accessed=float(cost.get("bytes accessed", 0.0)),
        collective_bytes=float(coll_bytes),
        chips=chips, model_flops=model_flops, bytes_min=bytes_min,
        hw=hw or detect_hw().name)
