"""Roofline terms for compiled dry-run artifacts (TPU v5e targets).

Per (arch × shape × mesh) cell:

  compute_s    = HLO_FLOPs   / (chips × 197e12)         [bf16 MXU peak]
  memory_s     = HLO_bytes   / (chips × 819e9)          [HBM]
  collective_s = wire_bytes  / (chips × 50e9)           [ICI per link]

``cost_analysis()`` on a post-SPMD module reports *per-device* flops/bytes, so
terms divide by 1 device; the helpers below normalize either convention via
``per_device`` — the dry-run stores raw values plus the convention used.

MODEL_FLOPS (6·N·D dense / 6·N_active·D MoE) over HLO_FLOPs measures how much
compiled compute is useful (catches remat & redundancy waste).
"""

from __future__ import annotations

import dataclasses

__all__ = ["ChipPeaks", "CHIP_PEAKS", "peaks_for",
           "local_peaks", "RooflineTerms", "compute_terms", "PEAK_FLOPS",
           "HBM_BW", "ICI_BW"]


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks."""

    flops: float   # bf16 matmul FLOP/s
    hbm_bw: float  # HBM bytes/s
    ici_bw: float  # chip-to-chip bytes/s per link


# THE peak table, keyed by ``jax.Device.device_kind`` as the runtime and the
# TPU compiler report it.  TPU v5e (Google Cloud documentation, "TPU v5e"):
# 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s interconnect over 4 links.
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}

# the dry-run's target chip, and the ranking weights on the CPU backend
_V5E = CHIP_PEAKS["TPU v5 lite"]
PEAK_FLOPS = _V5E.flops
HBM_BW = _V5E.hbm_bw
ICI_BW = _V5E.ici_bw


def peaks_for(backend: str, device_kind: str | None) -> ChipPeaks:
    """The table entry for ``device_kind``; a kind not in it is an error,
    never a default.  The CPU backend keeps the v5e constants: there they
    only weigh compute against traffic when ranking shapes, and the
    seconds they give are not CPU speeds."""
    if backend == "cpu":
        return _V5E
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak entry for device kind {device_kind!r}; "
                         f"known: {sorted(CHIP_PEAKS)}") from None


def local_peaks() -> ChipPeaks:
    """Peaks of this process's first JAX device."""
    import jax
    dev = jax.devices()[0]
    return peaks_for(dev.platform, dev.device_kind)


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    hlo_flops_total: float  # summed over chips
    hlo_bytes_total: float
    wire_bytes_per_chip: float
    chips: int

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Lower-bound step time: max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — >1 means XLA counted fewer flops than
        the analytic model (fusions), <1 means remat/redundant compute."""
        if self.hlo_flops_total <= 0:
            return 0.0
        return self.model_flops / self.hlo_flops_total

    @property
    def mfu_bound(self) -> float:
        """Achievable MFU upper bound at this placement: useful flops over
        chips×peak×step_time."""
        t = self.step_time_s
        if t <= 0:
            return 0.0
        return self.model_flops / (self.chips * PEAK_FLOPS * t)

    def row(self) -> dict:
        return {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "model_flops": self.model_flops,
            "hlo_flops": self.hlo_flops_total,
            "useful_fraction": self.useful_flops_fraction,
            "mfu_bound": self.mfu_bound,
            "step_time_s": self.step_time_s,
            "chips": self.chips,
        }


def compute_terms(
    hlo_flops: float,
    hlo_bytes: float,
    wire_bytes: float,
    chips: int,
    model_flops: float,
    per_device: bool = True,
) -> RooflineTerms:
    """Build roofline terms.

    per_device=True: hlo_flops/hlo_bytes/wire_bytes are per-chip quantities
    (the post-SPMD convention); False: global quantities divided by chips.
    """
    if per_device:
        flops_total = hlo_flops * chips
        bytes_total = hlo_bytes * chips
        wire_per_chip = wire_bytes
    else:
        flops_total = hlo_flops
        bytes_total = hlo_bytes
        wire_per_chip = wire_bytes / chips
    return RooflineTerms(
        compute_s=flops_total / (chips * PEAK_FLOPS),
        memory_s=bytes_total / (chips * HBM_BW),
        collective_s=wire_per_chip / ICI_BW,
        model_flops=model_flops,
        hlo_flops_total=flops_total,
        hlo_bytes_total=bytes_total,
        wire_bytes_per_chip=wire_per_chip,
        chips=chips,
    )
