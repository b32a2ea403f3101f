"""Analytical time/energy model (RAPL replacement -- DESIGN.md §2, §7).

The container has no energy counters and no TPU, so we model:

    t_compute    = FLOPs / (chips * peak_flops * f_scale)
    t_hbm        = HBM_bytes / (chips * hbm_bw)
    t_ici        = ICI_bytes / (chips * ici_bw)          (per-chip link bytes)
    t            = max(t_compute, t_hbm, t_ici)           (perfect overlap)
    t_no_overlap = t_compute + t_hbm + t_ici              (pessimistic bound)

    E = FLOPs*e_flop*v(f)^2/v(1)^2 + HBM_bytes*e_hbm + ICI_bytes*e_ici
        + t * P_static * chips

Frequency ("DVFS") scaling: compute rate scales with f; dynamic compute
energy scales ~ f*V^2 per unit time i.e. ~ V(f)^2 per op, with V linear in f
between V_MIN..1.0 -- the standard first-order CMOS model.  Memory bandwidth
and memory energy are *not* scaled by core frequency, which is precisely the
mechanism behind the paper's "speed != energy efficiency once memory-bound"
finding; the model reproduces it by construction, and the benchmarks verify
the crossover points quantitatively.

``hbm_bytes``/``flops`` are *caller-supplied* workload terms: for tuned
GEMMs they come from :mod:`repro.tune.cost`, which accounts the fused
epilogue (DESIGN.md §9) -- a fused bias/activation/residual drops the
post-matmul C re-read/re-write passes from ``hbm_bytes`` (and their
elementwise ops ride ``flops``), so the J and EDP this module reports for
a fused kernel are lower by exactly the eliminated traffic's
``e_hbm``-weighted energy.  Nothing here special-cases fusion: the
contract is that callers pass the traffic their pipeline *actually*
generates.

Constants are documented estimates (DESIGN.md §7); all *validated* claims
are relative, so they survive any sane constant choice.
"""
from __future__ import annotations

from dataclasses import dataclass

__all__ = ["HW", "TPU_V5E", "HW_BY_DEVICE_KIND", "hw_for_device",
           "RooflineTerms", "roofline_terms", "energy_joules",
           "clamp_f_scale", "F_SCALE_MAX"]

# highest supported DVFS point (modest turbo headroom above nominal);
# both the time and the energy side of the model clamp to the same
# [f_min, F_SCALE_MAX] range so they can never disagree about which
# frequency actually ran (regression-tested in tests/test_power.py)
F_SCALE_MAX = 1.25


@dataclass(frozen=True)
class HW:
    name: str = "tpu-v5e"
    peak_flops: float = 197e12      # bf16 FLOP/s per chip (assignment)
    hbm_bw: float = 819e9           # B/s per chip (assignment)
    ici_bw: float = 50e9            # B/s per link (assignment)
    ici_links: int = 4              # torus links per chip
    dcn_bw: float = 25e9            # B/s per host, pod-to-pod
    hbm_per_chip: float = 16e9      # bytes
    vmem_per_chip: float = 128e6    # bytes (v5e ~128MB VMEM)
    # energy constants (pJ -> J/op via 1e-12)
    e_flop: float = 0.55e-12        # J per bf16 FLOP at nominal f
    e_hbm: float = 45e-12           # J per HBM byte
    e_ici: float = 15e-12           # J per ICI byte
    e_dcn: float = 60e-12           # J per DCN byte
    p_static: float = 55.0          # W per chip (leakage + uncore)
    v_min: float = 0.7              # voltage fraction at min frequency
    f_min: float = 0.5              # min supported f_scale


TPU_V5E = HW()

# the modeled chip for each TPU ``device_kind`` JAX reports
HW_BY_DEVICE_KIND = {"TPU v5 lite": TPU_V5E}


def hw_for_device(device) -> HW:
    """The modeled chip of a JAX ``device``.  A TPU whose
    ``device_kind`` has no row in :data:`HW_BY_DEVICE_KIND` raises:
    modeling it as a v5e would misreport every time and joule.  Any
    other platform (the CPU of the tests) models the v5e the kernels
    are written for."""
    if device.platform != "tpu":
        return TPU_V5E
    try:
        return HW_BY_DEVICE_KIND[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no modeled chip for TPU device_kind {device.device_kind!r}; "
            f"known kinds: {sorted(HW_BY_DEVICE_KIND)}") from None


def clamp_f_scale(hw: HW, f_scale: float) -> float:
    """Clamp a requested frequency scale to the supported DVFS range."""
    return max(hw.f_min, min(f_scale, F_SCALE_MAX))


def _voltage(hw: HW, f_scale: float) -> float:
    """Linear V(f) between (f_min, v_min) and (1.0, 1.0), clamped."""
    f = clamp_f_scale(hw, f_scale)
    slope = (1.0 - hw.v_min) / (1.0 - hw.f_min)
    return hw.v_min + slope * (f - hw.f_min)


@dataclass(frozen=True)
class RooflineTerms:
    t_compute: float
    t_hbm: float
    t_ici: float
    t_dcn: float = 0.0

    @property
    def t_overlap(self) -> float:
        return max(self.t_compute, self.t_hbm, self.t_ici, self.t_dcn)

    @property
    def t_serial(self) -> float:
        return self.t_compute + self.t_hbm + self.t_ici + self.t_dcn

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_hbm,
            "collective": self.t_ici,
            "dcn": self.t_dcn,
        }
        return max(terms, key=terms.get)

    def fraction_of_roofline(self, useful_flops: float, chips: int,
                             hw: HW = TPU_V5E) -> float:
        """MODEL_FLOPS MFU-style score: useful flops / (t_overlap * peak)."""
        if self.t_overlap == 0:
            return 0.0
        return useful_flops / (self.t_overlap * chips * hw.peak_flops)


def roofline_terms(
    flops: float,
    hbm_bytes: float,
    ici_bytes: float,
    chips: int,
    hw: HW = TPU_V5E,
    f_scale: float = 1.0,
    dcn_bytes: float = 0.0,
    hosts: int | None = None,
) -> RooflineTerms:
    """Three-term roofline (assignment §ROOFLINE).  ``flops``/``bytes`` are
    *global*; ``ici_bytes`` is the per-chip-busiest-link byte count if known,
    else global/chips is used as the per-chip estimate."""
    return RooflineTerms(
        t_compute=flops / (chips * hw.peak_flops * clamp_f_scale(hw, f_scale)),
        t_hbm=hbm_bytes / (chips * hw.hbm_bw),
        t_ici=ici_bytes / (chips * hw.ici_bw * hw.ici_links),
        t_dcn=dcn_bytes / (max(hosts or chips // 4, 1) * hw.dcn_bw),
    )


def energy_joules(
    flops: float,
    hbm_bytes: float,
    ici_bytes: float,
    chips: int,
    hw: HW = TPU_V5E,
    f_scale: float = 1.0,
    dcn_bytes: float = 0.0,
    overlap: bool = True,
    wall_time: float | None = None,
) -> dict:
    """Energy breakdown in joules (the Fig. 6 analogue).

    Returns package-style components: ``core`` (compute dynamic), ``hbm``,
    ``ici``/``dcn`` and ``static``; plus ``total`` and the wall ``time``.
    """
    terms = roofline_terms(flops, hbm_bytes, ici_bytes, chips, hw,
                           f_scale=f_scale, dcn_bytes=dcn_bytes)
    t = wall_time if wall_time is not None else (
        terms.t_overlap if overlap else terms.t_serial)
    f_scale = clamp_f_scale(hw, f_scale)  # breakdown reports what *ran*
    v = _voltage(hw, f_scale)
    core = flops * hw.e_flop * (v * v) / (1.0 * 1.0)
    hbm = hbm_bytes * hw.e_hbm
    ici = ici_bytes * hw.e_ici
    dcn = dcn_bytes * hw.e_dcn
    static = t * hw.p_static * chips
    return {
        "time": t,
        "core": core,
        "hbm": hbm,
        "ici": ici,
        "dcn": dcn,
        "static": static,
        "total": core + hbm + ici + dcn + static,
        "terms": terms,
        "f_scale": f_scale,
    }
