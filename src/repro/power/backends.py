"""Pluggable energy telemetry backends (DESIGN.md §8).

The paper measures energy with RAPL counters; this repo must produce
faithful numbers everywhere from a bare container (no counters, no
accelerator) to a Linux host with powercap and/or NVIDIA GPUs.  Three
backends behind one protocol:

* :class:`RaplBackend`  -- Linux powercap (``/sys/class/powercap``),
  per-domain package/dram counters with wraparound handling.  This is
  the paper's own instrument.
* :class:`NvmlBackend`  -- best-effort GPU energy via ``pynvml``
  (optional dependency): the cumulative ``TotalEnergyConsumption``
  counter where supported, otherwise trapezoidal integration of the
  instantaneous power draw.
* :class:`ModelBackend` -- the analytic time/energy model
  (:mod:`repro.core.energy`) fed by workload hints (FLOPs/bytes from
  the LRU traffic simulator or HLO cost analysis) and the *measured*
  wall time, so counter-less environments still produce calibrated,
  non-degenerate readings.

:func:`detect_backend` auto-selects (rapl > nvml > model) with graceful
fallback; ``REPRO_POWER_BACKEND`` pins a choice.  On a TPU it always
selects the model: RAPL and NVML read host CPU and GPU counters, which
never see the chip.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Protocol, runtime_checkable

from repro.core.energy import HW, TPU_V5E, energy_joules, hw_for_device

__all__ = ["WorkloadHints", "PowerBackend", "RaplBackend", "NvmlBackend",
           "ModelBackend", "detect_backend", "RAPL_SYSFS_ROOT"]

RAPL_SYSFS_ROOT = "/sys/class/powercap"
_ENV_BACKEND = "REPRO_POWER_BACKEND"


@dataclass(frozen=True)
class WorkloadHints:
    """What ran inside a metered region, for model-based accounting.

    Counter backends ignore hints (the hardware saw the work); the
    :class:`ModelBackend` combines them with the measured wall time.
    ``flops`` also feeds the derived J/FLOP on every backend's readings.
    ``hw=None`` (the default) defers to the backend's configured HW, so
    a calibrated ``ModelBackend(hw=...)`` is not silently overridden.
    """

    flops: float = 0.0
    hbm_bytes: float = 0.0
    ici_bytes: float = 0.0
    dcn_bytes: float = 0.0
    chips: int = 1
    f_scale: float = 1.0
    hw: HW | None = None
    # optional breakdown of hbm_bytes for telemetry (DESIGN.md §10): the
    # serve loop reports attention-cache traffic (paged gather vs
    # contiguous strips) next to the GEMM weight/activation traffic, so
    # a J/step reading can be attributed to the cache layout.  Purely
    # informational -- the energy model consumes hbm_bytes.
    attn_bytes: float = 0.0
    gemm_bytes: float = 0.0


@runtime_checkable
class PowerBackend(Protocol):
    """One energy-measurement instrument.

    ``start()`` returns an opaque token (typically a counter snapshot);
    ``stop(token, elapsed_s, hints)`` returns joules by domain for the
    interval.  Domain names are backend-specific ("package-0"/"dram" for
    RAPL, "gpu0" for NVML, "core"/"hbm"/"static"/... for the model);
    ``primary_domains`` lists the non-overlapping domains whose sum is
    the total (RAPL subzones are *contained in* their package zone and
    must not be double-counted).
    """

    name: str
    primary_domains: tuple[str, ...]

    def start(self) -> Any: ...

    def stop(self, token: Any, elapsed_s: float,
             hints: WorkloadHints | None = None) -> dict[str, float]: ...


# --------------------------------------------------------------------- RAPL
class RaplBackend:
    """Linux powercap RAPL counters.

    Walks ``<root>/intel-rapl:*`` zones (and one level of ``:N:M``
    subzones), reading ``energy_uj`` (cumulative microjoules) and
    ``max_energy_range_uj`` (the wraparound modulus).  Counter deltas
    are taken modulo the range, so a single wrap during a metered region
    is handled exactly; totals sum only top-level zones (subzone energy
    is already contained in its package).
    """

    name = "rapl"

    def __init__(self, root: str | None = None):
        self.root = root or RAPL_SYSFS_ROOT
        # label -> (energy_uj path, max_range_uj); insertion order = walk order
        self._domains: dict[str, tuple[str, int]] = {}
        self.primary_domains: tuple[str, ...] = ()
        self._discover()
        if not self._domains:
            raise RuntimeError(f"no readable RAPL zones under {self.root}")

    @classmethod
    def available(cls, root: str | None = None) -> bool:
        try:
            return bool(cls(root)._domains)
        except (OSError, RuntimeError):
            return False

    def _zone_label(self, zdir: str, taken) -> str | None:
        try:
            with open(os.path.join(zdir, "name")) as f:
                label = f.read().strip()
            # probe readability now: perms differ per distro
            self._read_uj(os.path.join(zdir, "energy_uj"))
        except (OSError, ValueError):
            return None
        base, i = label, 1
        while label in taken:
            i += 1
            label = f"{base}:{i}"
        return label

    def _discover(self) -> None:
        try:
            zones = sorted(e for e in os.listdir(self.root)
                           if e.startswith("intel-rapl:"))
        except OSError:
            return
        primaries = []
        for z in zones:
            zdir = os.path.join(self.root, z)
            if not os.path.isdir(zdir):
                continue
            label = self._zone_label(zdir, self._domains)
            if label is None:
                continue
            self._domains[label] = (
                os.path.join(zdir, "energy_uj"),
                self._max_range(zdir))
            # top-level zones are "intel-rapl:N" (one ':'); subzones
            # "intel-rapl:N:M" nest inside them
            if z.count(":") == 1:
                primaries.append(label)
        self.primary_domains = tuple(primaries)

    @staticmethod
    def _max_range(zdir: str) -> int:
        try:
            with open(os.path.join(zdir, "max_energy_range_uj")) as f:
                return max(int(f.read().strip()), 1)
        except (OSError, ValueError):
            return 2 ** 32  # common hardware default; only wrap handling cares

    @staticmethod
    def _read_uj(path: str) -> int:
        with open(path) as f:
            return int(f.read().strip())

    def start(self) -> dict[str, int]:
        return {label: self._read_uj(path)
                for label, (path, _) in self._domains.items()}

    def stop(self, token: dict[str, int], elapsed_s: float,
             hints: WorkloadHints | None = None) -> dict[str, float]:
        out = {}
        for label, (path, max_range) in self._domains.items():
            if label not in token:
                continue
            delta = self._read_uj(path) - token[label]
            if delta < 0:  # counter wrapped (at most once per sane interval)
                delta += max_range
            out[label] = delta * 1e-6
        return out


# --------------------------------------------------------------------- NVML
class NvmlBackend:
    """Best-effort GPU energy via pynvml (optional dependency).

    Prefers the cumulative mJ counter
    (``nvmlDeviceGetTotalEnergyConsumption``, Volta+); devices without it
    fall back to integrating instantaneous power over the interval.
    Everything is wrapped defensively: NVML quirks must degrade to a
    missing domain, never an exception on the hot path.
    """

    name = "nvml"

    def __init__(self):
        import pynvml  # noqa: F401 -- ImportError propagates to available()

        self._nvml = pynvml
        self._nvml.nvmlInit()
        count = self._nvml.nvmlDeviceGetCount()
        self._handles = [self._nvml.nvmlDeviceGetHandleByIndex(i)
                         for i in range(count)]
        if not self._handles:
            raise RuntimeError("NVML initialised but no devices")
        self.primary_domains = tuple(f"gpu{i}" for i in range(count))

    @classmethod
    def available(cls) -> bool:
        try:
            cls()
            return True
        except Exception:  # import error, driver missing, zero devices, ...
            return False

    def _energy_mj(self, handle) -> int | None:
        try:
            return int(self._nvml.nvmlDeviceGetTotalEnergyConsumption(handle))
        except Exception:
            return None

    def _power_w(self, handle) -> float | None:
        try:
            return self._nvml.nvmlDeviceGetPowerUsage(handle) * 1e-3
        except Exception:
            return None

    def start(self) -> list[tuple[int | None, float | None]]:
        return [(self._energy_mj(h), self._power_w(h))
                for h in self._handles]

    def stop(self, token, elapsed_s: float,
             hints: WorkloadHints | None = None) -> dict[str, float]:
        out = {}
        for i, (h, (e0, p0)) in enumerate(zip(self._handles, token)):
            e1 = self._energy_mj(h)
            if e0 is not None and e1 is not None:
                out[f"gpu{i}"] = max(e1 - e0, 0) * 1e-3
                continue
            p1 = self._power_w(h)
            if p0 is not None and p1 is not None:
                out[f"gpu{i}"] = 0.5 * (p0 + p1) * elapsed_s
        return out


# -------------------------------------------------------------------- model
class ModelBackend:
    """Analytic accounting when no counter exists (DESIGN.md §7).

    Energy is ``energy_joules(hints..., wall_time=elapsed)``: dynamic
    terms come from the workload hints (FLOPs / HBM / ICI / DCN bytes --
    typically produced by the LRU traffic simulator or the HLO cost
    analyzer), static power from the measured wall time.  With no hints
    at all the reading degrades to static power x time, which is still a
    non-degenerate, comparable number.
    """

    name = "model"
    primary_domains = ("core", "hbm", "ici", "dcn", "static")

    def __init__(self, hw: HW = TPU_V5E,
                 default_hints: WorkloadHints | None = None):
        self.hw = hw
        self.default_hints = default_hints

    @classmethod
    def available(cls) -> bool:
        return True

    def start(self) -> None:
        return None

    def stop(self, token: None, elapsed_s: float,
             hints: WorkloadHints | None = None) -> dict[str, float]:
        h = hints or self.default_hints or WorkloadHints()
        e = energy_joules(h.flops, h.hbm_bytes, h.ici_bytes, h.chips,
                          hw=h.hw or self.hw, f_scale=h.f_scale,
                          dcn_bytes=h.dcn_bytes, wall_time=elapsed_s)
        return {d: float(e[d]) for d in self.primary_domains}


# ---------------------------------------------------------------- detection
def detect_backend(prefer: str | None = None, *,
                   rapl_root: str | None = None,
                   hw: HW | None = None) -> PowerBackend:
    """Pick the best available backend.

    Order: explicit ``prefer`` (or ``$REPRO_POWER_BACKEND``), then RAPL,
    then NVML, then the analytic model.  An unavailable preference falls
    back down the same chain rather than raising: telemetry must never
    take down the workload it observes.

    On a TPU the model is the only backend: its readings are modeled
    joules of the chip, where a host counter would label the CPU
    package's energy as the run's.  Asking for a counter there raises.
    ``hw`` defaults to the modeled chip of the first JAX device
    (:func:`repro.core.energy.hw_for_device`, which raises on an
    unknown TPU kind).
    """
    import jax

    device = jax.devices()[0]
    hw = hw or hw_for_device(device)
    prefer = prefer or os.environ.get(_ENV_BACKEND) or None
    order = ["rapl", "nvml", "model"]
    if prefer is not None and prefer not in order:
        raise ValueError(
            f"unknown power backend {prefer!r}; choose from {order}")
    if device.platform == "tpu":
        if prefer not in (None, "model"):
            raise ValueError(
                f"power backend {prefer!r} reads host counters, not the "
                f"TPU; only 'model' is available on a TPU")
        return ModelBackend(hw=hw)
    if prefer is not None:
        order = [prefer] + [b for b in order if b != prefer]
    for name in order:
        # construct once and keep the instance: probing availability via
        # a throwaway construction would double the sysfs walk (RAPL) or
        # leak a second NVML init on every detection
        try:
            if name == "rapl":
                return RaplBackend(rapl_root)
            if name == "nvml":
                return NvmlBackend()
            return ModelBackend(hw=hw)  # name == "model": always available
        except Exception:
            continue
    return ModelBackend(hw=hw)  # every counter backend failed

