"""Tests for repro.power (DESIGN.md §8): RAPL counter parsing +
wraparound, backend auto-detection fallback order, EnergyMeter nesting,
report schema validation, objective-aware autotuning (cache keyspace +
the edp-vs-time winner acceptance case), and the core/energy
frequency-clamp regression."""
import dataclasses
import json
import os
import time

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.energy import (
    F_SCALE_MAX,
    TPU_V5E,
    clamp_f_scale,
    energy_joules,
    roofline_terms,
)
from repro.power import (
    EnergyMeter,
    EnergyReport,
    ModelBackend,
    NvmlBackend,
    RaplBackend,
    WorkloadHints,
    detect_backend,
    validate_bench_payload,
    validate_report,
)
from repro.tune import TuneConfig, autotune, objective_value, predict
from repro.tune.cache import TuneCache, cache_key

DRAM_MAX_UJ = 65_712_999_613


# ------------------------------------------------------------------ fixtures
def _write_zone(root, zone, label, uj, max_uj=262_143_328_850):
    d = os.path.join(root, zone)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "name"), "w") as f:
        f.write(label + "\n")
    with open(os.path.join(d, "energy_uj"), "w") as f:
        f.write(f"{uj}\n")
    with open(os.path.join(d, "max_energy_range_uj"), "w") as f:
        f.write(f"{max_uj}\n")
    return d


@pytest.fixture
def rapl_root(tmp_path):
    """A fake /sys/class/powercap: two packages, one dram subzone."""
    root = str(tmp_path / "powercap")
    _write_zone(root, "intel-rapl:0", "package-0", 1_000_000)
    _write_zone(root, "intel-rapl:0:0", "dram", 500_000, DRAM_MAX_UJ)
    _write_zone(root, "intel-rapl:1", "package-1", 42_000)
    return root


@pytest.fixture
def tune_cache(tmp_path, monkeypatch):
    """Isolated on-disk tuner cache (also steers schedule="auto")."""
    path = str(tmp_path / "tune.json")
    monkeypatch.setenv("REPRO_TUNE_CACHE", path)
    return TuneCache(path)


# ---------------------------------------------------------------------- RAPL
def test_rapl_domain_parsing(rapl_root):
    b = RaplBackend(rapl_root)
    assert set(b._domains) == {"package-0", "dram", "package-1"}
    # dram is *contained in* package-0: only top-level zones sum to total
    assert b.primary_domains == ("package-0", "package-1")


def test_rapl_delta_and_wraparound(rapl_root):
    b = RaplBackend(rapl_root)
    token = b.start()
    # package-0 advances 2 J; dram wraps (500000 -> 100 past max range)
    _write_zone(rapl_root, "intel-rapl:0", "package-0", 3_000_000)
    _write_zone(rapl_root, "intel-rapl:0:0", "dram", 100, DRAM_MAX_UJ)
    out = b.stop(token, 0.1)
    assert out["package-0"] == pytest.approx(2.0)
    assert out["dram"] == pytest.approx(
        (DRAM_MAX_UJ - 500_000 + 100) * 1e-6)
    assert out["package-1"] == 0.0


def test_rapl_meter_total_skips_subzones(rapl_root):
    b = RaplBackend(rapl_root)
    with EnergyMeter("r", backend=b) as em:
        _write_zone(rapl_root, "intel-rapl:0", "package-0", 2_000_000)
        _write_zone(rapl_root, "intel-rapl:0:0", "dram", 900_000, DRAM_MAX_UJ)
    # total = package deltas only; dram stays visible as a domain
    assert em.reading.joules == pytest.approx(1.0)
    assert em.reading.domains["dram"] == pytest.approx(0.4)


def test_rapl_unavailable_without_sysfs(tmp_path):
    assert not RaplBackend.available(str(tmp_path / "nope"))
    with pytest.raises(RuntimeError):
        RaplBackend(str(tmp_path / "nope"))


# ----------------------------------------------------------------- detection
def test_detect_prefers_rapl_when_present(rapl_root):
    assert detect_backend(rapl_root=rapl_root).name == "rapl"


def test_detect_falls_back_to_model(tmp_path):
    # container truth: no powercap tree, no pynvml -> analytic model
    b = detect_backend(rapl_root=str(tmp_path / "nope"))
    assert b.name == "model" or NvmlBackend.available()


def test_detect_explicit_preference_and_fallback(rapl_root, tmp_path,
                                                 monkeypatch):
    # an explicit preference wins over the default order ...
    assert detect_backend("model", rapl_root=rapl_root).name == "model"
    # ... an unavailable preference degrades instead of raising
    got = detect_backend("rapl", rapl_root=str(tmp_path / "nope")).name
    assert got in ("nvml", "model")
    # ... the env var pins the choice
    monkeypatch.setenv("REPRO_POWER_BACKEND", "model")
    assert detect_backend(rapl_root=rapl_root).name == "model"
    with pytest.raises(ValueError):
        detect_backend("wattmeter")


class _Device:
    """Stands in for a JAX device: only what detection reads."""

    def __init__(self, platform, device_kind):
        self.platform = platform
        self.device_kind = device_kind


def _on_device(monkeypatch, platform, kind):
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: [_Device(platform, kind)])


def test_detect_on_tpu_is_the_model_even_with_host_counters(
        rapl_root, monkeypatch):
    """RAPL reads the host CPU package: on a TPU the model is the only
    backend, whatever counters the host has."""
    _on_device(monkeypatch, "tpu", "TPU v5 lite")
    b = detect_backend(rapl_root=rapl_root)
    assert b.name == "model" and b.hw is TPU_V5E
    for counter in ("rapl", "nvml"):
        with pytest.raises(ValueError, match="only 'model'"):
            detect_backend(counter, rapl_root=rapl_root)
    monkeypatch.setenv("REPRO_POWER_BACKEND", "rapl")
    with pytest.raises(ValueError, match="only 'model'"):
        detect_backend(rapl_root=rapl_root)


def test_unknown_tpu_kind_raises(monkeypatch):
    from repro.core.energy import hw_for_device
    with pytest.raises(ValueError, match="TPU v9 ultra"):
        hw_for_device(_Device("tpu", "TPU v9 ultra"))
    assert hw_for_device(_Device("tpu", "TPU v5 lite")) is TPU_V5E
    assert hw_for_device(_Device("cpu", "cpu")) is TPU_V5E
    _on_device(monkeypatch, "tpu", "TPU v9 ultra")
    with pytest.raises(ValueError, match="no modeled chip"):
        detect_backend()


# ------------------------------------------------------------ meter + model
def test_model_backend_reading_is_non_degenerate():
    """Acceptance: in a container with no counters the ModelBackend must
    still produce non-zero, internally consistent readings."""
    with EnergyMeter("work", backend=ModelBackend()) as em:
        time.sleep(0.02)
    r = em.reading
    assert r.seconds >= 0.02
    assert r.joules > 0          # static power x wall time at minimum
    assert r.edp == pytest.approx(r.joules * r.seconds)
    assert r.watts == pytest.approx(r.joules / r.seconds)


def test_model_backend_uses_hints():
    h = WorkloadHints(flops=1e12, hbm_bytes=1e9, chips=2)
    d = ModelBackend().stop(None, 0.5, h)
    assert d["core"] == pytest.approx(1e12 * TPU_V5E.e_flop)
    assert d["hbm"] == pytest.approx(1e9 * TPU_V5E.e_hbm)
    assert d["static"] == pytest.approx(0.5 * TPU_V5E.p_static * 2)
    with EnergyMeter("hinted", backend=ModelBackend(), flops=2e9) as em:
        pass
    assert em.reading.joules_per_flop == pytest.approx(
        em.reading.joules / 2e9)


def test_model_backend_custom_hw_survives_hints():
    """A calibrated ModelBackend(hw=...) must not be silently overridden
    by the TPU_V5E default when hints are passed (regression)."""
    hot = dataclasses.replace(TPU_V5E, p_static=500.0, e_flop=1e-9)
    d = ModelBackend(hw=hot).stop(None, 1.0, WorkloadHints(flops=1e6))
    assert d["static"] == pytest.approx(500.0)
    assert d["core"] == pytest.approx(1e6 * 1e-9 * 1.0)
    # an explicit hints.hw still wins over the backend's
    d2 = ModelBackend(hw=hot).stop(None, 1.0, WorkloadHints(hw=TPU_V5E))
    assert d2["static"] == pytest.approx(TPU_V5E.p_static)


def test_meter_stacks_are_thread_local():
    """A meter open in another thread must not capture this thread's
    readings as children (regression: global nesting stack)."""
    import threading

    b = ModelBackend()
    entered = threading.Event()
    release = threading.Event()
    holder: dict = {}

    def hold_open():
        with EnergyMeter("other-thread", backend=b) as m:
            holder["m"] = m
            entered.set()
            release.wait(5)

    t = threading.Thread(target=hold_open)
    t.start()
    entered.wait(5)
    rep = EnergyReport()
    with EnergyMeter("main-thread", backend=b, reporter=rep):
        pass
    release.set()
    t.join(5)
    assert [x.label for x in rep.readings] == ["main-thread"]
    assert holder["m"].reading.children == []


def test_meter_nesting_builds_tree():
    b = ModelBackend()
    rep = EnergyReport()
    with EnergyMeter("outer", backend=b, reporter=rep) as outer:
        with EnergyMeter("inner-1", backend=b):
            pass
        with EnergyMeter("inner-2", backend=b) as i2, \
                EnergyMeter("leaf", backend=b):
            pass
    r = outer.reading
    assert [c.label for c in r.children] == ["inner-1", "inner-2"]
    assert [c.label for c in i2.reading.children] == ["leaf"]
    # only the top-level reading reaches the session reporter
    assert [x.label for x in rep.readings] == ["outer"]


def test_meter_decorator_accumulates():
    m = EnergyMeter("fn", backend=ModelBackend())

    @m
    def work():
        return 7

    assert work() == 7 and work() == 7
    assert len(m.readings) == 2
    assert m.reading is m.readings[-1]


# ------------------------------------------------------------------- report
def test_report_roundtrip_validates(tmp_path):
    rep = EnergyReport(meta={"driver": "test"})
    with EnergyMeter("a", backend=ModelBackend(), reporter=rep, flops=1e6):
        pass
    with EnergyMeter("b", backend=ModelBackend(), reporter=rep):
        pass
    path = str(tmp_path / "report.json")
    rep.write(path)
    with open(path) as f:
        d = json.load(f)
    assert validate_report(d) == []
    assert d["totals"]["joules"] == pytest.approx(
        sum(r.joules for r in rep.readings))


def test_report_validation_catches_breakage():
    rep = EnergyReport()
    with EnergyMeter("a", backend=ModelBackend(), reporter=rep):
        pass
    d = rep.to_dict()
    good = json.loads(json.dumps(d))
    good["schema_version"] = 99
    assert any("schema_version" in e for e in validate_report(good))
    bad = json.loads(json.dumps(d))
    bad["readings"][0]["joules"] = -1.0
    assert any("joules" in e for e in validate_report(bad))
    with pytest.raises(ValueError):
        validate_report({"kind": "nope"}, strict=True)


def test_bench_payload_validation():
    rep = EnergyReport(backend="model")
    payload = {"schema_version": 2, "git_sha": "deadbeef",
               "backend": "cpu", "power_backend": "model",
               "results": {}, "energy": rep.to_dict()}
    assert validate_bench_payload(payload) == []
    del payload["git_sha"]
    assert any("git_sha" in e for e in validate_bench_payload(payload))


# --------------------------------------------- frequency clamp (regression)
def test_frequency_clamp_shared_between_time_and_energy():
    """_voltage clamps f_scale; t_compute must clamp to the *same* range
    or time and energy disagree outside [f_min, F_SCALE_MAX]."""
    hw = TPU_V5E
    over = roofline_terms(1e15, 1e9, 0.0, 1, hw, f_scale=4.0)
    at_max = roofline_terms(1e15, 1e9, 0.0, 1, hw, f_scale=F_SCALE_MAX)
    assert over.t_compute == at_max.t_compute
    under = roofline_terms(1e15, 1e9, 0.0, 1, hw, f_scale=0.01)
    at_min = roofline_terms(1e15, 1e9, 0.0, 1, hw, f_scale=hw.f_min)
    assert under.t_compute == at_min.t_compute
    # full energy dicts agree too (same clamped f on both sides)
    e_over = energy_joules(1e15, 1e9, 0.0, 1, hw, f_scale=4.0)
    e_max = energy_joules(1e15, 1e9, 0.0, 1, hw, f_scale=F_SCALE_MAX)
    for key in ("time", "core", "static", "total"):
        assert e_over[key] == e_max[key]
    assert clamp_f_scale(hw, 0.9) == 0.9  # in-range values untouched


def test_energy_breakdown_reports_clamped_f_scale():
    """Regression: the breakdown dict used to echo the *raw* requested
    f_scale while the time/voltage terms used the clamped one -- a
    caller logging breakdown["f_scale"] recorded a frequency that never
    ran."""
    hw = TPU_V5E
    over = energy_joules(1e12, 1e9, 0.0, 1, hw, f_scale=3.0)
    assert over["f_scale"] == F_SCALE_MAX
    under = energy_joules(1e12, 1e9, 0.0, 1, hw, f_scale=0.01)
    assert under["f_scale"] == hw.f_min
    # the whole breakdown is indistinguishable from asking for the
    # clamped value directly
    assert over == energy_joules(1e12, 1e9, 0.0, 1, hw,
                                 f_scale=F_SCALE_MAX)
    assert under == energy_joules(1e12, 1e9, 0.0, 1, hw,
                                  f_scale=hw.f_min)
    # in-range values pass through untouched
    assert energy_joules(1e12, 1e9, 0.0, 1, hw,
                         f_scale=0.8)["f_scale"] == 0.8


# --------------------------------------------------- objective-aware tuning
_EDP_HW = dataclasses.replace(
    TPU_V5E, name="edp-demo", peak_flops=1e18, hbm_bw=1.5e12,
    e_flop=0.01e-12, p_static=1.0)
# the paper's §II trade, isolated: row-major pays no index cost but
# streams ~2x the HBM bytes of closed-form Morton at this cache size
_EDP_CANDS = [TuneConfig("rowmajor", 128, 128, 128, use_prefetch=True),
              TuneConfig("morton", 128, 128, 128, use_prefetch=False)]


def test_objective_cache_keyspace():
    k_time = cache_key(512, 512, 512, "float32", "cpu")
    assert k_time == cache_key(512, 512, 512, "float32", "cpu",
                               objective="time")  # historical form stable
    k_edp = cache_key(512, 512, 512, "float32", "cpu", objective="edp")
    assert k_edp != k_time and k_edp.endswith("/obj=edp")


def test_old_time_entry_not_served_for_edp(tune_cache):
    """A wall-time-tuned winner must not satisfy objective="edp"."""
    key = cache_key(512, 512, 512, "float32", "cpu")
    tune_cache.put(key, {"config": TuneConfig("hilbert", 256, 256,
                                              128).to_dict()})
    res = autotune(512, 512, 512, "float32", cache=tune_cache,
                   measure=False, objective="edp")
    assert not res.from_cache
    hit = autotune(512, 512, 512, "float32", cache=tune_cache,
                   objective="edp")
    assert hit.from_cache  # its own keyspace does cache


def test_objective_value_scores():
    e = predict(TuneConfig("rowmajor"), 1024, 1024, 1024, 4, hw=_EDP_HW)
    t = objective_value(e, "time", hw=_EDP_HW)
    en = objective_value(e, "energy", hw=_EDP_HW)
    assert objective_value(e, "edp", hw=_EDP_HW) == pytest.approx(en * t)
    with pytest.raises(ValueError):
        objective_value(e, "speed")
    with pytest.raises(ValueError):
        autotune(128, 128, 128, objective="speed")


def test_edp_objective_selects_different_winner(tune_cache):
    """Acceptance: on a synthetic HW the EDP/energy optimum differs from
    the wall-time optimum -- the paper's 'fastest != most efficient'."""
    winners = {}
    for obj in ("time", "energy", "edp"):
        res = autotune(4096, 4096, 4096, "float32", measure=False,
                       cache=tune_cache, hw=_EDP_HW, capacity=256,
                       candidates=_EDP_CANDS, objective=obj)
        winners[obj] = res.config
    assert winners["time"].schedule == "rowmajor"
    assert winners["edp"].schedule == "morton"
    assert winners["energy"].schedule == "morton"
    assert winners["edp"] != winners["time"]


def test_sfc_matmul_auto_with_objective(tune_cache):
    from repro.kernels.ops import sfc_matmul

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((96, 80)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((80, 64)), jnp.float32)
    out = np.asarray(sfc_matmul(a, b, schedule="auto", objective="edp"))
    np.testing.assert_allclose(out, np.asarray(a @ b), rtol=1e-4, atol=1e-4)
    # the edp resolution landed in its own cache bucket
    assert any(k.endswith("/obj=edp") for k in tune_cache)


def test_dot_engine_objective_roundtrip(tune_cache):
    from repro.models.layers import DotEngine

    eng = DotEngine(schedule="auto", objective="energy")
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((4, 24, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    y = eng.dot(x, w)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(jnp.einsum("...d,df->...f", x, w)),
        rtol=1e-4, atol=1e-4)
    assert any("obj=energy" in k for k in tune_cache)


# -------------------------------------------- real counters (auto-skipped)
@pytest.mark.skipif(not RaplBackend.available(),
                    reason="no readable RAPL counters on this host")
def test_real_rapl_counters_smoke():
    with EnergyMeter("real-rapl", backend=RaplBackend()) as em:
        time.sleep(0.05)
    assert em.reading.joules >= 0.0
    assert em.reading.domains


@pytest.mark.skipif(not NvmlBackend.available(),
                    reason="no NVML-visible GPU on this host")
def test_real_nvml_counters_smoke():
    with EnergyMeter("real-nvml", backend=NvmlBackend()) as em:
        time.sleep(0.05)
    assert em.reading.joules >= 0.0
