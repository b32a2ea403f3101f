"""Tests for the repro.tune autotuner: cost-model ranking, cache
round-trip/corruption recovery, schedule="auto" equivalence through
DotEngine (interpret mode)."""
import json

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels.ops import sfc_gmm, sfc_matmul
from repro.kernels.ref import matmul_ref
from repro.models.layers import DotEngine
from repro.tune import (
    TuneConfig,
    autotune,
    candidate_configs,
    predict,
)
from repro.tune.cache import TuneCache, cache_key, shape_bucket


@pytest.fixture
def tune_cache(tmp_path, monkeypatch):
    """Isolated on-disk cache; also steers DotEngine's auto resolution."""
    path = str(tmp_path / "tune.json")
    monkeypatch.setenv("REPRO_TUNE_CACHE", path)
    return TuneCache(path)


def _rand(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype=dtype)


# ------------------------------------------------------------ cost model ---
def test_cost_model_sfc_beats_rowmajor_when_memory_bound():
    """Paper §IV-A on the block grid: in the memory-bound regime (cache
    of ~4 k-panels, grid >> cache) Morton and Hilbert traffic < row-major."""
    m = n = k = 4096
    cap = 4 * (k // 128)
    traffic = {
        s: predict(TuneConfig(s, 128, 128, 128), m, n, k, 4,
                   capacity=cap).traffic_bytes
        for s in ("rowmajor", "morton", "hilbert")
    }
    assert traffic["morton"] < traffic["rowmajor"]
    assert traffic["hilbert"] < traffic["rowmajor"]


def test_cost_model_index_cost_ordering():
    """Without prefetch the index time reproduces the paper's cost order
    RM < MO < HO; with prefetch it is amortised to zero."""
    m = n = k = 1024
    ts = {}
    for s in ("rowmajor", "morton", "hilbert"):
        ts[s] = predict(TuneConfig(s, 128, 128, 128, use_prefetch=False),
                        m, n, k, 4).t_index
    assert ts["rowmajor"] < ts["morton"] < ts["hilbert"]
    assert predict(TuneConfig("morton", 128, 128, 128, use_prefetch=True),
                   m, n, k, 4).t_index == 0.0


def test_cost_model_prefix_probe_scales():
    """The prefix probe (huge grids) must stay in the same ballpark as the
    full simulation, and exactly match it when no truncation happens."""
    cfg = TuneConfig("morton", 128, 128, 128)
    m = n = k = 2048
    full = predict(cfg, m, n, k, 4, capacity=64, max_sim_steps=10**9)
    probed = predict(cfg, m, n, k, 4, capacity=64, max_sim_steps=2000)
    assert probed.extras["probe_tiles"] < full.extras["probe_tiles"]
    assert probed.traffic_bytes == pytest.approx(
        full.traffic_bytes, rel=0.25)
    # no-truncation branch: the full run must have replayed every tile
    assert full.extras["probe_tiles"] == (2048 // 128) ** 2


def test_candidate_space_is_valid():
    cands = candidate_configs(2048, 2048, 2048)
    assert any(c.schedule == "xla" for c in cands)
    assert any(c.schedule == "morton" for c in cands)
    # no candidate exceeds VMEM (f32 operands + accumulator)
    for c in cands:
        if c.schedule == "xla":
            continue
        need = (c.bm * c.bk + c.bk * c.bn + c.bm * c.bn) * 4 \
            + c.bm * c.bn * 4
        assert need <= 128e6
    # prefetch=False only where the closed-form decode exists
    for c in cands:
        if not c.use_prefetch:
            assert c.schedule in ("morton", "hilbert")


def test_autotune_choice_beats_rowmajor_default_2048(tune_cache):
    """Acceptance: on a >=2048^2 f32 case the chosen config's modelled
    HBM traffic <= the row-major/128 default's."""
    res = autotune(2048, 2048, 2048, "float32", measure=False,
                   cache=tune_cache, refresh=True)
    chosen = res.best_estimate
    rm = predict(TuneConfig("rowmajor", 128, 128, 128), 2048, 2048, 2048, 4)
    assert chosen is not None
    assert chosen.traffic_bytes <= rm.traffic_bytes


def test_autotune_memory_bound_picks_sfc_over_rowmajor(tune_cache):
    """Forced into the memory-bound regime (tiny simulated cache, no xla
    baseline), the tuner must prefer a locality-preserving order."""
    cands = [TuneConfig(s, 128, 128, 128)
             for s in ("rowmajor", "morton", "hilbert")]
    res = autotune(4096, 4096, 4096, "float32", measure=False,
                   cache=tune_cache, refresh=True,
                   capacity=128, candidates=cands)
    assert res.config.schedule in ("morton", "hilbert")


# ----------------------------------------------------------------- cache ---
def test_cache_roundtrip(tune_cache):
    key = cache_key(300, 300, 300, "float32", "cpu")
    assert tune_cache.get(key) is None
    entry = {"config": TuneConfig("hilbert", 256, 256, 128).to_dict()}
    tune_cache.put(key, entry)
    # fresh instance re-reads from disk
    fresh = TuneCache(tune_cache.path)
    got = fresh.get(key)
    assert got is not None
    assert TuneConfig.from_dict(got["config"]) == \
        TuneConfig("hilbert", 256, 256, 128)


def test_cache_shape_bucketing():
    assert shape_bucket(2048, 2048, 2048) == (2048, 2048, 2048)
    assert shape_bucket(2000, 1025, 100) == (2048, 2048, 128)
    k1 = cache_key(2000, 2000, 2000, "float32", "cpu")
    k2 = cache_key(2048, 2048, 2048, "float32", "cpu")
    assert k1 == k2
    assert cache_key(2048, 2048, 2048, "bfloat16", "cpu") != k2
    assert cache_key(2048, 2048, 2048, "float32", "tpu") != k2


def test_cache_corruption_recovery(tune_cache):
    key = cache_key(128, 128, 128, "float32", "cpu")
    tune_cache.put(key, {"config": TuneConfig().to_dict()})
    # corrupt the file on disk
    with open(tune_cache.path, "w") as f:
        f.write('{"version": 1, "entries": {truncated garbage')
    fresh = TuneCache(tune_cache.path)
    assert fresh.get(key) is None  # degraded to empty, no exception
    fresh.put(key, {"config": TuneConfig("morton").to_dict()})
    again = TuneCache(tune_cache.path)
    assert again.get(key) is not None  # healthy file rewritten
    with open(tune_cache.path) as f:
        json.load(f)  # valid JSON again


def test_cache_atomic_file_is_valid_json(tune_cache):
    for i in range(5):
        tune_cache.put(f"k{i}", {"config": TuneConfig().to_dict()})
        with open(tune_cache.path) as f:
            assert len(json.load(f)["entries"]) == i + 1


def test_autotune_uses_cache(tune_cache):
    r1 = autotune(512, 512, 512, "float32", cache=tune_cache,
                  measure=False)
    assert not r1.from_cache
    r2 = autotune(512, 512, 512, "float32", cache=tune_cache)
    assert r2.from_cache
    assert r2.config == r1.config
    # refresh bypasses the cache
    r3 = autotune(512, 512, 512, "float32", cache=tune_cache,
                  measure=False, refresh=True)
    assert not r3.from_cache


def test_cache_put_preserves_other_writers_entries(tune_cache):
    """A put() must merge with entries persisted by other processes after
    this instance's snapshot was taken (no lost updates on rewrite)."""
    tune_cache.put("mine", {"config": TuneConfig().to_dict()})
    assert tune_cache.get("mine") is not None  # snapshot now in memory
    other = TuneCache(tune_cache.path)
    other.put("theirs", {"config": TuneConfig("hilbert").to_dict()})
    tune_cache.put("mine2", {"config": TuneConfig("morton").to_dict()})
    final = TuneCache(tune_cache.path)
    assert sorted(final.keys()) == ["mine", "mine2", "theirs"]


def test_autotune_honours_passed_empty_cache(tmp_path):
    """An explicitly passed (empty, hence falsy: __len__) cache must be
    written to -- not silently swapped for the default-path cache."""
    mine = TuneCache(str(tmp_path / "explicit.json"))
    autotune(256, 256, 256, "float32", cache=mine, measure=False)
    assert (tmp_path / "explicit.json").exists()
    assert len(TuneCache(mine.path)) == 1


def test_cached_closed_form_winner_revalidated_for_bucket_sibling(tune_cache):
    """A use_prefetch=False winner tuned on a square-pow2 grid must not
    crash a same-bucket shape whose padded grid has no closed-form
    decode: resolution flips it to the (always valid) prefetch table."""
    from repro.tune import resolve_config

    key = cache_key(512, 512, 512, "float32", "cpu")
    tune_cache.put(key, {"config": TuneConfig(
        "morton", 128, 128, 128, use_prefetch=False).to_dict()})
    # exact tuned shape: config passes through unchanged (4x4 grid)
    assert resolve_config(512, 512, 512, "float32").use_prefetch is False
    # bucket sibling 300x300x300 -> 3x3 padded grid: must be sanitised
    cfg = resolve_config(300, 300, 300, "float32")
    assert cfg.use_prefetch is True
    a = _rand((300, 300), jnp.float32, 30)
    out = DotEngine(schedule="auto", interpret=True).dot(a, a)
    np.testing.assert_allclose(np.asarray(out), np.asarray(matmul_ref(a, a)),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------- auto schedule ---
def test_auto_schedule_bit_identical_to_morton(tune_cache):
    """Acceptance: DotEngine(schedule="auto") is bit-identical to the
    schedule="morton" reference path (both resolve to the same CPU
    execution; on TPU both run the Pallas kernel whose result is
    schedule-invariant, see test_kernels)."""
    a = _rand((300, 260), jnp.float32, 0)
    b = _rand((260, 190), jnp.float32, 1)
    out_auto = DotEngine(schedule="auto").dot(a, b)
    out_mo = sfc_matmul(a, b, schedule="morton")
    np.testing.assert_array_equal(np.asarray(out_auto), np.asarray(out_mo))


def test_auto_schedule_matches_ref_interpret(tune_cache):
    """auto resolution feeding the real Pallas kernel (interpret mode)."""
    from repro.tune import resolve_config

    a = _rand((64, 64), jnp.float32, 2)
    b = _rand((64, 64), jnp.float32, 3)
    cfg = resolve_config(64, 64, 64, "float32")
    if cfg.schedule == "xla":
        out = DotEngine(schedule="auto", interpret=True).dot(a, b)
    else:
        out = sfc_matmul(a, b, schedule=cfg.schedule, bm=16, bn=16, bk=16,
                         use_prefetch=cfg.use_prefetch, interpret=True,
                         force_pallas=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(matmul_ref(a, b)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("schedule", ["xla", "morton"])
def test_measure_config_inside_a_trace(schedule):
    """schedule="auto" resolves -- and on a TPU measures -- while the
    caller is being traced.  The measurement must run the candidate
    eagerly (not stage it into the caller's trace, where the clock would
    time tracing) and must not fold the kernel's index maps into
    constants."""
    import jax

    from repro.tune import measure_config

    seen = []

    def traced(x):
        seen.append(measure_config(
            TuneConfig(schedule, 128, 128, 128), 128, 256, 128,
            interpret=True, reps=1, warmup=1))
        return x

    staged = jax.make_jaxpr(traced)(1.0)
    assert not staged.eqns       # nothing of the measurement was staged
    assert isinstance(seen[0], float) and seen[0] > 0


def test_supertile_g_reaches_kernel():
    """A tuned supertile factor must be executed, not silently replaced
    by the schedule default (g=2)."""
    from repro.core.schedule import grid_schedule

    a = _rand((64, 64), jnp.float32, 20)
    b = _rand((64, 64), jnp.float32, 21)
    for g in (2, 4):
        out = sfc_matmul(a, b, schedule="supertile", bm=16, bn=16, bk=16,
                         g=g, interpret=True, force_pallas=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(matmul_ref(a, b)),
            rtol=1e-5, atol=1e-5)
    # the two factors genuinely produce different traversals
    assert not np.array_equal(grid_schedule("supertile", 4, 4, g=2),
                              grid_schedule("supertile", 4, 4, g=4))


def test_dot_engine_auto(tune_cache):
    eng = DotEngine(schedule="auto")
    x = _rand((4, 32, 24), jnp.float32, 14)
    w = _rand((24, 16), jnp.float32, 15)
    y = eng.dot(x, w)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(jnp.einsum("...d,df->...f", x, w)),
        rtol=1e-5, atol=1e-5)


def test_kernel_wrappers_refuse_auto():
    """"auto" is resolved by DotEngine alone: the wrappers refuse it
    rather than fall to XLA's dot off a TPU."""
    a = _rand((128, 24), jnp.float32, 18)
    with pytest.raises(ValueError, match="DotEngine"):
        sfc_matmul(a, _rand((24, 16), jnp.float32, 19), schedule="auto")
    with pytest.raises(ValueError, match="DotEngine"):
        sfc_gmm(a, _rand((2, 24, 16), jnp.float32, 20),
                jnp.asarray([1, 0], jnp.int32), schedule="auto")


def test_cache_put_survives_readonly_path(tmp_path, monkeypatch):
    """Serving must not die when the cache path is unwritable: the write
    is best-effort and the in-memory winner stays usable.  (Injected
    EROFS: chmod-based read-only dirs do not bind when running as root,
    e.g. in containers.)"""
    import os as _os

    c = TuneCache(str(tmp_path / "tune.json"))

    def _erofs(*a, **k):
        raise OSError(30, "Read-only file system")

    monkeypatch.setattr(_os, "replace", _erofs)
    c.put("k", {"config": TuneConfig().to_dict()})  # must not raise
    assert c.get("k") is not None  # in-memory result retained
    monkeypatch.undo()
    c.put("k2", {"config": TuneConfig().to_dict()})  # persistence resumes
    assert sorted(TuneCache(c.path).keys()) == ["k", "k2"]


# ------------------------------------------------------- DVFS dimension ---
def test_tune_config_f_scale_roundtrip_and_legacy():
    """f_scale round-trips through the cache dict form; pre-DVFS cache
    entries (no f_scale key) deserialise to nominal frequency."""
    c = TuneConfig("morton", 128, 128, 128, f_scale=0.75)
    assert TuneConfig.from_dict(c.to_dict()) == c
    legacy = {"schedule": "hilbert", "bm": 256, "bn": 256, "bk": 128,
              "use_prefetch": True, "g": 0}
    assert TuneConfig.from_dict(legacy).f_scale == 1.0
    assert c.kernel_config().f_scale == 1.0
    assert c.kernel_config().schedule == "morton"


def test_with_f_scale_rescales_without_resimulating():
    from repro.core.energy import TPU_V5E
    from repro.tune import with_f_scale

    base = predict(TuneConfig("morton", 128, 128, 128), 1024, 1024, 1024, 4)
    half = with_f_scale(base, 0.5)
    assert half.config.f_scale == 0.5
    assert half.traffic_bytes == base.traffic_bytes  # f-invariant
    assert half.t_compute == pytest.approx(2 * base.t_compute)
    assert half.t_hbm == base.t_hbm
    # matches a from-scratch prediction at that frequency
    direct = predict(TuneConfig("morton", 128, 128, 128, f_scale=0.5),
                     1024, 1024, 1024, 4)
    assert half.time == pytest.approx(direct.time)
    # out-of-range requests clamp (shared clamp with the energy model)
    assert with_f_scale(base, 9.0).config.f_scale == \
        with_f_scale(base, 1.25).config.f_scale
    assert with_f_scale(base, 0.0).config.f_scale == TPU_V5E.f_min


def test_energy_objective_selects_lower_f_scale_when_memory_bound(
        tune_cache):
    """Acceptance: on a memory-bound shape (2048x2048x256, bf16) the
    energy winner runs at a lower DVFS point than the time winner --
    the paper's Fig. 5/6 crossover as a tuning outcome."""
    rt = autotune(2048, 2048, 256, "bfloat16", measure=False,
                  cache=tune_cache, objective="time")
    re = autotune(2048, 2048, 256, "bfloat16", measure=False,
                  cache=tune_cache, objective="energy")
    assert re.config.f_scale < rt.config.f_scale
    # and the winners are served from per-objective cache keyspaces
    assert rt.key != re.key


def test_f_scale_expansion_skippable_and_pinnable(tune_cache):
    """f_scales=() pins candidates at their own frequency; an explicit
    grid is searched as given (clamped)."""
    cands = [TuneConfig("rowmajor", 128, 128, 128)]
    res = autotune(512, 512, 512, "float32", measure=False,
                   cache=tune_cache, refresh=True, candidates=cands,
                   f_scales=())
    assert all(e.config.f_scale == 1.0 for e in res.estimates)
    res2 = autotune(512, 512, 512, "float32", measure=False,
                    cache=tune_cache, refresh=True, candidates=cands,
                    f_scales=(0.6, 9.0))
    fs = sorted({e.config.f_scale for e in res2.estimates})
    assert fs == [0.6, 1.0, 1.25]  # own f, explicit 0.6, clamped 9.0


def test_cache_entry_records_chosen_not_analytic_best(tune_cache,
                                                      monkeypatch):
    """Regression: the cache entry's predicted_time/predicted_score used
    to come from ests[0] (the analytic front-runner) even when
    measurement overturned the ranking -- provenance misreported the
    winner's predicted cost."""
    import sys

    import repro.tune.autotune  # noqa: F401 -- ensure module is loaded
    # the package re-exports the function under the submodule's name, so
    # reach the module itself through sys.modules
    at = sys.modules["repro.tune.autotune"]

    cands = [TuneConfig("morton", 128, 128, 128),
             TuneConfig("rowmajor", 128, 128, 128)]

    def fake_measure(cfg, m, n, k, dtype="float32", **kw):
        return 1e-3 if cfg.schedule == "rowmajor" else 1e-2

    monkeypatch.setattr(at, "measure_config", fake_measure)
    # tiny simulated cache: analytically morton wins (less traffic);
    # the forced measurement overturns it in favour of rowmajor
    res = at.autotune(4096, 4096, 4096, "float32", measure=True,
                      cache=tune_cache, refresh=True, capacity=128,
                      candidates=cands, f_scales=(), topk=4)
    assert res.estimates[0].config.schedule == "morton"
    assert res.config.schedule == "rowmajor"
    entry = tune_cache.get(res.key)
    chosen_est = next(e for e in res.estimates
                      if e.config == res.config)
    assert entry["config"]["schedule"] == "rowmajor"
    assert entry["predicted_time"] == pytest.approx(chosen_est.time)
    assert entry["predicted_score"] == pytest.approx(chosen_est.time)
    # the analytic front-runner is preserved under its own key
    assert entry["analytic_best"]["config"]["schedule"] == "morton"
    assert entry["analytic_best"]["predicted_score"] < \
        entry["predicted_score"]


def test_time_objective_measurement_not_overturned_by_turbo(tune_cache,
                                                            monkeypatch):
    """Regression: objective="time" must adjudicate on the raw measured
    wall time.  The device runs at nominal frequency, so a hypothetical
    f_scale=1.25 variant's modelled discount must never let a measurably
    slower kernel beat a faster one."""
    import sys

    import repro.tune.autotune  # noqa: F401
    at = sys.modules["repro.tune.autotune"]

    # xla is compute-bound at 4096^3 f32 (streaming traffic), so its
    # turbo variant's *model* time is ~0.8x nominal; morton with a tiny
    # simulated cache is memory-bound (no turbo benefit).  Measurement
    # says morton is genuinely faster.
    cands = [TuneConfig("xla"), TuneConfig("morton", 128, 128, 128)]

    def fake_measure(cfg, m, n, k, dtype="float32", **kw):
        return 1.05e-3 if cfg.schedule == "xla" else 1.00e-3

    monkeypatch.setattr(at, "measure_config", fake_measure)
    res = at.autotune(4096, 4096, 4096, "float32", measure=True,
                      cache=tune_cache, refresh=True, capacity=128,
                      candidates=cands, topk=8)
    # sanity: the trap is armed -- a scaled xla turbo score would be
    # 1.05e-3 * ~0.8 < 1.00e-3 and win
    xla1 = next(e for e in res.estimates
                if e.config.schedule == "xla" and e.config.f_scale == 1.0)
    xla_t = next(e for e in res.estimates
                 if e.config.schedule == "xla" and e.config.f_scale == 1.25)
    assert 1.05e-3 * xla_t.time / xla1.time < 1.00e-3
    assert res.config.schedule == "morton"


def test_resolve_config_objective_isolation_with_f_scale(tune_cache):
    """A time winner at f_scale=1.0 must never be served to an energy
    caller (per-objective cache keyspace AND per-objective memo)."""
    from repro.tune import resolve_config

    k_time = cache_key(2048, 2048, 256, "bfloat16", "cpu")
    k_energy = cache_key(2048, 2048, 256, "bfloat16", "cpu",
                         objective="energy")
    tune_cache.put(k_time, {"config": TuneConfig(
        "morton", 128, 128, 128, f_scale=1.0).to_dict()})
    tune_cache.put(k_energy, {"config": TuneConfig(
        "morton", 128, 128, 128, f_scale=0.5).to_dict()})
    # interleave resolutions so the in-process memo holds both at once
    for _ in range(2):
        assert resolve_config(2048, 2048, 256, "bfloat16").f_scale == 1.0
        assert resolve_config(2048, 2048, 256, "bfloat16",
                              objective="energy").f_scale == 0.5


def test_validate_for_shape_preserves_f_scale(tune_cache):
    """_validate_for_shape flips use_prefetch for bucket siblings with
    no closed-form decode; the tuned DVFS point must survive the flip."""
    from repro.tune import resolve_config
    from repro.tune.autotune import _validate_for_shape

    cfg = TuneConfig("morton", 128, 128, 128, use_prefetch=False,
                     f_scale=0.75)
    out = _validate_for_shape(cfg, 300, 300, 300)
    assert out.use_prefetch is True
    assert out.f_scale == 0.75
    # exact tuned shape: untouched (including f_scale)
    assert _validate_for_shape(cfg, 512, 512, 512) == cfg
    # end-to-end through resolve_config's per-call validation
    key = cache_key(512, 512, 512, "float32", "cpu", objective="edp")
    tune_cache.put(key, {"config": cfg.to_dict()})
    got = resolve_config(300, 300, 300, "float32", objective="edp")
    assert got.use_prefetch is True and got.f_scale == 0.75


def test_resolved_f_scale_helper(tune_cache):
    from repro.tune import resolved_f_scale

    key = cache_key(2048, 2048, 256, "bfloat16", "cpu",
                    objective="energy")
    tune_cache.put(key, {"config": TuneConfig(
        "xla", f_scale=0.75).to_dict()})
    assert resolved_f_scale(2048, 2048, 256, "bfloat16",
                            objective="energy") == 0.75


def test_resolve_memo_invalidated_by_cache_mutation(tune_cache):
    """TuneCache.invalidate() (an on-disk mutation) must defeat the
    in-process resolve memo: the next resolution re-tunes."""
    import os
    import time as _time

    from repro.tune import resolve_config

    cfg1 = resolve_config(512, 512, 512, "float32")
    key = cache_key(512, 512, 512, "float32", "cpu")
    # plant a distinctive winner, bumping mtime past the memoised one
    _time.sleep(0.01)
    tune_cache.invalidate()
    tune_cache.put(key, {"config": TuneConfig(
        "hilbert", 256, 256, 128).to_dict()})
    cfg2 = resolve_config(512, 512, 512, "float32")
    assert cfg2 == TuneConfig("hilbert", 256, 256, 128)
    assert cfg2 != cfg1 or cfg1.schedule == "hilbert"


def test_validate_for_shape_clamps_overbudget_vmem(tune_cache):
    """Latent-gap regression (ISSUE 8 satellite): a cached winner whose
    blocks blow the VMEM working set for the exact serving shape used to
    sail through validation (only the decode mechanism was re-checked)
    and would hard-fault at launch.  It must now be clamped to the
    128^3 baseline, preserving schedule and tuned f_scale."""
    from repro.tune import resolve_config
    from repro.tune.autotune import _validate_for_shape

    bad = TuneConfig("morton", 4096, 4096, 512, f_scale=0.75)
    out = _validate_for_shape(bad, 4096, 4096, 512)
    assert (out.bm, out.bn, out.bk) == (128, 128, 128)
    assert out.schedule == "morton" and out.f_scale == 0.75
    # sane config for the same shape: untouched
    ok = TuneConfig("morton", 256, 256, 128)
    assert _validate_for_shape(ok, 4096, 4096, 512) == ok
    # end-to-end: a stale/hand-edited cache entry cannot reach the
    # kernel launch with an over-budget working set
    key = cache_key(4096, 4096, 512, "float32", "cpu")
    tune_cache.put(key, {"config": bad.to_dict()})
    got = resolve_config(4096, 4096, 512, "float32")
    assert (got.bm, got.bn, got.bk) == (128, 128, 128)
    assert got.f_scale == 0.75


def test_autotune_compiles_zero_rejected_candidates(tune_cache,
                                                    monkeypatch):
    """ISSUE 8 acceptance: every config the tuner is about to compile
    (the pre-measure hook seam) passes the full-level contract check --
    the tuner never wastes a compile on a rejected candidate."""
    import sys

    import repro.tune.autotune  # noqa: F401 -- ensure module is loaded
    from repro.analysis import check_gemm_contract

    # the package re-exports the function under the submodule's name, so
    # reach the module itself through sys.modules
    at = sys.modules["repro.tune.autotune"]

    monkeypatch.setattr(at, "measure_config",
                        lambda cfg, m, n, k, dtype, **kw: 1e-3)
    compiled = []
    at._PRECOMPILE_HOOKS.append(
        lambda cfg, m, n, k: compiled.append((cfg, m, n, k)))
    try:
        autotune(512, 512, 512, measure=True, topk=8, refresh=True,
                 cache=tune_cache)
    finally:
        at._PRECOMPILE_HOOKS.pop()
    assert compiled, "hook never fired"
    for cfg, m, n, k in compiled:
        rep = check_gemm_contract(cfg, m, n, k, level="full")
        assert rep.ok, (cfg, rep.to_dict())


def test_autotune_filters_explicit_bad_candidates(tune_cache):
    """Explicit candidate lists go through the same contract gate as
    the enumerator: an over-budget config is dropped before predict(),
    and the rejection is counted."""
    from repro.obs.metrics import default_registry

    rej = default_registry().counter("tune.contracts.rejected")
    before = rej.value
    bad = TuneConfig("morton", 4096, 4096, 4096)
    res = autotune(512, 512, 512, measure=False, refresh=True,
                   cache=tune_cache,
                   candidates=[bad, TuneConfig("xla")])
    assert res.config.schedule == "xla"
    assert all(e.config.kernel_config() != bad for e in res.estimates)
    assert rej.value == before + 1
