"""Fault-tolerant serving (DESIGN.md §14): the chaos schedule DSL and
thread-local injection hook, allocator + serve-state snapshot/restore
round trips (in-memory and through the checkpoint store), NaN
quarantine, per-request deadlines (including the preemption-past-
deadline regression), load shedding, the sticky kernel fallback, power
-meter degradation, checkpoint corruption detection, and the acceptance
bar: a serve run under an injected fault schedule finishes with the
surviving requests' tokens byte-identical to a fault-free run.
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointCorruptionError, load_checkpoint, \
    save_checkpoint
from repro.configs import get_smoke_config
from repro.launch.serve import ServeLoop
from repro.models import init_model
from repro.obs import MetricsRegistry
from repro.runtime import ChaosEvent, ChaosInjector, InjectedFault, \
    ServeSnapshotter, TransientFault, parse_chaos_spec
from repro.runtime import chaos as chaos_mod
from repro.serve import PageAllocator, ServeConfig


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("qwen3_1_7b")


@pytest.fixture(scope="module")
def params(cfg):
    return init_model(cfg, jax.random.PRNGKey(0))


# ----------------------------------------------------- chaos schedule -----
def test_parse_chaos_spec():
    inj = parse_chaos_spec(
        "alloc@step=2,nan@step=3:req=1:times=2,straggler@delay=0.5,"
        "kernel@p=0.5")
    assert [e.point for e in inj.events] == \
        ["alloc", "nan", "straggler", "kernel"]
    assert inj.events[0].step == 2
    assert inj.events[1].request == 1 and inj.events[1].times == 2
    assert inj.events[2].seconds == 0.5
    assert inj.events[3].p == 0.5


def test_parse_chaos_spec_rejects_garbage():
    with pytest.raises(ValueError, match="unknown chaos key"):
        parse_chaos_spec("alloc@bogus=1")
    with pytest.raises(ValueError, match="unknown chaos point"):
        parse_chaos_spec("frobnicate@step=1")
    with pytest.raises(ValueError, match="empty"):
        parse_chaos_spec("  ")


def test_injector_step_semantics_consume_once():
    inj = ChaosInjector([ChaosEvent("alloc", step=3)])
    assert inj.match("alloc", step=1) is None       # not yet
    assert inj.match("kernel", step=5) is None      # wrong point
    assert inj.match("alloc", step=5) is not None   # first check past 3
    assert inj.match("alloc", step=6) is None       # times=1: consumed
    assert inj.exhausted()
    assert inj.fired == [("alloc", 5, None)]


def test_injector_request_and_probability():
    inj = ChaosInjector([ChaosEvent("nan", request=2)])
    assert inj.match("nan", request=1) is None
    assert inj.match("nan", request=2) is not None
    never = ChaosInjector([ChaosEvent("kernel", p=0.0)])
    assert all(never.match("kernel") is None for _ in range(20))
    always = ChaosInjector([ChaosEvent("kernel", p=1.0, times=5)])
    assert sum(always.match("kernel") is not None
               for _ in range(5)) == 5


def test_fire_hook_thread_local_install():
    assert chaos_mod.active() is None
    chaos_mod.fire("alloc")    # no injector: one attribute read, no-op
    inj = ChaosInjector([ChaosEvent("alloc", step=2)])
    with chaos_mod.install(inj):
        chaos_mod.set_context(step=0)
        chaos_mod.fire("alloc")        # ambient step 0 < 2: silent
        chaos_mod.set_context(step=2)
        with pytest.raises(InjectedFault) as ei:
            chaos_mod.fire("alloc")
        assert ei.value.point == "alloc"
        assert isinstance(ei.value, TransientFault)
    assert chaos_mod.active() is None  # uninstalled on exit


# ------------------------------------------- allocator serialization -----
def test_allocator_state_dict_round_trip_with_index():
    import json
    a = PageAllocator(16, 4, 2, prefix_sharing=True)
    a.ensure_range(0, 10)
    a.register_prefix(0, list(range(10)))
    a.ensure_range(1, 5)
    a.release(1)
    a.release(0)     # indexed pages land on the cached-free FIFO
    d = json.loads(json.dumps(a.state_dict()))   # disk round trip
    b = PageAllocator(16, 4, 2, prefix_sharing=True)
    b.load_state_dict(d)
    assert b._free == a._free                    # order preserved
    assert b._free_cached == a._free_cached
    np.testing.assert_array_equal(b.block_table, a.block_table)
    np.testing.assert_array_equal(b.ref, a.ref)
    assert b.stats == a.stats
    b.check_invariants()
    # the prefix index survived: same pages match the same prompt
    assert b.index.match(list(range(10)), 4) == \
        a.index.match(list(range(10)), 4)


def test_allocator_load_rejects_geometry_mismatch():
    a = PageAllocator(16, 4, 2)
    b = PageAllocator(16, 4, 4)
    with pytest.raises(ValueError, match="does not fit"):
        b.load_state_dict(a.state_dict())


# ------------------------------------------------- snapshot / restore -----
def test_serve_snapshot_restore_round_trip(cfg, params, tmp_path):
    sc = ServeConfig(slots=2, cache_len=64, layout="paged",
                     mode="continuous", prefill_budget=8)
    loop = ServeLoop(cfg, params, sc)
    rng = np.random.default_rng(0)
    for r in range(3):
        loop.submit(r, rng.integers(2, cfg.vocab, size=6).tolist())
    for _ in range(3):
        loop._run_iteration(max_new=5)
    snap = ServeSnapshotter(loop, every=1, root=str(tmp_path))
    snap.snapshot(3)
    want = (loop.pos.copy(), loop.active.copy(),
            {r: list(t) for r, t in loop.out.items()},
            [(r, list(p)) for r, p in loop.queue])
    while loop._pending():
        loop._run_iteration(max_new=5)
    final = {r: list(t) for r, t in loop.out.items()}

    def check_rewound():
        np.testing.assert_array_equal(loop.pos, want[0])
        np.testing.assert_array_equal(loop.active, want[1])
        assert loop.out == want[2]
        assert loop.queue == want[3]
        loop.alloc.check_invariants()

    assert snap.restore() == 3               # in-memory path
    check_rewound()
    # replay from the snapshot reproduces the same final tokens
    while loop._pending():
        loop._run_iteration(max_new=5)
    assert {r: list(t) for r, t in loop.out.items()} == final
    assert snap.restore(from_disk=True) == 3  # checkpoint-store path
    check_rewound()


# ------------------------------------------------- deadlines / watchdog ---
def test_deadline_fails_expired_request_only(cfg, params):
    m = MetricsRegistry()
    sc = ServeConfig(slots=2, cache_len=64, deadline_ms=2000.0)
    loop = ServeLoop(cfg, params, sc, metrics=m)
    loop.submit(0, [5, 6, 7])                               # fresh
    loop.submit(1, [8, 9, 10],
                arrival_ts=time.monotonic() - 10.0)         # long dead
    out = loop.run(max_new=4)
    assert loop.errors == {1: "deadline"}
    assert 1 not in out                       # failed before admission
    assert len(out[0]) == 3 + 4               # survivor unaffected
    assert m.counter("serve.faults.deadline").value == 1
    assert m.counter("serve.requests.failed").value == 1
    assert m.counter("serve.requests.finished").value == 1


def test_preempt_past_deadline_finishes_with_error(cfg, params):
    """Regression (DESIGN.md §14): a preemption victim already past its
    deadline must finish-with-error, not requeue for a re-prefill it
    can never turn into a timely response."""
    sc = ServeConfig(slots=2, cache_len=64, layout="paged",
                     page_size=4, num_pages=8)
    m = MetricsRegistry()
    loop = ServeLoop(cfg, params, sc, metrics=m)
    loop.submit(0, [5, 6, 7, 8])
    loop.submit(1, [9, 10, 11, 12])
    loop._admit()
    assert loop.active.all()
    loop.deadline_ms = 1000.0
    loop.arrival_s[1] = time.monotonic() - 10.0   # victim: expired
    assert loop._preempt_victim(0)
    assert loop.errors == {1: "deadline"}
    assert loop.queue == []                       # NOT requeued
    assert not loop.active[1]
    assert m.counter("serve.requests.failed").value == 1
    loop.alloc.check_invariants()


def test_preempt_within_deadline_still_requeues(cfg, params):
    sc = ServeConfig(slots=2, cache_len=64, layout="paged",
                     page_size=4, num_pages=8, deadline_ms=60000.0)
    loop = ServeLoop(cfg, params, sc)
    loop.submit(0, [5, 6, 7, 8])
    loop.submit(1, [9, 10, 11, 12])
    loop._admit()
    assert loop._preempt_victim(0)
    assert loop.queue and loop.queue[0][0] == 1   # historical behaviour
    assert loop.errors == {}


# --------------------------------------------------------- load shed -----
def test_load_shedding_on_occupancy_watermark(cfg, params):
    m = MetricsRegistry()
    sc = ServeConfig(slots=1, cache_len=64, layout="paged",
                     page_size=8, shed_occupancy=0.05)
    loop = ServeLoop(cfg, params, sc, metrics=m)
    for r in range(3):
        loop.submit(r, [5 + r] * 8)
    out = loop.run(max_new=4)
    # req 0 admitted while the pool was empty; its occupancy crosses
    # the watermark, so the queued tail is shed with an error
    assert loop.errors == {1: "shed", 2: "shed"}
    assert m.counter("serve.shed").value == 2
    assert len(out[0]) == 8 + 4


# ------------------------------------------------ kernel degradation -----
def test_kernel_dispatch_degrades_sticky_to_ref():
    from repro.kernels import paged_attention as pa
    from repro.kernels.ref import paged_decode_attention_ref
    pa.reset_fallback()
    rng = np.random.default_rng(0)
    B, H, hkv, dh, ps, maxp = 2, 4, 2, 8, 4, 3
    rows = 6 + 1
    q = jnp.asarray(rng.standard_normal((B, H, dh)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((rows, ps, hkv, dh)),
                     jnp.float32).at[-1].set(0)
    vp = jnp.asarray(rng.standard_normal((rows, ps, hkv, dh)),
                     jnp.float32).at[-1].set(0)
    tab = jnp.asarray(rng.integers(0, rows - 1, size=(B, maxp)),
                      jnp.int32)
    inj = ChaosInjector([ChaosEvent("kernel")])
    try:
        with chaos_mod.install(inj):
            out = pa.paged_decode_attention(q, kp, vp, tab,
                                            jnp.int32(5),
                                            interpret=True)
        key = pa.fallback_key(B, H, dh, ps, maxp)
        assert pa.fallback_active(key)
        assert pa.FALLBACK_EVENTS \
            and "kernel" in pa.FALLBACK_EVENTS[0]["reason"]
        ref = paged_decode_attention_ref(q, kp, vp, tab, jnp.int32(5))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=0, atol=1e-6)
        # sticky: the next dispatch routes straight to ref without
        # touching Pallas (no injector installed to prove it degraded)
        out2 = pa.paged_decode_attention(q, kp, vp, tab, jnp.int32(5),
                                         interpret=True)
        np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                                   rtol=0, atol=1e-6)
        assert len(pa.FALLBACK_EVENTS) == 1   # marked exactly once
    finally:
        pa.reset_fallback()


def _paged_inputs():
    rng = np.random.default_rng(0)
    B, H, hkv, dh, ps, maxp = 2, 4, 2, 8, 4, 3
    q = jnp.asarray(rng.standard_normal((B, H, dh)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((7, ps, hkv, dh)), jnp.float32)
    tab = jnp.asarray(rng.integers(0, 6, size=(B, maxp)), jnp.int32)
    return q, kp, kp, tab


def test_kernel_build_fault_propagates(monkeypatch):
    """A Pallas fault nobody injected is a real fault: it propagates
    and marks no fallback (a silent switch to the reference would hide
    that the kernel never ran on the chip)."""
    from repro.kernels import paged_attention as pa
    pa.reset_fallback()

    def broken(*a, **k):
        raise RuntimeError("mosaic refused the kernel")

    monkeypatch.setattr(pa, "paged_decode_attention_pallas", broken)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        pa.paged_decode_attention(*_paged_inputs(), jnp.int32(5),
                                  interpret=True)
    assert pa.FALLBACK_EVENTS == []


def test_serve_step_fault_propagates(cfg, params):
    """A failing jitted step on a guarded paged loop raises out of
    run(): no retry, no restore, no degradation."""
    sc = ServeConfig(slots=2, cache_len=64, layout="paged",
                     mode="continuous", prefill_budget=16)
    m = MetricsRegistry()
    loop = ServeLoop(cfg, params, sc, metrics=m)
    assert loop.guards

    def broken(*a):
        raise RuntimeError("device lost")

    loop._step = broken
    loop.submit(0, [5, 6, 7, 8])
    with pytest.raises(RuntimeError, match="device lost"):
        loop.run(max_new=4)
    assert not loop._kernel_degraded
    assert m.counter("serve.degraded").value == 0
    assert m.counter("serve.retries").value == 0


# ------------------------------------------------- power degradation -----
def test_power_chaos_degrades_to_zero_joules():
    from repro.obs import default_registry
    from repro.power import EnergyMeter, detect_backend
    before = default_registry().counter("power.faults").value
    inj = ChaosInjector([ChaosEvent("power")])
    with chaos_mod.install(inj):
        with EnergyMeter("x", backend=detect_backend("model")) as em:
            time.sleep(0.001)
    assert em.reading.joules == 0.0           # degraded, not crashed
    assert em.reading.seconds > 0             # the interval still timed
    assert default_registry().counter("power.faults").value == before + 1


# ------------------------------------------- checkpoint corruption -----
def _save_tree(root):
    tree = {"w": np.arange(64, dtype=np.float32).reshape(8, 8),
            "b": np.ones(8, np.float32)}
    save_checkpoint(str(root), 3, tree)
    return tree, root / "step_00000003"


def test_checkpoint_truncated_leaf_raises(tmp_path):
    tree, step_dir = _save_tree(tmp_path)
    leaf = step_dir / "w.npy"
    leaf.write_bytes(leaf.read_bytes()[:40])
    with pytest.raises(CheckpointCorruptionError, match="truncated"):
        load_checkpoint(str(tmp_path), 3, tree)


def test_checkpoint_bit_flip_raises(tmp_path):
    tree, step_dir = _save_tree(tmp_path)
    leaf = step_dir / "w.npy"
    data = bytearray(leaf.read_bytes())
    data[-5] ^= 0xFF                   # data region, header intact
    leaf.write_bytes(bytes(data))
    with pytest.raises(CheckpointCorruptionError, match="crc32"):
        load_checkpoint(str(tmp_path), 3, tree)
    # pre-existing `except OSError` recovery paths keep working
    assert issubclass(CheckpointCorruptionError, OSError)


def test_checkpoint_missing_leaf_and_bad_manifest(tmp_path):
    tree, step_dir = _save_tree(tmp_path)
    (step_dir / "b.npy").unlink()
    with pytest.raises(CheckpointCorruptionError, match="missing"):
        load_checkpoint(str(tmp_path), 3, tree)
    (step_dir / "manifest.json").write_text("{ not json")
    with pytest.raises(CheckpointCorruptionError, match="manifest"):
        load_checkpoint(str(tmp_path), 3, tree)


# ------------------------------------------------ integration (chaos) ----
CHAOS_SPEC = "alloc@step=2,nan@step=3:req=1,straggler@step=4:delay=0.05"


def _serve(cfg, params, chaos=None, metrics=None, mode="continuous"):
    sc = ServeConfig(slots=2, cache_len=64, layout="paged", mode=mode,
                     prefill_budget=16, chaos=chaos)
    loop = ServeLoop(cfg, params, sc,
                     metrics=metrics or MetricsRegistry())
    rng = np.random.default_rng(0)
    for r in range(4):
        loop.submit(r, rng.integers(2, cfg.vocab, size=8).tolist())
    return loop, loop.run(max_new=6)


def test_chaos_survivors_byte_identical(cfg, params):
    """The acceptance bar: an injected allocator fault (restored +
    replayed), a NaN poisoning (quarantined), and a straggler delay
    leave every *surviving* request's tokens byte-identical to a
    fault-free run, with the faulted request finished-with-error and
    the allocator invariant-clean."""
    _, base = _serve(cfg, params)
    m = MetricsRegistry()
    loop, out = _serve(cfg, params, chaos=CHAOS_SPEC, metrics=m)
    assert loop.errors == {1: "nan"}          # failed, not dropped
    assert {p for p, *_ in loop.chaos.fired} == \
        {"alloc", "nan", "straggler"}
    assert loop.chaos.exhausted()
    assert m.counter("serve.requests.failed").value == 1
    assert m.counter("serve.faults.nan").value == 1
    assert m.counter("serve.faults.straggler").value == 1
    assert m.counter("serve.faults.alloc").value >= 1
    assert m.counter("serve.retries").value >= 1
    assert m.counter("serve.restores").value >= 1
    assert loop.snapshotter is not None and loop.snapshotter.restores >= 1
    loop.alloc.check_invariants()
    for r, toks in base.items():
        if r in loop.errors:
            continue
        assert out[r] == toks, f"survivor {r} diverged"


def test_lockstep_step_fault_retries_transparently(cfg, params):
    _, base = _serve(cfg, params, mode="lockstep")
    m = MetricsRegistry()
    loop, out = _serve(cfg, params, chaos="step@step=1",
                       metrics=m, mode="lockstep")
    assert loop.errors == {}                  # fully transparent
    assert m.counter("serve.retries").value == 1
    assert m.counter("serve.restores").value == 1
    assert out == base
