"""The reduction from the program's scopes to the per-layer metrics
that read them (``harness/scopes.py``): on hand-made traces, on the
model's shapes, and on small recorded traces of the chip that carry the
scope of each op (``bench/record_scoped_fixture.py``)."""
import json
from pathlib import Path

import pytest

from conftest import SMOKE_MODEL
from harness import model, profile, scopes
from harness.cell import BENCH, Cell, metric_reader
from harness.record import Record, Step
from harness.runner import Reading

FIXTURES = Path(__file__).resolve().parent / "fixtures"
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]
SCOPED = ["sfc_attn_proj_roofline.score", "sfc_mlp_roofline.score",
          "sfc_head_roofline.score", "attention_core_pct.score"]
KERNEL = r"^sfc_matmul_pallas\b"

# op_name paths as the program gives them (repro.models), in the scan
# under remat and outside it
LAYER = "jit(score)/layers/while/body/closed_call/checkpoint"
PALLAS = "jit(_sfc_matmul)/jit(sfc_matmul_pallas)/pallas_call"
HAND = {
    "sfc_matmul_pallas.1": f"{LAYER}/attn/q/{PALLAS}",
    "sfc_matmul_pallas.2": f"{LAYER}/attn/o/{PALLAS}",
    "sfc_matmul_pallas.3": f"{LAYER}/mlp/gate/{PALLAS}",
    "sfc_matmul_pallas.4": f"jit(score)/head/{PALLAS}",
    "fusion.1": f"{LAYER}/attn/core/bqhgd,bkhd->bhgqk/dot_general",
    "fusion.2": f"{LAYER}/attn/core/mul",
    "dynamic-slice_bitcast_fusion.1": "jit(score)/layers/while/body/"
                                      "dynamic_slice",
    "while.1": "jit(score)/layers/while",
    "exponential_reduce_fusion": "jit(score)/reduce_max",
}


def _hand_trace():
    """One device, ns: each op once, the scan's while over the layer's
    ops, and a 10 ns idle gap."""
    tr = profile.Trace({"/device:TPU:0": sorted([
        (0, 70, "while.1"), (0, 5, "dynamic-slice_bitcast_fusion.1"),
        (5, 15, "sfc_matmul_pallas.1"), (15, 20, "fusion.1"),
        (20, 25, "fusion.2"), (25, 35, "sfc_matmul_pallas.2"),
        (35, 70, "sfc_matmul_pallas.3"), (80, 95, "sfc_matmul_pallas.4"),
        (95, 100, "exponential_reduce_fusion")])}, [])
    tr.scopes = dict(HAND)
    return tr


def _reading(trace, rec, m=SMOKE_MODEL):
    cell = Cell("x", 1, m, {}, {}, [], [])
    return Reading(rec, trace, 0.0, 100.0, model.shapes(m), PEAKS, cell,
                   1.0)


def test_role_is_the_innermost_gemm_scope():
    assert [scopes.role(HAND[f"sfc_matmul_pallas.{i}"])
            for i in range(1, 5)] == ["q", "o", "gate", "head"]
    assert scopes.role("jit(score)/jit(_take)/gather") is None
    # a part is a whole path component: "core" is no role, "qk" no "q"
    assert scopes.role(HAND["fusion.1"]) is None
    assert scopes.role("a/qk/b") is None
    assert scopes.role("a/head/mlp/up/b") == "up"
    assert scopes.under(HAND["fusion.2"], "attn/core")
    assert not scopes.under("x/attn/o/core2", "attn/core")
    assert [scopes.part(HAND[n]) for n in (
        "sfc_matmul_pallas.1", "fusion.1", "sfc_matmul_pallas.3",
        "sfc_matmul_pallas.4", "dynamic-slice_bitcast_fusion.1",
        "exponential_reduce_fusion")] == [
        "attn/q", "attn/core", "mlp/gate", "head", "layers", "outside"]


def test_the_scope_map_is_read_from_the_profiles_programs(tmp_path):
    """A profile holds the compiled HLO of each program it saw, and each
    instruction's op_name there is the scope path (a CPU profile has no
    device plane, but the same metadata plane)."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("layers"):
            with jax.named_scope("q"):
                y = x @ x
        return jnp.tanh(y).sum()
    g = jax.jit(f)
    x = jnp.ones((64, 64))
    g(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        g(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    progs = scopes.programs(str(tmp_path))
    (name,) = [n for n in progs if n.startswith("jit_f(")]
    dots = {n: p for n, p in progs[name].items()
            if p.endswith("dot_general")}
    assert dots and all(scopes.role(p) == "q" and scopes.part(p) == "layers"
                        for p in dots.values()), progs[name]
    # with no device plane, every program the profile holds
    assert scopes.load(str(tmp_path)).keys() >= progs[name].keys()
    assert scopes.load(str(tmp_path / "nothing")) == {}


def test_role_reduction_on_a_hand_made_trace():
    tr = _hand_trace()
    sec = {r: scopes.role_seconds(tr, tr.scopes, KERNEL, (r,), 0, 100)
           * 1e9 for r in scopes.ROLES}
    assert sec == pytest.approx({"q": 10, "k": 0, "v": 0, "o": 10,
                                 "gate": 35, "up": 0, "down": 0,
                                 "head": 15})
    assert sum(sec.values()) == pytest.approx(
        profile.kernel_seconds(tr, KERNEL, 0, 100) * 1e9)
    # clipped to the window, as the kernel's own reader clips
    assert scopes.role_seconds(tr, tr.scopes, KERNEL, ("gate",), 0,
                               50) * 1e9 == pytest.approx(15)
    assert scopes.scope_seconds(tr, tr.scopes, "attn/core", 0, 100) \
        * 1e9 == pytest.approx(10)
    # the while spans its ops and is no part of its own
    parts = {k: v * 1e9 for k, v in
             scopes.part_seconds(tr, tr.scopes, 0, 100).items()}
    assert parts == pytest.approx({"layers": 5, "attn/q": 10,
                                   "attn/core": 10, "attn/o": 10,
                                   "mlp/gate": 35, "head": 15,
                                   "outside": 5})
    assert sum(parts.values()) == pytest.approx(
        profile.busy_seconds(tr, 0, 100) * 1e9)

    rec = Record(0.0, 1.0, [Step(0.0, 1.0, [(256, 0, True)])])
    r = _reading(tr, rec)
    s = r.shapes
    f, bw = PEAKS["bf16_flops_per_s"], PEAKS["hbm_bytes_per_s"]

    def want(roles, t_ns):
        return 100 * scopes.role_min_seconds(s, roles, 256, 256, f, bw) \
            / (t_ns / 1e9)
    assert metric_reader("sfc_attn_proj_roofline.score")(r) == \
        pytest.approx(want(("q", "k", "v", "o"), 20))
    assert metric_reader("sfc_mlp_roofline.score")(r) == \
        pytest.approx(want(("gate", "up", "down"), 35))
    assert metric_reader("sfc_head_roofline.score")(r) == \
        pytest.approx(want(("head",), 15))
    assert metric_reader("attention_core_pct.score")(r) == \
        pytest.approx(100 * 10 / 90)


def test_without_scopes_the_readers_read_nothing():
    """A program that names none of its parts (or a trace with no scope
    map) gives no reading, and raises nothing."""
    tr = _hand_trace()
    tr.scopes = {n: p.replace("/attn/", "/").replace("/mlp/", "/")
                 .replace("/head/", "/").replace("/q/", "/")
                 .replace("/o/", "/").replace("/gate/", "/")
                 for n, p in HAND.items()}
    rec = Record(0.0, 1.0, [Step(0.0, 1.0, [(256, 0, True)])])
    for trace in (tr, profile.Trace(tr.device_ops, []), None):
        r = _reading(trace, rec)
        assert [metric_reader(m)(r) for m in SCOPED] == [None] * 4


@pytest.mark.parametrize("config", ["smoke", "qwen3-1.7b", "glm4-9b-20L"])
@pytest.mark.parametrize("rows,head_rows", [(2048, 2048), (4096, 128),
                                            (1, 1), (512, 0), (0, 64)])
def test_role_least_times_sum_to_the_gemm_bound(config, rows, head_rows):
    m = SMOKE_MODEL if config == "smoke" else json.loads(
        (BENCH / "configs" / f"{config}.json").read_text())
    s = model.shapes(m)
    f, bw = PEAKS["bf16_flops_per_s"], PEAKS["hbm_bytes_per_s"]
    parts = [scopes.role_min_seconds(s, (r,), rows, head_rows, f, bw)
             for r in scopes.ROLES]
    assert sum(parts) == pytest.approx(
        s.gemm_min_seconds(rows, head_rows, f, bw), rel=1e-12)
    assert scopes.role_min_seconds(s, scopes.ROLES, rows, head_rows, f,
                                   bw) == pytest.approx(sum(parts),
                                                        rel=1e-12)


RECORDED = sorted(FIXTURES.glob("*.scoped.json"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.stem)
def test_recorded_scoped_chip_trace(path):
    """A few steps of a chip run with the scope of each op: all seven
    per-layer readers read what was recorded, every share stays within
    100%, every SFC kernel event has exactly one GEMM role, the roles'
    kernel seconds add up to the kernel's, and under 2% of the busy time
    lies outside the program's scopes."""
    fx = json.loads(path.read_text())
    tr = profile.Trace.from_json(fx["trace"])
    tr.scopes = fx["scopes"]
    rec = Record(fx["record"]["t_open"], fx["record"]["t_close"],
                 [Step(st["t0"], st["t1"], [tuple(g) for g in st["segments"]])
                  for st in fx["record"]["steps"]])
    lo, hi = profile.window(tr)
    m = json.loads((BENCH / "configs" / f"{fx['config']}.json").read_text())
    r = Reading(rec, tr, lo, hi, model.shapes(m), PEAKS,
                Cell("x", 1, m, {}, {}, [], []), 1.0)
    want = {**fx["metrics"], **fx["scoped_metrics"]}
    assert set(fx["scoped_metrics"]) == set(SCOPED)
    assert len(want) == 7
    got = {name: metric_reader(name)(r) for name in want}
    for name, v in got.items():
        assert v is not None, name
        assert 0.0 <= v <= 100.0, (name, v)
    assert got == pytest.approx(want)

    sfc = {n for ops in tr.device_ops.values() for _, _, n in ops
           if n.startswith("sfc_matmul_pallas")}
    assert len(sfc) == 8
    assert sorted(scopes.role(tr.scopes[n]) for n in sfc) == \
        sorted(scopes.ROLES)
    by_role = sum(scopes.role_seconds(tr, tr.scopes, KERNEL, (x,), lo, hi)
                  for x in scopes.ROLES)
    assert by_role == pytest.approx(
        profile.kernel_seconds(tr, KERNEL, lo, hi), rel=1e-3)
    parts = scopes.part_seconds(tr, tr.scopes, lo, hi)
    assert parts.get("outside", 0.0) < 0.02 * profile.busy_seconds(
        tr, lo, hi)
