"""The reduction from the program's scopes to the per-layer metrics
that read them (``harness/scopes.py``): on hand-made traces, on the
model's shapes, and on small recorded traces of the chip that carry the
scope of each op (``bench/record_scoped_fixture.py``)."""
import json
from pathlib import Path

import pytest

from conftest import SMOKE_MODEL, dense
from harness import model, profile, scopes
from harness.cell import BENCH, Cell, metric_reader
from harness.record import Record, Step
from harness.runner import Reading

FIXTURES = Path(__file__).resolve().parent / "fixtures"
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]
SCOPED = ["sfc_attn_proj_roofline.score", "sfc_mlp_roofline.score",
          "sfc_head_roofline.score", "attention_core_pct.score"]
KERNEL = r"^sfc_matmul_pallas\b"
# the dense family's GEMM roles
ROLES = scopes.roles_of(dense().shapes(SMOKE_MODEL))

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
    return Reading(rec, trace, 0.0, 100.0, dense().shapes(m), PEAKS, cell,
                   1.0)


def test_role_is_the_innermost_gemm_scope():
    assert ROLES == ("attn/q", "attn/k", "attn/v", "attn/o", "mlp/gate",
                     "mlp/up", "mlp/down", "head")
    assert [scopes.role(HAND[f"sfc_matmul_pallas.{i}"], ROLES)
            for i in range(1, 5)] == ["attn/q", "attn/o", "mlp/gate", "head"]
    assert scopes.role("jit(score)/jit(_take)/gather", ROLES) is None
    # a part is a whole path component: "core" is no role, "qk" no "q"
    assert scopes.role(HAND["fusion.1"], ROLES) is None
    assert scopes.role("a/attn/qk/b", ROLES) is None
    assert scopes.role("a/head/mlp/up/b", ROLES) == "mlp/up"
    assert scopes.under(HAND["fusion.2"], "attn/core")
    assert not scopes.under("x/attn/o/core2", "attn/core")
    assert [scopes.part(HAND[n], ROLES) for n in (
        "sfc_matmul_pallas.1", "fusion.1", "sfc_matmul_pallas.3",
        "sfc_matmul_pallas.4", "dynamic-slice_bitcast_fusion.1",
        "exponential_reduce_fusion")] == [
        "attn/q", "attn/core", "mlp/gate", "head", "layers", "outside"]


def test_roles_are_told_apart_by_their_whole_path():
    """An expert's gate and the dense MLP's gate are two roles: each op
    falls to the longest declared role on its path, whatever its last
    part."""
    declared = ("attn/q", "mlp/gate", "moe/experts/gate", "moe/router",
                "head")
    expert = f"{LAYER}/moe/experts/gate/{PALLAS}"
    mlp = f"{LAYER}/mlp/gate/{PALLAS}"
    assert scopes.role(expert, declared) == "moe/experts/gate"
    assert scopes.role(mlp, declared) == "mlp/gate"
    assert scopes.role(f"{LAYER}/moe/experts/up/{PALLAS}", declared) is None
    # a gate under the experts but not the declared path's is no role
    assert scopes.role(f"{LAYER}/experts/gate/{PALLAS}", declared) is None
    tr = profile.Trace({"/device:TPU:0": [
        (0, 30, "sfc_matmul_pallas.1"), (30, 40, "sfc_matmul_pallas.2")]},
        [])
    tr.scopes = {"sfc_matmul_pallas.1": expert, "sfc_matmul_pallas.2": mlp}
    sec = {r: scopes.role_seconds(tr, tr.scopes, KERNEL, (r,), declared, 0,
                                  100) * 1e9 for r in declared}
    assert sec == pytest.approx({"attn/q": 0, "mlp/gate": 10,
                                 "moe/experts/gate": 30, "moe/router": 0,
                                 "head": 0})
    assert {k: v * 1e9 for k, v in scopes.part_seconds(
        tr, tr.scopes, declared, 0, 100).items()} == pytest.approx(
        {"moe/experts/gate": 30, "mlp/gate": 10})


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
    assert dots and all(scopes.role(p, ("q",)) == "q"
                        and scopes.part(p, ()) == "layers"
                        for p in dots.values()), progs[name]
    # with no device plane, every program the profile holds
    assert scopes.load(str(tmp_path)).keys() >= progs[name].keys()
    assert scopes.load(str(tmp_path / "nothing")) == {}


def test_role_reduction_on_a_hand_made_trace():
    tr = _hand_trace()
    sec = {r: scopes.role_seconds(tr, tr.scopes, KERNEL, (r,), ROLES, 0,
                                  100) * 1e9 for r in ROLES}
    assert sec == pytest.approx({"attn/q": 10, "attn/k": 0, "attn/v": 0,
                                 "attn/o": 10, "mlp/gate": 35, "mlp/up": 0,
                                 "mlp/down": 0, "head": 15})
    assert sum(sec.values()) == pytest.approx(
        profile.kernel_seconds(tr, KERNEL, 0, 100) * 1e9)
    # clipped to the window, as the kernel's own reader clips
    assert scopes.role_seconds(tr, tr.scopes, KERNEL, ("mlp/gate",), ROLES,
                               0, 50) * 1e9 == pytest.approx(15)
    assert scopes.scope_seconds(tr, tr.scopes, "attn/core", 0, 100) \
        * 1e9 == pytest.approx(10)
    # the while spans its ops and is no part of its own
    parts = {k: v * 1e9 for k, v in
             scopes.part_seconds(tr, tr.scopes, ROLES, 0, 100).items()}
    assert parts == pytest.approx({"layers": 5, "attn/q": 10,
                                   "attn/core": 10, "attn/o": 10,
                                   "mlp/gate": 35, "head": 15,
                                   "outside": 5})
    assert sum(parts.values()) == pytest.approx(
        profile.busy_seconds(tr, 0, 100) * 1e9)

    rec = Record(0.0, 1.0, [Step(0.0, 1.0, [(256, 0, True)])])
    r = _reading(tr, rec)
    gemms = r.shapes.gemms()
    f, bw = PEAKS["bf16_flops_per_s"], PEAKS["hbm_bytes_per_s"]

    def want(roles, t_ns):
        return 100 * model.gemm_min_seconds(gemms, 256, 256, f, bw,
                                            roles=roles) / (t_ns / 1e9)
    assert metric_reader("sfc_attn_proj_roofline.score")(r) == \
        pytest.approx(want(("attn/q", "attn/k", "attn/v", "attn/o"), 20))
    assert metric_reader("sfc_mlp_roofline.score")(r) == \
        pytest.approx(want(("mlp/gate", "mlp/up", "mlp/down"), 35))
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
    gemms = dense().shapes(m).gemms()
    f, bw = PEAKS["bf16_flops_per_s"], PEAKS["hbm_bytes_per_s"]
    parts = [model.gemm_min_seconds(gemms, rows, head_rows, f, bw,
                                    roles=(r,)) for r in ROLES]
    assert sum(parts) == pytest.approx(
        model.gemm_min_seconds(gemms, rows, head_rows, f, bw), rel=1e-12)
    assert model.gemm_min_seconds(gemms, rows, head_rows, f, bw,
                                  roles=ROLES) == pytest.approx(sum(parts),
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
    r = Reading(rec, tr, lo, hi, dense().shapes(m), PEAKS,
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
    assert sorted(scopes.role(tr.scopes[n], ROLES) for n in sfc) == \
        sorted(ROLES)
    by_role = sum(scopes.role_seconds(tr, tr.scopes, KERNEL, (x,), ROLES, lo,
                                      hi) for x in ROLES)
    assert by_role == pytest.approx(
        profile.kernel_seconds(tr, KERNEL, lo, hi), rel=1e-3)
    parts = scopes.part_seconds(tr, tr.scopes, ROLES, lo, hi)
    assert parts.get("outside", 0.0) < 0.02 * profile.busy_seconds(
        tr, lo, hi)
