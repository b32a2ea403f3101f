"""The reduction from a trace and a run record to the per-layer
metrics, on a small recorded trace of the chip and on hand-made
intervals."""
import json
from pathlib import Path

import pytest

from conftest import SMOKE_MODEL, dense
from harness import model, profile
from harness.cell import BENCH, Cell, metric_reader
from harness.record import Record, Step
from harness.runner import Reading

FIXTURES = Path(__file__).resolve().parent / "fixtures"
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]


def test_union_counts_overlaps_once():
    ivs = [(0, 10, "a"), (5, 15, "b"), (20, 30, "c"), (25, 26, "d")]
    assert profile.union(ivs, 0, 40) == 25
    assert profile.union(ivs, 8, 22) == 9
    assert profile.idle_gaps(ivs, 0, 40) == [(15, 20), (30, 40)]
    assert profile.idle_gaps(ivs, 2, 12) == []


def test_kernels_are_found_by_name():
    """Names as a v5e trace gives them: the op's HLO name, which for a
    Pallas kernel is the jitted function that calls pallas_call."""
    assert profile.op_name("%sfc_matmul_pallas.78 = f32[128,151936]{1,0} "
                           "custom-call(s32[2374]{0} %copy)") == \
        "sfc_matmul_pallas.78"
    tr = profile.Trace({"/device:TPU:0": [
        (0, 40, "while.13"), (0, 10, "sfc_matmul_pallas.78"),
        (10, 12, "fusion.3"), (12, 20, "sfc_matmul_pallas_x.11"),
        (20, 30, "sfc_matmul_pallas.85")]}, [])
    sfc = metric_reader("sfc_gemm_roofline.score").__globals__["KERNEL"]
    assert profile.kernel_seconds(tr, sfc, 0, 40) * 1e9 == 20
    assert profile.kernel_seconds(tr, sfc, 5, 25) * 1e9 == 10
    assert profile.busy_seconds(tr, 0, 50) * 1e9 == 40
    top = profile.top_ops(tr, 0, 40)
    assert [k for k, _ in top] == ["sfc_matmul_pallas",
                                   "sfc_matmul_pallas_x", "fusion"]
    assert top[0][1] * 1e9 == 20


def test_idle_gaps_are_named_by_the_host():
    tr = profile.Trace({"/device:TPU:0": [(0, 10, "a"), (40, 50, "b")]},
                       [("bench.window", 0, 60), ("bench.call", 0, 30),
                        ("bench.call", 45, 52)])
    assert profile.longest_gaps(tr, 0, 60) == [
        ["bench.call", 30e-9], ["bench.between_calls", 10e-9]]


def _reading(trace, rec):
    cell = Cell("x", 1, SMOKE_MODEL, {}, {}, [], [])
    return Reading(rec, trace, 0.0, 1e9, dense().shapes(SMOKE_MODEL), PEAKS,
                   cell, 1.0)


def test_shares_from_model_shapes_and_real_tokens():
    s = dense().shapes(SMOKE_MODEL)
    rec = Record(0.0, 1.0, [Step(0.0, 0.5, [(256, 0, True)] * 2),
                            Step(0.5, 1.0, [(16, 40, False)])])
    flops = 2 * sum(s.token_flops(p + 1, True) for p in range(256)) \
        + sum(s.token_flops(p + 1, False) for p in range(40, 56))
    tr = profile.Trace({"/device:TPU:0": [(0, 1e9, "x")]},
                       [("bench.call", 0, 1e9)])
    r = _reading(tr, rec)
    assert metric_reader("mfu_pct.score")(r) == pytest.approx(
        100 * flops / PEAKS["bf16_flops_per_s"])
    assert metric_reader("device_idle_pct.score")(r) == 0.0
    # no kernel events: the roofline reader finds nothing to read
    assert metric_reader("sfc_gemm_roofline.score")(r) is None


def test_gemm_bound_counts_weights_once_a_step():
    """The least GEMM time of one step over many rows is the larger of
    its FLOPs and its bytes; the head is counted for head rows only."""
    gemms = dense().shapes(SMOKE_MODEL).gemms()
    f, bw = PEAKS["bf16_flops_per_s"], PEAKS["hbm_bytes_per_s"]
    w = sum(g.count * g.K * g.N for g in gemms if not g.head)
    assert model.gemm_min_seconds(gemms, 1, 0, f, bw) >= 2 * w / bw
    assert model.gemm_min_seconds(gemms, 512, 512, f, bw) > \
        model.gemm_min_seconds(gemms, 512, 0, f, bw)
    assert model.gemm_min_seconds(gemms, 0, 0, f, bw) == 0.0


@pytest.mark.parametrize("rows", [1, 64, 4096])
def test_a_row_share_scales_rows_not_weights(rows):
    """A GEMM that runs over a share of the step's rows (a routed
    expert's) does that share of the FLOPs and moves that share of the
    rows' bytes, but reads all of its weights."""
    k, n, share = 2048, 1408, 6 / 64
    full = model.Gemm("moe/experts/gate", k, n, 4)
    part = model.Gemm("moe/experts/gate", k, n, 4, row_share=share)
    f, bw = PEAKS["bf16_flops_per_s"], PEAKS["hbm_bytes_per_s"]

    def least(g, f, bw):
        # one of the two bounds at a time: the other made free
        return model.gemm_min_seconds([g], rows, 0, f, bw)
    inf = float("inf")
    assert least(part, f, inf) == pytest.approx(
        share * least(full, f, inf), rel=1e-12)
    weights = 4 * 2 * k * n / bw
    assert least(part, inf, bw) - weights == pytest.approx(
        share * (least(full, inf, bw) - weights), rel=1e-12)
    assert model.matmul_flops([part], False) == pytest.approx(
        share * model.matmul_flops([full], False), rel=1e-12)


RECORDED = sorted(FIXTURES.glob("*.json"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.stem)
def test_recorded_chip_trace(path):
    """A few steps of a chip run, with their record: every share stays
    within 100%, and the kernels are where the trace names them."""
    fx = json.loads(path.read_text())
    tr = profile.Trace.from_json(fx["trace"])
    rec = Record(fx["record"]["t_open"], fx["record"]["t_close"],
                 [Step(st["t0"], st["t1"], [tuple(g) for g in st["segments"]])
                  for st in fx["record"]["steps"]])
    lo, hi = profile.window(tr)
    cell = Cell("x", 1, json.loads((BENCH / "configs" /
                                    f"{fx['config']}.json").read_text()),
                {}, {}, [], [])
    r = Reading(rec, tr, lo, hi, cell.family().shapes(cell.model), PEAKS,
                cell, 1.0)
    got = {}
    for name in fx["metrics"]:
        got[name] = metric_reader(name)(r)
        assert got[name] is not None, name
        if name.split(".")[0].endswith(("_pct", "_roofline")):
            assert 0.0 <= got[name] <= 100.0, (name, got[name])
    assert got == pytest.approx(fx["metrics"])


def test_end_to_end_rate_of_a_window():
    """A rate over the whole window: the steps that began in it, over
    its length from opening to close."""
    rec = Record(10.0, 12.0, [Step(9.5, 10.0, [(256, 0, True)]),
                              Step(10.0, 11.0, [(256, 0, True)] * 2),
                              Step(11.0, 12.0, [(256, 0, True)] * 2)])
    r = _reading(None, rec)
    assert metric_reader("scored_tok_s")(r) == 4 * 256 / 2.0
