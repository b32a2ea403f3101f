"""Tests for repro.analysis: kernel contract checker, SFC schedule
verifier (bijection proofs + static LRU cross-check), and the HLO
traffic auditor (ISSUE 8 / DESIGN.md §13)."""
import numpy as np
import pytest

from repro.analysis import (
    STATIC_DRIFT_TOL,
    audit_hlo,
    check_attn_contract,
    check_gemm_contract,
    crosscheck_cost_model,
    gemm_vmem_bytes,
    verify_order,
    verify_schedule,
)
from repro.core.energy import TPU_V5E
from repro.core.schedule import SCHEDULES, grid_schedule
from repro.tune.cost import EpilogueSpec, TuneConfig


# ------------------------------------------------------- contract checker --
def test_contract_accepts_every_default_candidate():
    from repro.tune import candidate_configs

    for m, n, k in ((512, 512, 512), (2048, 2048, 256), (300, 300, 300)):
        for cfg in candidate_configs(m, n, k):
            rep = check_gemm_contract(cfg, m, n, k, level="full")
            assert rep.ok, (cfg, rep.to_dict())


def test_contract_rejects_overbudget_vmem():
    """A 4096^2 output block + f32 accumulator is ~150 MB >> the 115 MB
    budget; the checker must veto it even though it divides exactly."""
    cfg = TuneConfig(schedule="morton", bm=4096, bn=4096, bk=512)
    rep = check_gemm_contract(cfg, 4096, 4096, 512, level="fast")
    assert not rep.ok
    assert "vmem-budget" in rep.codes()
    assert rep.stats["vmem_bytes"] > rep.stats["vmem_budget"]


def test_contract_epilogue_tightens_vmem():
    base = gemm_vmem_bytes(TuneConfig(bm=256, bn=256, bk=256))
    ep = EpilogueSpec(bias=True, activation="gelu", residual=True)
    full = gemm_vmem_bytes(TuneConfig(bm=256, bn=256, bk=256), 4, ep)
    # bias row (an 8 x 128 f32 tile per 128 columns) + residual (bm, bn)
    # block, each double-buffered as the kernel's pipeline holds them
    assert full == base + 2 * (8 * 256 * 4 + 256 * 256 * 4)


def test_contract_rejects_prefetchless_nonsquare():
    cfg = TuneConfig(schedule="hilbert", use_prefetch=False)
    rep = check_gemm_contract(cfg, 3 * 128, 128, 256, level="fast")
    assert "no-closed-form" in rep.codes()
    # the same geometry with the prefetch table is fine
    ok = check_gemm_contract(
        TuneConfig(schedule="hilbert"), 3 * 128, 128, 256, level="full")
    assert ok.ok


def test_contract_xla_baseline_trivially_ok():
    rep = check_gemm_contract(TuneConfig(schedule="xla"), 7, 9, 11)
    assert rep.ok and rep.stats["grid"] is None


def test_contract_full_level_replays_grid():
    rep = check_gemm_contract(
        TuneConfig(schedule="hilbert", bm=128, bn=128, bk=128),
        1024, 768, 512, level="full")
    assert rep.ok
    assert rep.stats["grid"] == (8, 6, 4)
    assert rep.stats["tiles"] == 48


# ------------------------------------------------------ schedule verifier --
@pytest.mark.parametrize("name", sorted(SCHEDULES))
def test_every_schedule_is_a_bijection(name):
    for rows, cols in ((1, 1), (2, 2), (4, 4), (16, 16), (3, 5), (8, 2)):
        rep = verify_schedule(name, rows, cols,
                              g=2 if name == "supertile" else 0)
        assert rep.ok, rep.to_dict()


def test_verifier_catches_transposed_corruption():
    """Transposing one entry of a non-symmetric permutation makes one
    tile double-written and another never written -- exactly the
    write-write race the verifier exists to catch."""
    order = np.array(grid_schedule("rowmajor", 4, 3))
    assert not np.array_equal(order[1], order[1][::-1])
    order[1] = order[1][::-1]  # (0, 1) -> (1, 0), duplicating step 3
    rep = verify_order(order, 4, 3)
    assert not rep.ok
    assert "write-race" in rep.codes()
    assert "missed-tile" in rep.codes()
    assert any("(1, 0)" in v.message and "2 times" in v.message
               for v in rep.violations)


def test_verifier_catches_oob_and_short_orders():
    order = np.array(grid_schedule("morton", 4, 4))
    order[5] = (7, 7)  # outside the 4x4 grid
    rep = verify_order(order, 4, 4)
    assert {"oob-tile", "missed-tile"} <= rep.codes()
    rep = verify_order(order[:-2], 4, 4)
    assert "missed-tile" in rep.codes()


@pytest.mark.parametrize("schedule", ["rowmajor", "morton", "hilbert"])
@pytest.mark.parametrize("mt", [2, 4, 8, 16])
def test_static_lru_matches_cost_model(schedule, mt):
    """The stack-distance replay is an independent implementation of the
    cost model's LRU traffic accounting; on every grid up to 16x16 the
    two byte counts agree within STATIC_DRIFT_TOL (ISSUE 8 acceptance)."""
    rep = crosscheck_cost_model(schedule, mt, mt, 2)
    assert rep.ok, rep.to_dict()
    assert rep.stats["rel_drift"] <= STATIC_DRIFT_TOL
    assert rep.stats["static_bytes"] > 0


def test_static_lru_detects_planted_drift():
    """Same machinery, wrong capacity: the static replay at a quarter of
    the model's cache must disagree beyond tolerance on a pressured
    grid -- proving the cross-check can actually fail."""
    from repro.analysis.schedule import stack_distance_traffic
    from repro.tune.cost import predict

    mt, kt = 8, 2
    cfg = TuneConfig(schedule="rowmajor")
    est = predict(cfg, mt * 128, mt * 128, kt * 128, 4, capacity=8)
    order = grid_schedule("rowmajor", mt, mt)
    bb = {t: 128 * 128 * 4 for t in "ABC"}
    wrong = stack_distance_traffic(order, kt, bb, capacity=2)
    rel = abs(wrong["total_bytes"] - est.traffic_bytes) / est.traffic_bytes
    assert rel > STATIC_DRIFT_TOL


# ------------------------------------------------- paged-attention tables --
def _spec(slots=2, cache_len=256, heads=4, kv=2, d=64, ps=64):
    from repro.tune import DecodeAttnSpec
    from repro.tune.cost import AttnSpec

    return DecodeAttnSpec(slots=slots, cache_len=cache_len,
                          n_heads=heads, n_kv_heads=kv, d_head=d,
                          attn=AttnSpec(kind="paged", page_size=ps))


def test_attn_contract_clean_table_passes():
    bt = np.array([[0, 1, -1, -1], [2, 3, -1, -1]])
    rep = check_attn_contract(_spec(), block_table=bt, num_pages=8,
                              lengths=np.array([100, 120]))
    assert rep.ok, rep.to_dict()


def test_attn_contract_flags_oob_page():
    bt = np.array([[0, 9, -1, -1], [2, 3, -1, -1]])  # 9 >= num_pages
    rep = check_attn_contract(_spec(), block_table=bt, num_pages=8)
    assert "page-oob" in rep.codes()


def test_attn_contract_flags_aliased_page():
    bt = np.array([[0, 0, -1, -1], [2, 3, -1, -1]])  # slot 0 maps 0 twice
    rep = check_attn_contract(_spec(), block_table=bt, num_pages=8)
    assert "page-alias" in rep.codes()


def test_attn_contract_flags_unmapped_write_target():
    # slot 0 at length 100 writes into logical page 1, which is -1
    bt = np.array([[0, -1, -1, -1], [2, 3, -1, -1]])
    rep = check_attn_contract(_spec(), block_table=bt, num_pages=8,
                              lengths=np.array([100, 120]))
    assert "zero-row-write" in rep.codes()


def test_attn_contract_gqa_divisibility():
    rep = check_attn_contract(_spec(heads=5, kv=2))
    assert "gqa-divisibility" in rep.codes()


# ------------------------------------------------------------- HLO audit --
_SYNTH = """\
HloModule synth

ENTRY %main (p0: f32[256,128], p1: f32[128,256], p2: f32[256,256]) -> f32[256,256] {
  %p0 = f32[256,128]{1,0} parameter(0)
  %p1 = f32[128,256]{1,0} parameter(1)
  %p2 = f32[256,256]{1,0} parameter(2)
  %d = f32[256,256]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %a = f32[256,256]{1,0} add(%d, %p2)
}
"""


def test_audit_flags_synthetic_roundtrip():
    rep = audit_hlo(_SYNTH, gemm_shape=(256, 256),
                    forbid_epilogue_roundtrips=True)
    assert not rep.ok
    assert "unfused-epilogue" in rep.codes()
    # without the declared shape restriction it still fires
    assert "unfused-epilogue" in audit_hlo(_SYNTH).codes()
    # at a different declared shape the dot is sub-problem sized: clean
    assert audit_hlo(_SYNTH, gemm_shape=(512, 512),
                     forbid_epilogue_roundtrips=True).ok


def test_audit_flags_host_transfer_and_collectives():
    txt = _SYNTH.replace(
        "ROOT %a = f32[256,256]{1,0} add(%d, %p2)",
        "%s = f32[256,256]{1,0} all-reduce(%d), replica_groups={}\n"
        "  ROOT %o = f32[256,256]{1,0} copy(%s), is_host_transfer=true")
    rep = audit_hlo(txt, forbid_host_transfers=True,
                    forbid_collectives=True)
    assert not rep.ok
    assert {"host-transfer", "unexpected-collective"} <= rep.codes()
    # tolerated when not forbidden (e.g. a sharded train step)
    assert audit_hlo(txt).ok


def test_audit_flags_large_bf16_upcast():
    txt = _SYNTH.replace(
        "ROOT %a = f32[256,256]{1,0} add(%d, %p2)",
        "%c = f32[1024,1024]{1,0} convert(bf16[1024,1024]{1,0} %p2)\n"
        "  ROOT %a = f32[256,256]{1,0} add(%d, %p2)")
    assert "f32-upcast" in audit_hlo(txt).codes()


def test_audit_byte_parity_on_compiled_gemm():
    """End to end on this backend's real compiled dot: analyzer traffic
    must match the cost model's xla prediction (ISSUE 8 acceptance)."""
    from repro.analysis import audit_gemm

    rep = audit_gemm(512, 512, 256)
    assert rep.ok, rep.to_dict()
    assert rep.stats["byte_drift"] <= rep.stats["byte_tol"]
    assert rep.stats["flops"] == 2 * 512 * 512 * 256


@pytest.mark.slow
def test_epilogue_fusion_gate_end_to_end():
    """The CI regression pair: the deliberately unfused dot+gelu build
    is flagged, the fused Pallas interpret build is clean."""
    from repro.analysis import epilogue_fusion_gate

    gate = epilogue_fusion_gate()
    assert gate["gate_ok"], {k: v.to_dict() if hasattr(v, "to_dict")
                             else v for k, v in gate.items()}
    assert not gate["unfused"].ok
    assert gate["fused"].ok


def test_report_serialises_and_raises():
    cfg = TuneConfig(schedule="morton", bm=4096, bn=4096, bk=512)
    rep = check_gemm_contract(cfg, 4096, 4096, 512, level="fast")
    d = rep.to_dict()
    assert d["ok"] is False and d["violations"]
    with pytest.raises(AssertionError, match="VMEM"):
        rep.raise_if_failed()


def test_vmem_budget_tracks_hw():
    cfg = TuneConfig(bm=256, bn=256, bk=256)
    need = gemm_vmem_bytes(cfg)
    # A, B, C double-buffered; the dot's f32 product and accumulator
    assert need == 2 * (3 * 256 * 256) * 4 + 2 * 256 * 256 * 4
    # K in one block holds no accumulator
    assert gemm_vmem_bytes(cfg, k=256) == need - 256 * 256 * 4
    rep = check_gemm_contract(cfg, 1024, 1024, 1024, level="fast")
    assert rep.stats["vmem_budget"] == int(TPU_V5E.vmem_per_chip * 0.9)
