"""Kernel contract checker (DESIGN.md §13.1).

Statically proves, for a :class:`~repro.tune.cost.TuneConfig` on a
concrete GEMM shape (or a decode-attention problem on a block table),
the invariants the Pallas kernels otherwise enforce only by crashing at
compile time or -- worse -- by silently corrupting output tiles:

* **structure** -- positive shape/blocks, known schedule;
* **VMEM budget** -- the kernel's resident working set (A block + B
  block + staged C block + f32 accumulator scratch + epilogue ``(1,
  bn)`` bias tile + residual block) against a per-core budget
  (``hw.vmem_per_chip``, same 0.9 fraction the tuner's candidate
  enumeration uses);
* **closed-form decode** -- ``use_prefetch=False`` requires the
  in-``index_map`` decode, which exists only on square power-of-two
  (padded) grids for morton/hilbert and on any grid for
  rowmajor/colmajor;
* **grid/index-map replay** (``level="full"``) -- the schedule
  permutation is evaluated over the *whole* grid and every index map
  of ``repro.kernels.sfc_matmul`` is applied to it: ``a_map(t, kk) ->
  (i, kk)`` and ``b_map -> (kk, j)`` stay in bounds, and ``o_map ->
  (i, j)`` hits every output tile exactly once (a duplicate (i, j) in
  the permutation is a write-write race between grid steps; a missing
  one is an unwritten tile).  For closed-form configs the kernel's own
  ``decode_step`` is additionally evaluated at every t and must agree
  with the prefetch table.

The checker is pure host-side arithmetic -- milliseconds for the fast
level, O(grid) numpy for the full level -- so the autotuner runs it on
every candidate before anything compiles
(:func:`repro.tune.autotune.candidate_configs`).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from repro.core.energy import TPU_V5E
from repro.core.schedule import SCHEDULES, grid_schedule, is_pow2, \
    schedule_extra_kwargs
from repro.tune.cost import EpilogueSpec, TuneConfig

__all__ = ["Violation", "ContractReport", "VMEM_FRAC", "gemm_vmem_bytes",
           "check_gemm_contract", "check_attn_contract"]

# fraction of per-core VMEM a kernel's working set may claim -- the same
# headroom the tuner's candidate filter has always applied (semaphores,
# scalar-prefetch tables and compiler spills live in the rest)
VMEM_FRAC = 0.9

# how large a grid the full-level replay will evaluate the closed-form
# decode on, step by step (the permutation proof itself is vectorised
# numpy and runs at any size)
_MAX_DECODE_TILES = 4096


@dataclass(frozen=True)
class Violation:
    """One broken invariant: a stable machine-readable ``code`` plus a
    human diagnostic.  Codes are part of the tool's contract (CI and the
    tuner dispatch on them): ``bad-config``, ``unknown-schedule``,
    ``vmem-budget``, ``no-closed-form``, ``oob-tile``, ``write-race``,
    ``missed-tile``, ``decode-mismatch``, ``page-oob``, ``page-alias``,
    ``zero-row-write``, ``table-extent``, ``gqa-divisibility``."""

    code: str
    message: str

    def to_dict(self) -> dict:
        return {"code": self.code, "message": self.message}


@dataclass
class ContractReport:
    subject: str
    violations: list[Violation] = field(default_factory=list)
    stats: dict = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.violations

    def codes(self) -> set[str]:
        return {v.code for v in self.violations}

    def add(self, code: str, message: str) -> None:
        self.violations.append(Violation(code, message))

    def to_dict(self) -> dict:
        return {"subject": self.subject, "ok": self.ok,
                "violations": [v.to_dict() for v in self.violations],
                "stats": self.stats}

    def raise_if_failed(self) -> None:
        if not self.ok:
            raise AssertionError(
                f"{self.subject}: {len(self.violations)} contract "
                f"violation(s): "
                + "; ".join(v.message for v in self.violations))


def gemm_vmem_bytes(cfg: TuneConfig, dtype_bytes: int = 4,
                    epilogue: EpilogueSpec | None = None,
                    k: int | None = None) -> int:
    """Scoped VMEM of one ``sfc_matmul`` grid step: the kernel's own
    count (:func:`repro.kernels.sfc_matmul.sfc_vmem_bytes`, double
    buffers included), operands and output in ``dtype_bytes``.  ``k``,
    the GEMM's K, says whether the f32 accumulator and a zeroed last K
    block are held; None counts the accumulator, as where K takes more
    than one block."""
    from repro.kernels.sfc_matmul import sfc_vmem_bytes

    ep = None if (epilogue is None or epilogue.is_noop) else epilogue
    kt, k_tail = (2, 0) if k is None else (-(-k // cfg.bk), k % cfg.bk)
    return sfc_vmem_bytes(cfg.bm, cfg.bn, cfg.bk, dtype_bytes, dtype_bytes,
                          bias=bool(ep and ep.bias),
                          residual=bool(ep and ep.residual), kt=kt,
                          k_tail=k_tail)


def _closed_form_ok(schedule: str, mt: int, nt: int) -> bool:
    if schedule in ("rowmajor", "colmajor"):
        return True
    if schedule in ("morton", "hilbert"):
        return mt == nt and is_pow2(mt)
    return False


def check_gemm_contract(
    cfg: TuneConfig,
    m: int,
    n: int,
    k: int,
    *,
    dtype_bytes: int = 4,
    epilogue: EpilogueSpec | None = None,
    hw=TPU_V5E,
    vmem_frac: float = VMEM_FRAC,
    level: str = "full",
) -> ContractReport:
    """Check ``cfg`` against an M x N x K GEMM.

    ``level="fast"`` runs the O(1) arithmetic checks (structure, VMEM,
    closed-form existence) -- what the tuner applies per candidate.
    ``level="full"`` additionally replays the schedule permutation over
    the whole grid and applies every kernel index map to it.  The grid
    is the ceil-divided one: the kernel's last block may overhang M, N
    or K (``padded_shape`` is the extent the blocks cover).
    """
    rep = ContractReport(
        subject=f"gemm {m}x{n}x{k} {cfg.schedule} "
                f"bm={cfg.bm} bn={cfg.bn} bk={cfg.bk}")
    if level not in ("fast", "full"):
        raise ValueError(f"unknown level {level!r}")
    if min(m, n, k) < 1:
        rep.add("bad-config", f"non-positive GEMM shape {(m, n, k)}")
        return rep
    if cfg.schedule == "xla":
        rep.stats.update(grid=None, vmem_bytes=0, note="library baseline")
        return rep  # no Pallas kernel: nothing to prove
    if min(cfg.bm, cfg.bn, cfg.bk) < 1:
        rep.add("bad-config", f"non-positive blocks "
                              f"{(cfg.bm, cfg.bn, cfg.bk)}")
        return rep
    if cfg.schedule not in SCHEDULES:
        rep.add("unknown-schedule",
                f"schedule {cfg.schedule!r} not in {sorted(SCHEDULES)}")
        return rep

    mt, nt, kt = -(-m // cfg.bm), -(-n // cfg.bn), -(-k // cfg.bk)
    ep = None if (epilogue is None or epilogue.is_noop) else epilogue
    need = gemm_vmem_bytes(cfg, dtype_bytes, ep, k)
    budget = int(hw.vmem_per_chip * vmem_frac)
    rep.stats.update(
        grid=(mt, nt, kt), tiles=mt * nt,
        padded_shape=(mt * cfg.bm, nt * cfg.bn, kt * cfg.bk),
        vmem_bytes=need, vmem_budget=budget,
        epilogue=ep.tag() if ep else "none",
        # (8, 128) is the f32 native tile; misalignment is legal (Pallas
        # masks) but wasteful, so it is surfaced as a stat, not a veto
        tile_aligned=(cfg.bm % 8 == 0 and cfg.bn % 128 == 0
                      and cfg.bk % 128 == 0),
    )
    if need > budget:
        rep.add("vmem-budget",
                f"working set {need / 1e6:.1f} MB exceeds "
                f"{budget / 1e6:.1f} MB "
                f"({vmem_frac:.0%} of {hw.vmem_per_chip / 1e6:.0f} MB "
                f"VMEM): blocks bm={cfg.bm} bn={cfg.bn} bk={cfg.bk}"
                + (f" + epilogue {ep.tag()}" if ep else ""))
    if not cfg.use_prefetch and not _closed_form_ok(cfg.schedule, mt, nt):
        rep.add("no-closed-form",
                f"use_prefetch=False needs a closed-form decode; "
                f"{cfg.schedule!r} has none on a {mt}x{nt} grid "
                f"(morton/hilbert need a square power-of-two grid)")
    if level == "fast" or rep.violations:
        return rep

    # ---- full level: replay the permutation + every index map ---------
    from .schedule import verify_order

    order = grid_schedule(cfg.schedule, mt, nt, **cfg.schedule_kwargs())
    sub = verify_order(order, mt, nt, subject=rep.subject)
    rep.violations.extend(sub.violations)
    rep.stats["order_verified"] = sub.ok
    # index maps (repro.kernels.sfc_matmul): a_map(t, kk) -> (i, kk),
    # b_map -> (kk, j), o_map/residual_map -> (i, j), bias_map -> (0, j).
    # With the permutation proven a bijection onto [0,mt) x [0,nt) and
    # kk ranging over [0, kt) by grid construction, every read is in
    # bounds and each output tile is written by exactly one t (the
    # accumulator flushes once, at kk == kt-1).
    rep.stats["index_maps"] = {
        "a": "(i, kk)", "b": "(kk, j)", "o": "(i, j)", "bias": "(0, j)"}
    if not cfg.use_prefetch and sub.ok:
        if mt * nt <= _MAX_DECODE_TILES:
            from repro.kernels.sfc_matmul import decode_step

            arr = np.asarray(order)
            for t in range(mt * nt):
                i, j = decode_step(t, cfg.schedule, mt, nt)
                if (int(i), int(j)) != (int(arr[t, 0]), int(arr[t, 1])):
                    rep.add("decode-mismatch",
                            f"closed-form decode_step({t}) = "
                            f"({int(i)}, {int(j)}) but the schedule "
                            f"table says {tuple(int(x) for x in arr[t])}")
                    break
            rep.stats["decode_verified"] = not rep.violations
        else:
            rep.stats["decode_verified"] = "skipped (grid > " \
                f"{_MAX_DECODE_TILES} tiles)"
    return rep


def _attn_vmem_bytes(n_heads: int, n_kv_heads: int, d_head: int,
                     page_size: int, dtype_bytes: int) -> int:
    """Working set of one ``paged_attention`` grid step: the q block
    (1, h, d) + one K and one V page block (page, hkv, d) + the output
    block, plus the f32 online-softmax scratch (m, l: (hkv, g) each;
    acc: (hkv, g, d))."""
    g = n_heads // max(n_kv_heads, 1)
    io = (2 * n_heads * d_head
          + 2 * page_size * n_kv_heads * d_head) * dtype_bytes
    scratch = (2 * n_kv_heads * g + n_kv_heads * g * d_head) * 4
    return io + scratch


def check_attn_contract(
    spec,
    *,
    block_table=None,
    num_pages: int | None = None,
    lengths=None,
    dtype_bytes: int = 4,
    hw=TPU_V5E,
    vmem_frac: float = VMEM_FRAC,
) -> ContractReport:
    """Check a decode-attention problem (duck-typed
    :class:`~repro.tune.autotune.DecodeAttnSpec`: ``slots``,
    ``cache_len``, ``n_heads``, ``n_kv_heads``, ``d_head``, ``attn``).

    Static config checks always run (GQA divisibility, VMEM working set
    of the paged kernel).  When ``block_table`` (slots x width, logical
    page ids, -1 = unmapped) and ``num_pages`` are given, the block
    -table contract of DESIGN.md §10 is proven too:

    * every entry lies in ``[-1, num_pages)`` (``page-oob``);
    * no slot maps the same page twice (``page-alias``: two logical
      positions would write the same physical rows);
    * for every live slot (``lengths[s] > 0``) the page holding the
      *write target* -- position ``lengths[s] - 1`` -- is mapped: an
      unmapped entry gathers from the reserved zero row, and the zero
      row must never be a write target (``zero-row-write``).
    """
    attn = spec.attn
    rep = ContractReport(
        subject=f"attn slots={spec.slots} cache_len={spec.cache_len} "
                f"{attn.tag()}")
    if spec.slots < 1 or spec.cache_len < 1:
        rep.add("bad-config",
                f"non-positive slots/cache_len "
                f"{(spec.slots, spec.cache_len)}")
        return rep
    if spec.n_kv_heads < 1 or spec.n_heads % spec.n_kv_heads != 0:
        rep.add("gqa-divisibility",
                f"n_heads={spec.n_heads} not a multiple of "
                f"n_kv_heads={spec.n_kv_heads}")
        return rep
    if attn.kind != "paged":
        rep.stats["note"] = "contiguous layout: no block-table contract"
        return rep

    ps = attn.page_size
    need = _attn_vmem_bytes(spec.n_heads, spec.n_kv_heads, spec.d_head,
                            ps, dtype_bytes)
    budget = int(hw.vmem_per_chip * vmem_frac)
    rep.stats.update(page_size=ps, vmem_bytes=need, vmem_budget=budget)
    if need > budget:
        rep.add("vmem-budget",
                f"paged-attention working set {need / 1e6:.1f} MB "
                f"exceeds {budget / 1e6:.1f} MB (page_size={ps}, "
                f"heads={spec.n_heads}/{spec.n_kv_heads}, "
                f"d_head={spec.d_head})")
    if block_table is None:
        return rep
    if num_pages is None:
        raise ValueError("block_table checks need num_pages")

    bt = np.asarray(block_table)
    rep.stats.update(num_pages=int(num_pages),
                     table_shape=tuple(bt.shape),
                     mapped=int((bt >= 0).sum()))
    bad = np.argwhere((bt < -1) | (bt >= num_pages))
    for s, p in bad[:8]:
        rep.add("page-oob",
                f"slot {int(s)} entry {int(p)} maps page "
                f"{int(bt[s, p])} outside [0, {num_pages})")
    for s in range(bt.shape[0]):
        row = bt[s][bt[s] >= 0]
        if len(row) != len(set(row.tolist())):
            vals, counts = np.unique(row, return_counts=True)
            dup = int(vals[counts > 1][0])
            rep.add("page-alias",
                    f"slot {s} maps page {dup} at more than one "
                    f"logical position (double-write within the slot)")
    if lengths is not None:
        for s, ln in enumerate(lengths):
            if ln <= 0:
                continue
            pg = (int(ln) - 1) // ps
            if pg >= bt.shape[1]:
                rep.add("table-extent",
                        f"slot {s} write target (pos {int(ln) - 1}) "
                        f"falls in page {pg} beyond the table width "
                        f"{bt.shape[1]}")
            elif bt[s, pg] < 0:
                rep.add("zero-row-write",
                        f"slot {s} write target (pos {int(ln) - 1}, "
                        f"page {pg}) is unmapped: the decode write "
                        f"would land in the reserved zero row")
    return rep
