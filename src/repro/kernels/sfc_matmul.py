"""Pallas TPU blocked matmul with space-filling-curve grid traversal.

The paper's technique lifted to the TPU memory hierarchy (DESIGN.md §2):
the *output-tile grid* is visited in row-major / Morton / Hilbert order.
Pallas skips a block's HBM->VMEM copy when the block index of a grid
step equals the previous step's, so traversal order controls HBM
traffic -- the TPU analogue of the paper's cache-hit effect.  With k the
innermost grid dim that happens only where K takes one step (kt = 1):
then consecutive output tiles in one row of tiles keep their A block,
and those in one column keep their B block.  With kt > 1 every step
changes the k index of both blocks and no copy is skipped, whatever the
order (:func:`grid_step_counts` counts both cases).  At 2,048 rows the
blocks :func:`sfc_blocks` derives take K whole wherever the working set
fits, and the Morton walk then skips a copy on some half of the steps
(PERF.md §5 gives the share by GEMM role).

Two index strategies, mirroring the paper's cost/locality trade-off:

* ``use_prefetch=True`` (the default everywhere in this stack) -- the
  whole schedule is precomputed host-side into an SMEM-prefetched
  ``(T, 2) int32`` table, amortising the index cost to zero (the
  "dedicated hardware support" the paper's future-work section asks for,
  realised as scalar prefetch).  This also lifts the power-of-two/square
  grid restriction of closed-form decodes.
* ``use_prefetch=False`` -- paper-faithful: the curve decode (Raman--Wise
  contraction / Hilbert bit scan) runs *inside* the ``index_map`` on
  every grid step, i.e. index computation is traded for locality exactly
  as in the paper (but per tile, not per element).

The kernel accumulates in an f32 VMEM scratch across the innermost k dim
and writes the output tile once on the last k step (where K takes one
step the product goes straight to the flush, with no scratch).  That
flush is also the **fused epilogue** (DESIGN.md §9): an optional bias
add, activation (``gelu``/``silu``/``relu``), and residual add are
applied to the f32 accumulator *before* the single cast-and-write, so a
full projection layer (dot + bias + act + residual + dtype cast) costs
exactly one HBM write of C and zero re-reads -- the post-matmul
elementwise passes that would otherwise each stream the whole output
array through HBM are gone.

Blocks need not divide the operands.  A last block that overhangs M or N
reads undefined rows or columns and Pallas drops the part of the output
block that lies outside C; a last block that overhangs K is zeroed past
K inside the kernel before its product, so the overhang adds nothing.
Each call raises the compiler's scoped VMEM limit to its double-buffered
working set (:func:`sfc_vmem_bytes`) where that exceeds the default.

Both kernels, the dense one and the grouped one, take a weight stacked
on a leading layer axis with a ``layer`` index: the index rides the scalar
prefetch and B's index map puts it first, so a layer scan's GEMM reads
its layer's blocks in place.  A slice ``w[i]`` handed to a Pallas call
is a buffer of its own, which XLA writes out on every iteration; the
stack is read as the scan holds it.  Per step the kernel moves the same
bytes and does the same FLOPs either way.

The grouped kernel (:func:`sfc_gmm_pallas`) runs a routed expert layer's
GEMM: rows sorted by expert, each expert's rows padded to whole tiles of
:data:`GROUP_ROWS`, each tile times its expert's weight.  The number of
live tiles is known only at run time, so the grid is sized for the
static bound and the scalar prefetch carries the walk, each tile's
expert and the live step count; the steps past the live ones compute
nothing and repeat the last live step's block indices, so they start no
copy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.curves import hilbert_decode, morton_decode
from repro.core.energy import TPU_V5E
from repro.core.schedule import grid_schedule, is_pow2, \
    schedule_extra_kwargs
from repro.kernels.ref import ACTIVATIONS, apply_activation

__all__ = ["sfc_matmul_pallas", "sfc_gmm_pallas", "decode_step", "sfc_blocks",
           "sfc_vmem_bytes", "grid_step_counts", "GROUP_ROWS"]

# the compiler's default scoped VMEM limit on a v5e; a kernel whose
# working set is larger sets ``vmem_limit_bytes`` itself
SCOPED_VMEM_BYTES = 16 * 2**20
# what Mosaic keeps in VMEM besides the blocks (internal scratch, the
# dot's staging), added to the working set when the limit is raised
VMEM_HEADROOM_BYTES = 4 * 2**20
# the block rule's cap on the double-buffered working set: a v5e core
# has 128 MiB of VMEM
BLOCK_VMEM_BUDGET = 48 * 2**20
# the block rule, from a sweep of blocks on a v5e at the qwen3-1.7B and
# GLM-4-9B GEMMs (PERF.md §6): rows of at most 512, so that 2,048 rows
# leave the curve 4 rows of tiles; K whole up to 4,096, in equal blocks
# beyond; 1,024 or 2,048 columns, the fewer where a step's operands and
# output stream in within its compute time with a fifth to spare (the
# v5e's ridge: its peak FLOP/s over its HBM bandwidth)
BM_MAX, BK_MAX = 512, 4096
BN_STEPS = (1024, 2048)
STEP_INTENSITY = 1.2 * TPU_V5E.peak_flops / TPU_V5E.hbm_bw
LANES = 128
# the row tile of the grouped GEMM, to which each group is padded: one
# MXU pass high, so a group wastes at most 127 rows
GROUP_ROWS = 128


def decode_step(t, schedule: str, mt: int, nt: int):
    """Closed-form linear step -> (i, j) tile coordinates (traceable)."""
    if schedule == "rowmajor":
        return t // nt, t % nt
    if schedule == "colmajor":
        return t % mt, t // mt
    if schedule == "morton":
        assert mt == nt and is_pow2(mt), (
            "closed-form morton decode needs a square power-of-two grid; "
            "use use_prefetch=True otherwise")
        y, x = morton_decode(t)
        return y.astype(jnp.int32), x.astype(jnp.int32)
    if schedule == "hilbert":
        assert mt == nt and is_pow2(mt), (
            "closed-form hilbert decode needs a square power-of-two grid; "
            "use use_prefetch=True otherwise")
        order = int(np.log2(mt))
        y, x = hilbert_decode(t, order)
        return y.astype(jnp.int32), x.astype(jnp.int32)
    raise ValueError(f"no closed-form decode for schedule {schedule!r}")


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def sfc_vmem_bytes(bm: int, bn: int, bk: int, in_bytes: int,
                   out_bytes: int, *, bias: bool = False,
                   residual: bool = False, kt: int = 1,
                   k_tail: int = 0) -> int:
    """VMEM one grid step of the kernel holds: two buffers of each
    pipelined block -- A (bm, bk), B (bk, bn), the output (bm, bn) and,
    where the epilogue has them, the residual (bm, bn, in the output's
    dtype) and the bias row (counted at a full 8 x 128 f32 tile per 128
    columns) -- plus the dot's (bm, bn) f32 product, the f32 accumulator
    where K takes more than one step (``kt``), and the zeroed copies of
    A's and B's blocks where the last one overhangs K (``k_tail``)."""
    block = (bm * bk + bk * bn) * in_bytes + bm * bn * out_bytes
    if residual:
        block += bm * bn * out_bytes
    if bias:
        block += 8 * bn * 4
    need = 2 * block + bm * bn * 4 * (2 if kt > 1 else 1)
    if k_tail:
        need += (bm * bk + bk * bn) * in_bytes
    return need


def _vmem_limit(need: int):
    """``vmem_limit_bytes`` for a kernel of working set ``need``: None
    (the compiler's default) where the default holds it."""
    need += VMEM_HEADROOM_BYTES
    return None if need <= SCOPED_VMEM_BYTES else need


def sfc_blocks(m: int, n: int, k: int, in_bytes: int, out_bytes: int, *,
               bias: bool = False, residual: bool = False
               ) -> tuple[int, int, int]:
    """The (bm, bn, bk) of an M x N x K GEMM when the caller names none.

    * bm: at most ``BM_MAX`` rows, the M blocks balanced so that the last
      one wastes fewest rows, rounded up to the operand's sublane tile
      (16 rows in bf16): a decode step of a few slots gets one small
      block rather than 128 padded rows.
    * bk: K whole (one k step, so that the curve's consecutive tiles keep
      a resident block) up to ``BK_MAX``; beyond it K in equal blocks of
      a multiple of 128, the last one zeroed past K by the kernel.
    * bn: N whole, rounded up to 128 lanes, where that is narrower than
      the first of ``BN_STEPS`` whose step does ``STEP_INTENSITY`` FLOP
      per byte it moves (the larger, where none does); a ragged last
      block, never a padded weight.
    * k is split further while the double-buffered working set
      (:func:`sfc_vmem_bytes`) exceeds ``BLOCK_VMEM_BUDGET``.
    """
    sub = 8 * max(1, 4 // in_bytes)
    m_blocks = max(1, -(-m // BM_MAX))
    bm = _round_up(max(1, -(-m // m_blocks)), sub)
    kt = -(-k // BK_MAX)
    while True:
        bk = _round_up(-(-k // kt), LANES)
        for bn in BN_STEPS:
            bn = min(bn, _round_up(n, LANES))
            moved = (bm * bk + bk * bn) * in_bytes \
                + bm * bn * out_bytes * (2 if residual else 1) / kt
            if 2 * bm * bn * bk >= STEP_INTENSITY * moved:
                break
        need = sfc_vmem_bytes(bm, bn, bk, in_bytes, out_bytes, bias=bias,
                              residual=residual, kt=kt, k_tail=k % bk)
        if need <= BLOCK_VMEM_BUDGET or bk == LANES:
            return bm, bn, bk
        kt += 1


def grid_step_counts(schedule: str, mt: int, nt: int, kt: int, g: int = 0
                     ) -> tuple[int, int]:
    """(grid steps, steps that skip a copy) of one kernel call.

    A step skips a copy where its A or its B block index equals the
    previous step's (Pallas then keeps the resident block).  With k
    innermost that needs kt = 1."""
    steps = mt * nt * kt
    if kt > 1:
        return steps, 0
    order = grid_schedule(schedule, mt, nt,
                          **schedule_extra_kwargs(schedule, g))
    same = (order[1:] == order[:-1]).any(axis=1)
    return steps, int(same.sum())


def _fused_flush(acc, bias_ref, res_ref, activation: str, out_dtype):
    """The epilogue applied to the f32 accumulator at the last k step:
    out = act(acc + bias) + residual, then a single cast.  Bias blocks
    are (1, bn) VMEM tiles broadcast over the (bm, bn) accumulator."""
    if bias_ref is not None:
        acc = acc + bias_ref[...].astype(jnp.float32)
    acc = apply_activation(acc, activation)
    if res_ref is not None:
        acc = acc + res_ref[...].astype(jnp.float32)
    return acc.astype(out_dtype)


def _mm_kernel(a_ref, b_ref, *rest, kt: int, k_tail: int, out_dtype,
               activation: str = "none", has_bias: bool = False,
               has_residual: bool = False, k=None):
    """One grid step.  The k index is grid dim 1 (or ``k``, where the
    caller read it); ``k_tail`` is the width of the last k block that
    lies inside K (0: it lies inside whole)."""
    # rest: [bias_ref], [residual_ref], o_ref, [acc_ref] (inputs before
    # outputs before scratch -- pallas_call calling convention)
    rest = list(rest)
    acc_ref = rest.pop() if kt > 1 else None
    o_ref = rest.pop()
    bias_ref = rest[0] if has_bias else None
    res_ref = rest[-1] if has_residual else None

    def flush(acc):
        o_ref[...] = _fused_flush(acc, bias_ref, res_ref, activation,
                                  out_dtype)

    def product(masked: bool):
        a, b = a_ref[...], b_ref[...]
        if masked:
            # the block overhangs K: what lies past it is undefined
            a = jnp.where(lax.broadcasted_iota(jnp.int32, a.shape, 1)
                          < k_tail, a, 0)
            b = jnp.where(lax.broadcasted_iota(jnp.int32, b.shape, 0)
                          < k_tail, b, 0)
        return jnp.dot(a, b, preferred_element_type=jnp.float32)

    if kt == 1:
        flush(product(bool(k_tail)))
        return
    if k is None:
        k = pl.program_id(1)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if k_tail:
        @pl.when(k < kt - 1)
        def _body():
            acc_ref[...] += product(False)

        @pl.when(k == kt - 1)
        def _last():
            acc_ref[...] += product(True)
    else:
        acc_ref[...] += product(False)

    @pl.when(k == kt - 1)
    def _flush():
        flush(acc_ref[...])


def _mm_kernel_prefetch(*refs, n_prefetch: int, **kwargs):
    # identical body; the scalar-prefetch refs (the walk, the layer
    # index) are consumed by the index_maps only
    _mm_kernel(*refs[n_prefetch:], **kwargs)


def _flat_schedule(schedule: str, mt: int, nt: int, g: int):
    """The scalar-prefetch table, flat: tile step t visits
    (table[2t], table[2t+1]).  SMEM pads the minor dim of a 2-D table to
    128 words, so a (steps, 2) table takes 64x its size there and
    overflows SMEM's 1 MiB at the vocab head of a 2048-row prefill."""
    return jnp.asarray(
        grid_schedule(schedule, mt, nt, **schedule_extra_kwargs(schedule, g)),
        dtype=jnp.int32).reshape(-1)


def _check_epilogue(bias, residual, activation, n, out_shape):
    if activation not in ACTIVATIONS:
        raise ValueError(
            f"unknown activation {activation!r}; choose from {ACTIVATIONS}")
    if bias is not None:
        assert bias.shape == (n,), (bias.shape, n)
    if residual is not None:
        assert residual.shape == out_shape, (residual.shape, out_shape)


def _layer_operand(layer):
    """A layer index as the (1,) int32 scalar-prefetch operand."""
    return jnp.reshape(layer, (1,)).astype(jnp.int32)


_STATIC = ("schedule", "bm", "bn", "bk", "out_dtype", "use_prefetch",
           "interpret", "g", "activation")


@functools.partial(jax.jit, static_argnames=_STATIC)
def sfc_matmul_pallas(
    a,
    b,
    *,
    schedule: str = "morton",
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    use_prefetch: bool = True,
    interpret: bool = False,
    g: int = 0,
    bias=None,
    activation: str = "none",
    residual=None,
    layer=None,
):
    """C = act(A @ B + bias) + residual with SFC-ordered tile traversal.

    Grid (T, kt): the curve's T output tiles, K innermost.  Blocks need
    not divide the shapes (see the module docstring; the wrapper
    :func:`repro.kernels.ops.sfc_matmul` derives a block when none is
    named).  ``g`` is the supertile factor (``schedule="supertile"``
    only; 0 means the schedule's default).  ``bias`` is (N,),
    ``residual`` is (M, N); both optional -- the epilogue runs on the
    f32 accumulator inside the last-k flush, costing zero extra HBM
    output traffic (DESIGN.md §9).  ``layer`` (an int32 scalar, traced
    or not): ``b`` is then a stack (L, K, N) of which layer ``layer`` is
    the operand, read in place -- the index rides the scalar prefetch
    and B's index map puts it first, so a layer scan hands the kernel
    its stacked weight, not a copy of the slice.  The index must lie in
    [0, L): nothing checks it.
    """
    stacked = layer is not None
    assert a.ndim == 2 and b.ndim == 2 + stacked, (a.shape, b.shape)
    (m, k), n = a.shape, b.shape[-1]
    assert b.shape[-2] == k, (a.shape, b.shape)
    _check_epilogue(bias, residual, activation, n, (m, n))
    mt, nt, kt = -(-m // bm), -(-n // bn), -(-k // bk)
    out_dtype = out_dtype or a.dtype
    kern_kw = dict(kt=kt, k_tail=k % bk, out_dtype=out_dtype,
                   activation=activation, has_bias=bias is not None,
                   has_residual=residual is not None)
    out_shape = jax.ShapeDtypeStruct((m, n), out_dtype)
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)] if kt > 1 else []
    need = sfc_vmem_bytes(bm, bn, bk, a.dtype.itemsize,
                          jnp.dtype(out_dtype).itemsize,
                          bias=bias is not None,
                          residual=residual is not None, kt=kt,
                          k_tail=k % bk)
    grid = (mt * nt, kt)
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_vmem_limit(need))

    # the scalar-prefetch operands: the walk, then the layer index
    prefetch = []
    if use_prefetch:
        prefetch.append(_flat_schedule(schedule, mt, nt, g))

        def tile(t, sched_ref, *_):
            return sched_ref[2 * t], sched_ref[2 * t + 1]
    else:
        def tile(t, *_):
            return decode_step(t, schedule, mt, nt)
    if stacked:
        prefetch.append(_layer_operand(layer))

    def a_map(t, kk, *s):
        return tile(t, *s)[0], kk

    def b_map(t, kk, *s):
        return kk, tile(t, *s)[1]

    def o_map(t, kk, *s):
        return tile(t, *s)

    def bias_map(t, kk, *s):
        return 0, tile(t, *s)[1]

    b_spec = pl.BlockSpec(
        (None, bk, bn), lambda t, kk, *s: (s[-1][0], *b_map(t, kk, *s))) \
        if stacked else pl.BlockSpec((bk, bn), b_map)
    # the epilogue operands, in the order the kernel's ``rest`` parses
    in_specs, operands = [pl.BlockSpec((bm, bk), a_map), b_spec], [a, b]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bn), bias_map))
        operands.append(bias.reshape((1, n)))
    if residual is not None:
        in_specs.append(pl.BlockSpec((bm, bn), o_map))
        operands.append(residual)
    out_specs = pl.BlockSpec((bm, bn), o_map)
    if not prefetch:
        return pl.pallas_call(
            functools.partial(_mm_kernel, **kern_kw),
            grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch,
            compiler_params=params, interpret=interpret,
        )(*operands)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch)
    return pl.pallas_call(
        functools.partial(_mm_kernel_prefetch, n_prefetch=len(prefetch),
                          **kern_kw),
        grid_spec=grid_spec, out_shape=out_shape,
        compiler_params=params, interpret=interpret,
    )(*prefetch, *operands)


def _gmm_kernel(steps_ref, groups_ref, live_ref, *args, stacked: bool,
                **kwargs):
    """One grid step of the grouped GEMM: the dense kernel's body on the
    step's blocks, or nothing past the live steps."""
    k = pl.program_id(1)
    if stacked:  # the layer index, read by B's index map only
        args = args[1:]

    @pl.when(pl.program_id(0) < live_ref[0])
    def _live():
        _mm_kernel(*args, k=k, **kwargs)


def gmm_steps(schedule: str, mt: int, nt: int, live_tiles):
    """The grouped GEMM's walk of its (mt, nt) tile grid, flat as the
    scalar-prefetch table wants it, and its number of live steps: the
    curve's steps over the first ``live_tiles`` row tiles in the
    curve's order, then every other step set to the last live one, so
    that those steps keep the blocks they find resident."""
    base = jnp.asarray(
        grid_schedule(schedule, mt, nt, **schedule_extra_kwargs(schedule, 0)),
        dtype=jnp.int32)
    order = jnp.argsort(base[:, 0] >= live_tiles, stable=True)
    steps = base[order]
    live = live_tiles * nt
    last = steps[jnp.maximum(live - 1, 0)]
    steps = jnp.where(jnp.arange(mt * nt)[:, None] < live, steps, last)
    return steps.reshape(-1), live


@functools.partial(jax.jit, static_argnames=(
    "schedule", "bn", "bk", "out_dtype", "interpret", "activation"))
def sfc_gmm_pallas(a, b, group_tiles, *, schedule: str = "morton",
                   bn: int = 128, bk: int = 128, out_dtype=None,
                   interpret: bool = False, activation: str = "none",
                   layer=None):
    """Grouped GEMM: rows of ``a`` (R, K) sorted by group, group ``e``
    taking ``group_tiles[e]`` whole tiles of ``GROUP_ROWS`` rows, times
    its own ``b[e]`` (K, N) -> (R, N), with ``activation`` on the f32
    accumulator in the flush.  With ``layer`` (an int32 scalar in [0,
    L)), ``b`` is a stack (L, G, K, N) whose layer ``layer`` is read in
    place, the index one more scalar-prefetch operand.

    The grid is sized for R, the static bound; each row tile's group
    comes in the scalar prefetch, and the (row tile, column tile) grid
    is walked in ``schedule`` order over the live tiles.  Steps past
    them do no compute, and their index maps repeat the last live
    step's blocks, so they start no copy.  Rows past the live tiles are
    left as the output buffer had them."""
    r, k = a.shape
    ng, _, n = b.shape[-3:]
    bm = GROUP_ROWS
    stacked = layer is not None
    assert r % bm == 0 and b.shape[-2] == k and b.ndim == 3 + stacked, (
        a.shape, b.shape)
    _check_epilogue(None, None, activation, n, (r, n))
    mt, nt, kt = r // bm, -(-n // bn), -(-k // bk)
    out_dtype = out_dtype or a.dtype
    ends = jnp.cumsum(group_tiles)
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(mt), side="right"),
        ng - 1).astype(jnp.int32)
    steps, live = gmm_steps(schedule, mt, nt, ends[-1])

    prefetch = [steps, tile_group, live.reshape(1).astype(jnp.int32)]
    if stacked:
        prefetch.append(_layer_operand(layer))

    def kk_of(t, kk, live_ref):
        return jnp.where(t < live_ref[0], kk, kt - 1)

    def a_map(t, kk, steps_ref, groups_ref, live_ref, *_):
        return steps_ref[2 * t], kk_of(t, kk, live_ref)

    def b_map(t, kk, steps_ref, groups_ref, live_ref, *layer_ref):
        return (*(r[0] for r in layer_ref), groups_ref[steps_ref[2 * t]],
                kk_of(t, kk, live_ref), steps_ref[2 * t + 1])

    def o_map(t, kk, steps_ref, groups_ref, live_ref, *_):
        return steps_ref[2 * t], steps_ref[2 * t + 1]

    need = sfc_vmem_bytes(bm, bn, bk, a.dtype.itemsize,
                          jnp.dtype(out_dtype).itemsize, kt=kt,
                          k_tail=k % bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=(mt * nt, kt),
        in_specs=[pl.BlockSpec((bm, bk), a_map),
                  pl.BlockSpec((None,) * (1 + stacked) + (bk, bn), b_map)],
        out_specs=pl.BlockSpec((bm, bn), o_map),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)] if kt > 1
        else [])
    return pl.pallas_call(
        functools.partial(_gmm_kernel, stacked=stacked, kt=kt,
                          k_tail=k % bk, out_dtype=out_dtype,
                          activation=activation),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(need)),
        interpret=interpret,
    )(*prefetch, a, b)
