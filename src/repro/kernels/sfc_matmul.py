"""Pallas TPU blocked matmul with space-filling-curve grid traversal.

The paper's technique lifted to the TPU memory hierarchy (DESIGN.md §2):
the *output-tile grid* is visited in row-major / Morton / Hilbert order.
Consecutive grid steps that map to the same A- or B-block elide the
HBM->VMEM DMA (Pallas pipeline revisiting), so traversal order directly
controls HBM traffic -- the TPU analogue of the paper's cache-hit effect.

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
and writes the output tile once on the last k step.  That flush is also
the **fused epilogue** (DESIGN.md §9): an optional bias add, activation
(``gelu``/``silu``/``relu``), and residual add are applied to the f32
accumulator *before* the single cast-and-write, so a full projection
layer (dot + bias + act + residual + dtype cast) costs exactly one HBM
write of C and zero re-reads -- the post-matmul elementwise passes that
would otherwise each stream the whole output array through HBM are gone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.curves import hilbert_decode, morton_decode
from repro.core.schedule import grid_schedule, is_pow2, \
    schedule_extra_kwargs
from repro.kernels.ref import ACTIVATIONS, apply_activation

__all__ = ["sfc_matmul_pallas", "sfc_matmul_batched_pallas", "decode_step"]


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


def _fused_flush(acc, bias_ref, res_ref, activation: str, out_dtype,
                 batched: bool):
    """The epilogue applied to the f32 accumulator at the last k step:
    out = act(acc + bias) + residual, then a single cast.  Bias blocks
    are (1, bn) VMEM tiles broadcast over the (bm, bn) accumulator."""
    if bias_ref is not None:
        b = bias_ref[0] if batched else bias_ref[...]
        acc = acc + b.astype(jnp.float32)
    acc = apply_activation(acc, activation)
    if res_ref is not None:
        r = res_ref[0] if batched else res_ref[...]
        acc = acc + r.astype(jnp.float32)
    return acc.astype(out_dtype)


def _mm_kernel(a_ref, b_ref, *rest, kt: int, out_dtype,
               activation: str = "none", has_bias: bool = False,
               has_residual: bool = False):
    # rest: [bias_ref], [residual_ref], o_ref, acc_ref (inputs before
    # outputs before scratch -- pallas_call calling convention)
    rest = list(rest)
    acc_ref = rest.pop()
    o_ref = rest.pop()
    bias_ref = rest[0] if has_bias else None
    res_ref = rest[-1] if has_residual else None
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == kt - 1)
    def _flush():
        o_ref[...] = _fused_flush(acc_ref[...], bias_ref, res_ref,
                                  activation, out_dtype, batched=False)


def _flat_schedule(schedule: str, mt: int, nt: int, g: int):
    """The scalar-prefetch table, flat: tile step t visits
    (table[2t], table[2t+1]).  SMEM pads the minor dim of a 2-D table to
    128 words, so a (steps, 2) table takes 64x its size there and
    overflows SMEM's 1 MiB at the vocab head of a 2048-row prefill."""
    return jnp.asarray(
        grid_schedule(schedule, mt, nt, **schedule_extra_kwargs(schedule, g)),
        dtype=jnp.int32).reshape(-1)


def _mm_kernel_prefetch(sched_ref, *args, **kwargs):
    # identical body; the schedule ref is consumed by the index_maps only
    _mm_kernel(*args, **kwargs)


def _check_epilogue(bias, residual, activation, n, out_shape):
    if activation not in ACTIVATIONS:
        raise ValueError(
            f"unknown activation {activation!r}; choose from {ACTIVATIONS}")
    if bias is not None:
        assert bias.shape == (n,), (bias.shape, n)
    if residual is not None:
        assert residual.shape == out_shape, (residual.shape, out_shape)


def _epilogue_operands(bias, residual, bias_shape, bias_spec, res_spec):
    """The (in_specs, operands) tail for the optional epilogue inputs.

    Shared by all four kernel variants; the (bias, residual) order here
    must match the kernels' positional ``rest`` parsing."""
    specs, ops = [], []
    if bias is not None:
        specs.append(bias_spec)
        ops.append(bias.reshape(bias_shape))
    if residual is not None:
        specs.append(res_spec)
        ops.append(residual)
    return specs, ops


@functools.partial(
    jax.jit,
    static_argnames=("schedule", "bm", "bn", "bk", "out_dtype",
                     "use_prefetch", "interpret", "g", "activation"),
)
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
):
    """C = act(A @ B + bias) + residual with SFC-ordered tile traversal.

    Shapes must be multiples of the block sizes (use
    :func:`repro.kernels.ops.sfc_matmul` for the padding wrapper).
    ``g`` is the supertile factor (``schedule="supertile"`` only; 0 means
    the schedule's default).  ``bias`` is (N,), ``residual`` is (M, N);
    both optional -- the epilogue runs on the f32 accumulator inside the
    last-k flush, costing zero extra HBM output traffic (DESIGN.md §9).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        (m, n, k), (bm, bn, bk))
    _check_epilogue(bias, residual, activation, n, (m, n))
    mt, nt, kt = m // bm, n // bn, k // bk
    out_dtype = out_dtype or a.dtype
    grid = (mt * nt, kt)
    kern_kw = dict(kt=kt, out_dtype=out_dtype, activation=activation,
                   has_bias=bias is not None,
                   has_residual=residual is not None)
    out_shape = jax.ShapeDtypeStruct((m, n), out_dtype)
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    semantics = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
    )

    if not use_prefetch:
        def a_map(t, kk):
            i, _ = decode_step(t, schedule, mt, nt)
            return i, kk

        def b_map(t, kk):
            _, j = decode_step(t, schedule, mt, nt)
            return kk, j

        def o_map(t, kk):
            return decode_step(t, schedule, mt, nt)

        def bias_map(t, kk):
            _, j = decode_step(t, schedule, mt, nt)
            return 0, j

        ep_specs, ep_ops = _epilogue_operands(
            bias, residual, (1, n),
            pl.BlockSpec((1, bn), bias_map), pl.BlockSpec((bm, bn), o_map))
        return pl.pallas_call(
            functools.partial(_mm_kernel, **kern_kw),
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), a_map),
                pl.BlockSpec((bk, bn), b_map),
                *ep_specs,
            ],
            out_specs=pl.BlockSpec((bm, bn), o_map),
            out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=semantics,
            interpret=interpret,
        )(a, b, *ep_ops)

    # --- scalar-prefetch variant: host-precomputed schedule table ---------
    sched = _flat_schedule(schedule, mt, nt, g)

    def a_map(t, kk, sched_ref):
        return sched_ref[2 * t], kk

    def b_map(t, kk, sched_ref):
        return kk, sched_ref[2 * t + 1]

    def o_map(t, kk, sched_ref):
        return sched_ref[2 * t], sched_ref[2 * t + 1]

    def bias_map(t, kk, sched_ref):
        return 0, sched_ref[2 * t + 1]

    ep_specs, ep_ops = _epilogue_operands(
        bias, residual, (1, n),
        pl.BlockSpec((1, bn), bias_map), pl.BlockSpec((bm, bn), o_map))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), a_map),
            pl.BlockSpec((bk, bn), b_map),
            *ep_specs,
        ],
        out_specs=pl.BlockSpec((bm, bn), o_map),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(_mm_kernel_prefetch, **kern_kw),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=semantics,
        interpret=interpret,
    )(sched, a, b, *ep_ops)


# ---------------------------------------------------------------------------
# Batched variant: 3-D grid (batch, sfc tile step, k)
# ---------------------------------------------------------------------------

def _bmm_kernel(a_ref, b_ref, *rest, kt: int, out_dtype,
                activation: str = "none", has_bias: bool = False,
                has_residual: bool = False):
    rest = list(rest)
    acc_ref = rest.pop()
    o_ref = rest.pop()
    bias_ref = rest[0] if has_bias else None
    res_ref = rest[-1] if has_residual else None
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[0], b_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(k == kt - 1)
    def _flush():
        o_ref[0] = _fused_flush(acc_ref[...], bias_ref, res_ref,
                                activation, out_dtype, batched=True)


def _bmm_kernel_prefetch(sched_ref, *args, **kwargs):
    _bmm_kernel(*args, **kwargs)


@functools.partial(
    jax.jit,
    static_argnames=("schedule", "bm", "bn", "bk", "out_dtype",
                     "use_prefetch", "interpret", "g", "activation"),
)
def sfc_matmul_batched_pallas(
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
):
    """C[b] = act(A[b] @ B[b] + bias) + residual[b], SFC tile traversal.

    Grid is (batch, T, kt) with the curve applied to the (i, j) output
    tile plane -- the batch dim is outermost, so each batch element
    replays the full SFC sweep and inherits its locality (consecutive
    tile steps within one batch element elide A/B block DMAs exactly as
    in the 2-D kernel; the k-accumulator carries across the innermost
    dim only).  ``bias`` is (N,), shared across batch elements;
    ``residual`` matches the (batch, M, N) output.  Shapes must be
    multiples of the block sizes (see
    :func:`repro.kernels.ops.sfc_matmul_batched` for padding + batching
    of arbitrary leading dims).
    """
    bsz, m, k = a.shape
    bsz2, k2, n = b.shape
    assert bsz == bsz2 and k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        (m, n, k), (bm, bn, bk))
    _check_epilogue(bias, residual, activation, n, (bsz, m, n))
    mt, nt, kt = m // bm, n // bn, k // bk
    out_dtype = out_dtype or a.dtype
    grid = (bsz, mt * nt, kt)
    kern_kw = dict(kt=kt, out_dtype=out_dtype, activation=activation,
                   has_bias=bias is not None,
                   has_residual=residual is not None)
    out_shape = jax.ShapeDtypeStruct((bsz, m, n), out_dtype)
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    semantics = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
    )

    if not use_prefetch:
        def a_map(bb_, t, kk):
            i, _ = decode_step(t, schedule, mt, nt)
            return bb_, i, kk

        def b_map(bb_, t, kk):
            _, j = decode_step(t, schedule, mt, nt)
            return bb_, kk, j

        def o_map(bb_, t, kk):
            i, j = decode_step(t, schedule, mt, nt)
            return bb_, i, j

        def bias_map(bb_, t, kk):
            _, j = decode_step(t, schedule, mt, nt)
            return 0, 0, j

        ep_specs, ep_ops = _epilogue_operands(
            bias, residual, (1, 1, n),
            pl.BlockSpec((1, 1, bn), bias_map),
            pl.BlockSpec((1, bm, bn), o_map))
        return pl.pallas_call(
            functools.partial(_bmm_kernel, **kern_kw),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm, bk), a_map),
                pl.BlockSpec((1, bk, bn), b_map),
                *ep_specs,
            ],
            out_specs=pl.BlockSpec((1, bm, bn), o_map),
            out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=semantics,
            interpret=interpret,
        )(a, b, *ep_ops)

    sched = _flat_schedule(schedule, mt, nt, g)

    def a_map(bb_, t, kk, sched_ref):
        return bb_, sched_ref[2 * t], kk

    def b_map(bb_, t, kk, sched_ref):
        return bb_, kk, sched_ref[2 * t + 1]

    def o_map(bb_, t, kk, sched_ref):
        return bb_, sched_ref[2 * t], sched_ref[2 * t + 1]

    def bias_map(bb_, t, kk, sched_ref):
        return 0, 0, sched_ref[2 * t + 1]

    ep_specs, ep_ops = _epilogue_operands(
        bias, residual, (1, 1, n),
        pl.BlockSpec((1, 1, bn), bias_map), pl.BlockSpec((1, bm, bn), o_map))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), a_map),
            pl.BlockSpec((1, bk, bn), b_map),
            *ep_specs,
        ],
        out_specs=pl.BlockSpec((1, bm, bn), o_map),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(_bmm_kernel_prefetch, **kern_kw),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=semantics,
        interpret=interpret,
    )(sched, a, b, *ep_ops)
