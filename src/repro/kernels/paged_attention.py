"""Pallas TPU paged decode attention (DESIGN.md §10).

One query token per decode slot attends to that slot's KV pages, gathered
through its block table.  The block table rides the **same scalar-prefetch
discipline as** ``sfc_matmul``: it is prefetched into SMEM
(``PrefetchScalarGridSpec``) and consumed by the ``index_map`` functions,
so each grid step's (slot, page) pair resolves to a physical page row
*before* the pipeline needs the block -- the DMA for page ``p+1`` is in
flight while page ``p`` is in the MXU, exactly like the schedule table of
the SFC GEMM.  Pages are non-contiguous in HBM by construction (that is
the point of paging); the per-step block gather is the one-DMA-per-page
pattern of the classic TPU paged-attention kernel, driven here by
BlockSpec indexing rather than hand-rolled async copies.

Accumulation is the standard online softmax over page blocks, carried in
f32 VMEM scratch and flushed once at the last page -- the decode-attention
analogue of the SFC GEMM's last-k flush.

The kernel reads every shape it tiles by -- query heads, kv-heads, head
dim -- from its *local* operands, never from a model config, so a
kv-head-sharded pool (``repro.distributed.sharding
.paged_decode_state_specs``, DESIGN.md §15) needs no kernel changes:
under a mesh the model runs the kernel in a ``shard_map``
(``repro.models.attention.paged_decode_attention``; the SPMD
partitioner cannot split a Pallas call), each shard over its own
``n_kv_heads / model`` head slice with the full block table (replicated
control metadata), and the scalar-prefetch pipeline above runs per
shard exactly as it does on one chip.

``paged_decode_attention`` is the dispatching entry point: the Pallas
kernel on TPU (or under ``interpret=True``), otherwise the pure-XLA
gather fallback :func:`repro.kernels.ref.paged_decode_attention_ref`,
whose f32 math the kernel reproduces to ulp level (the reference computes
one direct softmax; the kernel's online rescaling is algebraically
identical and agrees bitwise on single-page spans).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import paged_decode_attention_ref
from repro.runtime.chaos import InjectedFault, fire as _chaos_fire

__all__ = ["paged_decode_attention", "paged_decode_attention_pallas",
           "FALLBACK_EVENTS", "fallback_key", "mark_fallback",
           "fallback_active", "reset_fallback"]


def _paged_attn_kernel(tab_ref, meta_ref, q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref, *, page_size: int,
                       n_kv_heads: int, max_pages: int, scale: float,
                       out_dtype):
    pg = pl.program_id(1)
    # per-slot newest position: one SMEM entry per grid row, so lockstep
    # (all equal) and continuous batching (per-slot vectors) share one
    # kernel (DESIGN.md §11)
    pos = meta_ref[pl.program_id(0)]

    @pl.when(pg == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(pg * page_size <= pos)
    def _block():
        q = q_ref[0].astype(jnp.float32)            # (H, dh)
        h, dh = q.shape
        g = h // n_kv_heads
        qg = q.reshape(n_kv_heads, g, dh)
        k = k_ref[0].astype(jnp.float32)            # (ps, hkv, dh)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.einsum("hgd,thd->hgt", qg, k,
                       preferred_element_type=jnp.float32) * scale
        t = pg * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        s = jnp.where(t <= pos, s, -1e30)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1)
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[..., None])
        m_ref[...] = m_next
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[..., None] + jnp.einsum(
            "hgt,thd->hgd", p, v, preferred_element_type=jnp.float32)

    @pl.when(pg == max_pages - 1)
    def _flush():
        h = q_ref.shape[1]
        dh = q_ref.shape[2]
        out = acc_ref[...] / l_ref[...][..., None]
        o_ref[0] = out.reshape(h, dh).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(q, k_pages, v_pages, phys_tables,
                                  cur_pos, *, interpret: bool = False):
    """q: (B, H, dh); k_pages/v_pages: (R, page_size, Hkv, dh) physical
    pool (last row reserved zero); phys_tables: (B, max_pages) physical
    row per logical page; cur_pos: newest position -- scalar (lockstep)
    or (B,) per-slot vector (continuous batching).

    Grid is (slot, page); the block table and ``cur_pos`` are the two
    scalar-prefetch operands, so the k/v index_maps read the *physical*
    row straight out of SMEM (zero gather address computation on the
    critical path -- the block-table analogue of the SFC schedule table).
    Returns (B, H, dh) in the cache dtype.
    """
    b, h, dh = q.shape
    _, page_size, hkv, dh2 = k_pages.shape
    assert dh == dh2, (q.shape, k_pages.shape)
    assert h % hkv == 0, (h, hkv)
    max_pages = phys_tables.shape[1]
    g = h // hkv
    scale = 1.0 / math.sqrt(dh)
    out_dtype = v_pages.dtype

    def q_map(bb, pg, tab_ref, meta_ref):
        return bb, 0, 0

    def kv_map(bb, pg, tab_ref, meta_ref):
        return tab_ref[bb, pg], 0, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_pages),
        in_specs=[
            pl.BlockSpec((1, h, dh), q_map),
            pl.BlockSpec((1, page_size, hkv, dh), kv_map),
            pl.BlockSpec((1, page_size, hkv, dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, h, dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((hkv, g), jnp.float32),        # running max
            pltpu.VMEM((hkv, g), jnp.float32),        # running denom
            pltpu.VMEM((hkv, g, dh), jnp.float32),    # unnormalised out
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _paged_attn_kernel, page_size=page_size, n_kv_heads=hkv,
            max_pages=max_pages, scale=scale, out_dtype=out_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dh), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(phys_tables.astype(jnp.int32),
      jnp.broadcast_to(
          jnp.reshape(cur_pos, (-1,)).astype(jnp.int32), (b,)),
      q, k_pages, v_pages)


# Graceful degradation under injected kernel faults (DESIGN.md §14):
# a degraded shape falls back to the XLA reference *stickily* -- every
# later trace of that shape dispatches straight to ref.  Metered: every
# engagement is recorded on FALLBACK_EVENTS so the serve loop
# (serve.degraded) and tests can see exactly what degraded and why.
# Keyed per shape: the (batch, heads, head-dim, page geometry) tuple.
_FALLBACK: set[tuple] = set()
FALLBACK_EVENTS: list[dict] = []


def fallback_key(b: int, h: int, dh: int, page_size: int,
                 max_pages: int) -> tuple:
    return (int(b), int(h), int(dh), int(page_size), int(max_pages))


def mark_fallback(key: tuple, reason: str = "launch-fault") -> None:
    if key not in _FALLBACK:
        _FALLBACK.add(key)
        FALLBACK_EVENTS.append({"key": key, "reason": reason})


def fallback_active(key: tuple) -> bool:
    return key in _FALLBACK


def reset_fallback() -> None:
    _FALLBACK.clear()
    FALLBACK_EVENTS.clear()


def paged_decode_attention(q, k_pages, v_pages, phys_tables, cur_pos, *,
                           interpret: bool | None = None,
                           force_pallas: bool = False):
    """Backend dispatch mirroring ``repro.kernels.ops``: Pallas on TPU
    (or ``interpret=True``), the XLA gather reference otherwise -- both
    produce the same f32 math, so callers never branch on backend.

    An injected ``kernel`` chaos event marks this shape's sticky
    fallback and degrades to the reference (ref computes identical
    math), so chaos runs exercise the degraded path (DESIGN.md §14).  A
    real Pallas build fault propagates: on the chip a silent switch to
    the reference would hide that the kernel never ran."""
    key = fallback_key(q.shape[0], q.shape[1], q.shape[2],
                       k_pages.shape[1], phys_tables.shape[1])
    want_pallas = bool(force_pallas or interpret
                       or jax.default_backend() == "tpu")
    if want_pallas and not fallback_active(key):
        try:
            _chaos_fire("kernel")
        except InjectedFault as e:
            mark_fallback(key, reason=repr(e))
        else:
            return paged_decode_attention_pallas(
                q, k_pages, v_pages, phys_tables, cur_pos,
                interpret=bool(interpret))
    return paged_decode_attention_ref(
        q, k_pages, v_pages, phys_tables, cur_pos)
