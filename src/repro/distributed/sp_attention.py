"""Sequence-parallel (SP) decode attention: KV caches sharded along the
*sequence* axis, combined with a distributed online softmax.

Why: at decode, KV caches dominate memory (llava decode_32k: ~1 TB global)
and batch-sharding alone leaves 64 GB/chip.  Sharding the cache sequence
over the "model" axis is universal (every cache length here is a multiple
of 16) and head-count agnostic -- unlike KV-head sharding, which fails for
kv=2/8 archs on a 16-way axis.  GSPMD cannot synthesize the nonlinear
softmax combine across shards, so this is a manual shard_map:

    m*  = pmax(m_loc)            (running max)
    l*  = psum(l_loc * e^(m_loc - m*))
    o*  = psum(o_loc * e^(m_loc - m*)) / l*

Each shard owns cache slots [i*S_loc, (i+1)*S_loc); the new token's KV is
written by its owning shard only.  Works for dense and SWA-ring caches.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["sp_decode_attention"]


def _axis_index(axes):
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _axis_size(axes):
    n = 1
    for a in axes:
        n *= jax.lax.axis_size(a)
    return n


def sp_decode_attention(q, k_cache, v_cache, kv_pos, k_new, v_new,
                        slot, pos, *, mesh, window=None,
                        seq_axes=("model",), dp_axes=(), row_mask=None):
    """One-token attention against a sequence-sharded KV cache.

    q: (B, 1, H, dh); k_cache/v_cache: (B, S, Hkv, dh) sharded on S over
    ``seq_axes`` (and on B over ``dp_axes``); kv_pos: (S,) likewise;
    k_new/v_new: (B, 1, Hkv, dh); slot/pos: scalars.
    Returns (out (B,1,H,dh), k', v', kv_pos').

    **Batch-sharding contract**: ``dp_axes`` is honoured only when the
    dp axis product divides B; otherwise the shard_map runs with batch
    replicated -- every device computes the full batch and the caller's
    batch sharding constraint (not this function) decides the final
    layout.  The drop is not silent: it increments the
    ``distributed.dp_dropped`` counter, because a production mesh whose
    batch stopped dividing (e.g. a degraded spec with a ragged batch)
    quietly loses its data-parallel speedup here and that must show up
    in a metrics snapshot, not in a profiler three layers down.

    The shard_map is FULLY manual over dp+seq axes (partial-manual with
    auto batch axes trips an XLA SPMD partitioner CHECK at 16-way meshes).
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    b = q.shape[0]
    dsz = 1
    for a in dp_axes:
        dsz *= mesh.shape[a]
    if dp_axes and dsz > 1 and b % dsz != 0:
        from repro.obs.metrics import default_registry
        default_registry().counter("distributed.dp_dropped").inc()
    dp_axes = tuple(dp_axes) if (dsz and b % max(dsz, 1) == 0 and dsz > 1) \
        else ()

    def local(q, kc, vc, kp, kn, vn):
        s_loc = kc.shape[1]
        start = _axis_index(seq_axes) * s_loc
        lslot = slot - start
        sel_slot = jnp.arange(s_loc) == lslot
        sel = sel_slot[None, :, None, None]
        if row_mask is not None:
            sel = sel & row_mask[:, None, None, None]
        kc = jnp.where(sel, kn, kc)
        vc = jnp.where(sel, vn, vc)
        kp = jnp.where(sel_slot, pos, kp)
        valid = (kp >= 0) & (kp <= pos)
        if window is not None:
            valid &= kp > pos - window
        b, _, h, dh = q.shape
        hkv = kc.shape[2]
        g = h // hkv
        qg = q.reshape(b, 1, hkv, g, dh)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kc).astype(jnp.float32)
        s = s * scale
        s = jnp.where(valid[None, None, None, None, :], s, -jnp.inf)
        m_loc = s.max(axis=-1)                       # (b,hkv,g,1)
        # guard fully-masked shards: exp(-inf - -inf) -> use safe max
        m_safe = jnp.where(jnp.isfinite(m_loc), m_loc, -1e30)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(valid[None, None, None, None, :], p, 0.0)
        l_loc = p.sum(axis=-1)                       # (b,hkv,g,1)
        o_loc = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc)
        m_g = jax.lax.pmax(m_safe, seq_axes)
        alpha = jnp.exp(m_safe - m_g)
        l_g = jax.lax.psum(l_loc * alpha, seq_axes)
        o_g = jax.lax.psum(o_loc * alpha[..., None].astype(o_loc.dtype),
                           seq_axes)
        out = o_g / jnp.maximum(l_g[..., None], 1e-30).astype(o_g.dtype)
        out = jnp.moveaxis(out, 3, 1).reshape(b, 1, h, dh)
        return out.astype(q.dtype), kc, vc, kp

    sq = tuple(seq_axes) if len(seq_axes) > 1 else seq_axes[0]
    dpn = (tuple(dp_axes) if len(dp_axes) > 1 else dp_axes[0]) \
        if dp_axes else None
    cspec = P(dpn, sq, None, None)
    rep = P(dpn, None, None, None)
    out, kc, vc, kp = jax.shard_map(
        local, mesh=mesh,
        in_specs=(rep, cspec, cspec, P(sq), rep, rep),
        out_specs=(rep, cspec, cspec, P(sq)),
        axis_names=set(seq_axes) | set(dp_axes),
        check_vma=False,
    )(q, k_cache, v_cache, kv_pos, k_new, v_new)
    return out, kc, vc, kp
