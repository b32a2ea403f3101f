"""GQA attention with q-chunked exact softmax (TPU/XLA friendly).

Prefill/train uses a *statically unrolled* q-chunk loop: chunk i attends
kv[: (i+1)*C] (or the SWA window slice), so causal attention does **zero
wasted FLOPs** (no masked-out full blocks, unlike naive chunked-flash) and
needs no online-softmax carry -- each q chunk takes an exact softmax over
its full key extent.  HLO size grows linearly in the chunk count (<= 32
chunks for the 32k shapes), which XLA handles comfortably.

Supports: GQA (kv head grouping), RoPE, qwen3-style per-head qk-norm,
sliding-window attention (SWA), encoder (bidirectional) mode, and a decode
step against a KV cache (the distributed sequence-parallel decode lives in
``repro.distributed.sp_attention``).

Latent attention (MLA, DeepSeek-V2/V3, :func:`mla_attention`) runs the
full-sequence forward only: q from its own projection; the keys and
values from a low-rank latent (``wkv_a``, RMS-normed, then ``wkv_b``),
each key's rotary part one vector shared by every head.  It has no
decode path yet: that needs a latent KV cache (ROADMAP Reach 5).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import DotEngine, apply_rope, init_linear, init_rms, rms_norm

__all__ = ["init_attention", "attention", "mla_attention",
           "decode_attention", "paged_decode_attention", "prefill_kv"]

# the RMSNorm epsilon of MLA's latent (DeepSeek-V3's kv_a_layernorm takes
# its norm's default; config.json does not give it)
KV_A_NORM_EPS = 1e-6


def init_attention(key, cfg, dtype=jnp.float32):
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    if cfg.mla:
        return _init_mla(key, cfg, dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_linear(ks[0], d, h * dh, dtype),
        "wk": init_linear(ks[1], d, hkv * dh, dtype),
        "wv": init_linear(ks[2], d, hkv * dh, dtype),
        "wo": init_linear(ks[3], h * dh, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rms(dh, dtype)
        p["k_norm"] = init_rms(dh, dtype)
    return p


def refuse_mla(cfg, what: str) -> None:
    """Raise where a latent-attention config asks for ``what``, which
    needs the latent KV cache it does not have yet."""
    if cfg.mla:
        raise NotImplementedError(
            f"{cfg.name} has latent attention (MLA): {what} needs a latent "
            f"KV cache, which is not built yet (ROADMAP Reach 5)")


def _init_mla(key, cfg, dtype):
    d, h, r, rd = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": init_linear(ks[0], d, h * (cfg.d_head + rd), dtype),
        "wkv_a": init_linear(ks[1], d, r + rd, dtype),
        "kv_norm": init_rms(r, dtype),
        "wkv_b": init_linear(ks[2], r, h * (cfg.d_head + cfg.v_head_dim),
                             dtype),
        "wo": init_linear(ks[3], h * cfg.v_head_dim, d, dtype),
    }


def _project_qkv(x, p, cfg, engine: DotEngine, cos, sin):
    b, s, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    with jax.named_scope("q"):
        q = engine.dot(x, p["wq"]).reshape(b, s, h, dh)
    with jax.named_scope("k"):
        k = engine.dot(x, p["wk"]).reshape(b, s, hkv, dh)
    with jax.named_scope("v"):
        v = engine.dot(x, p["wv"]).reshape(b, s, hkv, dh)
    with jax.named_scope("core"):
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)
        if cfg.rope:
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
    return q, k, v


def _sdpa(q, k, v, mask, scale):
    """q/k: (B,Sq|Sk,H|Hkv,dh), v: (B,Sk,Hkv,dv) -> (B,Sq,H,dv); GQA by
    grouping."""
    b, sq, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    scores = scores * scale
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v)
    return out.reshape(b, sq, h, v.shape[-1])


def attention(x, p, cfg, engine: DotEngine, cos, sin, *,
              q_chunk: int = 1024, residual=None, return_kv: bool = False):
    """Full-sequence attention (train / prefill).

    causal iff ``cfg.causal``; SWA iff ``cfg.swa_window``; encoder mode is
    just ``causal=False``.  ``residual`` (same shape as x) is added in
    the out-projection's fused epilogue -- the transformer block's
    ``x + attn(...)`` without a separate elementwise HBM pass
    (DESIGN.md §9).  ``return_kv=True`` additionally returns the
    post-rope/qk-norm (k, v) -- exactly what the decode cache stores --
    for bulk prefill into a decode state (transformer.prefill_kv).
    """
    from repro.distributed.ctx import constrain

    if cfg.mla:
        return mla_attention(x, p, cfg, engine, cos, sin, q_chunk=q_chunk,
                             residual=residual)
    b, s, _ = x.shape
    q, k, v = _project_qkv(x, p, cfg, engine, cos, sin)
    with jax.named_scope("core"):
        # SP attention core: queries sequence-sharded over "model"
        # (head-count agnostic, always divisible); k/v replicated across
        # it (DESIGN.md §5)
        q = constrain(q, "dp", "model", None, None)
        k = constrain(k, "dp", None, None, None)
        v = constrain(v, "dp", None, None, None)
        out = _full_core(q, k, v, cfg, q_chunk)
        out = constrain(out, "dp", "model", None, None)
    with jax.named_scope("o"):
        out = engine.dot(out.reshape(b, s, -1), p["wo"], residual=residual)
    return (out, k, v) if return_kv else out


def mla_attention(x, p, cfg, engine: DotEngine, cos, sin, *,
                  q_chunk: int = 1024, residual=None):
    """Latent attention over the whole sequence (train / prefill), as
    DeepSeek-V3 defines it with no q-LoRA: q = x wq, split per head into
    its plain (d_head) and rotary (qk_rope_dim) parts; x wkv_a gives the
    latent c (kv_lora_rank), RMS-normed, and one rotary key part shared
    by every head; c wkv_b gives each head's plain key part (d_head) and
    value (v_head_dim).  The rotary parts take RoPE by rotating halves:
    the configuration stores their columns de-interleaved, which equals
    the published rotation of adjacent pairs.  The causal softmax has
    scale (d_head + qk_rope_dim)^-1/2; ``residual`` rides the
    out-projection as in :func:`attention`."""
    b, s, _ = x.shape
    h, dn, r = cfg.n_heads, cfg.d_head, cfg.kv_lora_rank
    with jax.named_scope("q"):
        q = engine.dot(x, p["wq"]).reshape(b, s, h, -1)
    with jax.named_scope("kv_a"):
        kv = engine.dot(x, p["wkv_a"])
    with jax.named_scope("core"):
        c = rms_norm(kv[..., :r], p["kv_norm"], KV_A_NORM_EPS)
        k_pe = apply_rope(kv[..., None, r:], cos, sin)
    with jax.named_scope("kv_b"):
        kv = engine.dot(c, p["wkv_b"]).reshape(b, s, h, -1)
    with jax.named_scope("core"):
        q = jnp.concatenate([q[..., :dn], apply_rope(q[..., dn:], cos, sin)],
                            -1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe, (b, s, h, k_pe.shape[-1]))],
            -1)
        out = _full_core(q, k, kv[..., dn:], cfg, q_chunk)
    with jax.named_scope("o"):
        return engine.dot(out.reshape(b, s, -1), p["wo"], residual=residual)


def _full_core(q, k, v, cfg, q_chunk: int):
    """softmax(q k^T / sqrt(q's head width)) v over the whole sequence:
    bidirectional, or causal in statically unrolled q chunks (within the
    SWA window, if any)."""
    s = q.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1])
    window = cfg.swa_window
    if not cfg.causal:
        return _sdpa(q, k, v, None, scale)
    c = min(q_chunk, s)
    assert s % c == 0, (s, c)
    outs = []
    for i in range(s // c):
        q_i = q[:, i * c:(i + 1) * c]
        hi = (i + 1) * c
        lo = 0
        if window is not None:
            lo = max(0, hi - c - window + 1)
            lo = (lo // c) * c  # align to chunk for static shapes
        k_i = k[:, lo:hi]
        v_i = v[:, lo:hi]
        qpos = jnp.arange(i * c, hi)[:, None]
        kpos = jnp.arange(lo, hi)[None, :]
        mask = kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        outs.append(_sdpa(q_i, k_i, v_i, mask[None, None, None], scale))
    return jnp.concatenate(outs, axis=1)


def prefill_kv(x, p, cfg, engine: DotEngine, cos, sin):
    """Return (k, v) for cache seeding (no attention compute)."""
    _, k, v = _project_qkv(x, p, cfg, engine, cos, sin)
    return k, v


def paged_decode_attention(x, p, cfg, engine: DotEngine, k_pages, v_pages,
                           phys_tables, write_tables, cur_pos, cos, sin,
                           row_mask=None, residual=None, *,
                           interpret: bool | None = None):
    """One-token decode against the paged KV pool (DESIGN.md §10).

    x: (B, 1, d); k_pages/v_pages: (R, page_size, Hkv, dh) physical pool
    (last row reserved zero); phys_tables: (B, max_pages) physical rows
    for this layer (unallocated -> zero row); write_tables: (B,
    max_pages) the *logical* block table (-1 = unallocated), used to
    suppress writes through unallocated entries; cur_pos: the token's
    position -- a scalar shared by every slot (lockstep) or a (B,)
    vector of per-slot positions (continuous batching, DESIGN.md §11).
    ``row_mask``/``residual`` behave as in :func:`decode_attention`.

    Returns (out (B,1,d), k_pages', v_pages') with the new token's K/V
    scattered into each slot's page at (cur_pos // page_size,
    cur_pos % page_size).
    """
    from repro.distributed.ctx import current
    from repro.kernels.paged_attention import \
        paged_decode_attention as paged_core

    b = x.shape[0]
    page_size = k_pages.shape[1]
    q, k_new, v_new = _project_qkv(x, p, cfg, engine, cos, sin)

    pos = jnp.broadcast_to(
        jnp.asarray(cur_pos, jnp.int32).reshape(-1), (b,))
    page_idx = pos // page_size                           # (B,)
    offset = pos % page_size
    rows = jnp.take_along_axis(
        phys_tables, page_idx[:, None], axis=1)[:, 0]     # (B,)
    wmask = jnp.take_along_axis(
        write_tables, page_idx[:, None], axis=1)[:, 0] >= 0
    if row_mask is not None:  # slot-isolated writes (continuous batching)
        wmask = wmask & row_mask
    # gather-select-scatter: masked rows write their own current value
    # back, so duplicate zero-row indices stay deterministic
    sel = wmask[:, None, None]
    k_pages = k_pages.at[rows, offset].set(
        jnp.where(sel, k_new[:, 0], k_pages[rows, offset]))
    v_pages = v_pages.at[rows, offset].set(
        jnp.where(sel, v_new[:, 0], v_pages[rows, offset]))

    core = functools.partial(paged_core, interpret=interpret)
    ctx = current()
    if ctx is not None:
        # a Pallas kernel is one program per device, which the SPMD
        # partitioner cannot split: under a mesh it runs in a shard_map,
        # each model shard over the kv-head slice of the pool that
        # paged_decode_state_specs gives it (and the query heads of
        # those kv heads), everything else replicated
        ax = ctx.model_axis
        m = ctx.mesh.shape[ax]
        split = m > 1 and cfg.n_kv_heads % m == 0
        q_spec = P(None, ax if split else None, None)
        kv_spec = P(None, None, ax if split else None, None)
        core = jax.shard_map(
            core, mesh=ctx.mesh,
            in_specs=(q_spec, kv_spec, kv_spec, P(), P()),
            out_specs=q_spec, check_vma=False)
    with jax.named_scope("core"):
        out = core(q[:, 0], k_pages, v_pages, phys_tables, pos)
    with jax.named_scope("o"):
        out = engine.dot(out.reshape(b, 1, -1), p["wo"], residual=residual)
    return out, k_pages, v_pages


def decode_attention(x, p, cfg, engine: DotEngine, k_cache, v_cache,
                     cache_positions, write_slot, cur_pos, cos, sin,
                     row_mask=None, residual=None):
    """One-token decode against a (possibly ring/SWA) KV cache.

    x: (B, 1, d); k_cache/v_cache: (B, S_cache, Hkv, dh);
    cache_positions: (S_cache,) true token position held in each slot, -1 if
    empty (a ring cache reuses slots, so slot != position);
    write_slot: scalar slot index for the new token; cur_pos: its position.
    ``residual`` fuses the block's residual add into the out-projection
    (DESIGN.md §9).

    ``write_slot``/``cur_pos`` may instead be (B,) vectors -- per-row
    positions for continuous batching (DESIGN.md §11).  The vector path
    assumes the dense no-ring discipline the serve loop maintains
    (``write_slot == cur_pos``, every row's cache rows [0, cur_pos] are
    written): validity is derived per row from ``cur_pos`` alone, so a
    request's attention never depends on co-resident slots'
    ``cache_positions``.

    Returns (out (B,1,d), k_cache', v_cache') with the new entry written.
    """
    from repro.distributed import ctx as dctx

    b = x.shape[0]
    q, k_new, v_new = _project_qkv(x, p, cfg, engine, cos, sin)
    vector_pos = jnp.ndim(cur_pos) > 0
    c = dctx.current()
    if c is not None and vector_pos:
        raise NotImplementedError(
            "per-slot position vectors are single-device only; the "
            "sequence-parallel decode path takes a scalar position")
    with jax.named_scope("core"):
        if c is not None:
            # sequence-parallel decode: KV cache sharded along S,
            # online-softmax combine across shards
            # (repro.distributed.sp_attention).
            from repro.distributed.sp_attention import sp_decode_attention
            seq_axes = getattr(c, "seq_axes", None) or (c.model_axis,)
            out, k_cache, v_cache, _ = sp_decode_attention(
                q, k_cache, v_cache, cache_positions, k_new, v_new,
                write_slot, cur_pos, mesh=c.mesh, window=cfg.swa_window,
                seq_axes=seq_axes,
                dp_axes=tuple(a for a in c.dp if a not in seq_axes),
                row_mask=row_mask)
        else:
            out, k_cache, v_cache = _cached_core(
                q, k_new, v_new, k_cache, v_cache, cache_positions,
                write_slot, cur_pos, cfg, row_mask)
    with jax.named_scope("o"):
        out = engine.dot(out.reshape(b, 1, -1), p["wo"], residual=residual)
    return out, k_cache, v_cache


def _cached_core(q, k_new, v_new, k_cache, v_cache, cache_positions,
                 write_slot, cur_pos, cfg, row_mask):
    """Write the new token's K/V into one device's cache and attend over
    it: ``decode_attention`` without a mesh."""
    slots = jnp.arange(k_cache.shape[1])
    scale = 1.0 / math.sqrt(cfg.d_head)
    if jnp.ndim(cur_pos) > 0:
        # per-row write slot + per-row dense validity (no kv_pos): row b
        # attends exactly to its own positions [0, cur_pos[b]]
        sel = (slots[None, :] == write_slot[:, None])[:, :, None, None]
        if row_mask is not None:
            sel = sel & row_mask[:, None, None, None]
        k_cache = jnp.where(sel, k_new, k_cache)
        v_cache = jnp.where(sel, v_new, v_cache)
        valid = slots[None, :] <= cur_pos[:, None]           # (B, S)
        out = _sdpa(q, k_cache, v_cache,
                    valid[:, None, None, None, :], scale)
        return out, k_cache, v_cache
    sel = (slots == write_slot)[None, :, None, None]
    if row_mask is not None:  # slot-isolated writes (continuous batching)
        sel = sel & row_mask[:, None, None, None]
    k_cache = jnp.where(sel, k_new, k_cache)
    v_cache = jnp.where(sel, v_new, v_cache)
    pos = jnp.where(slots == write_slot, cur_pos, cache_positions)
    valid = (pos >= 0) & (pos <= cur_pos)
    if cfg.swa_window is not None:
        valid &= pos > cur_pos - cfg.swa_window
    out = _sdpa(q, k_cache, v_cache, valid[None, None, None, None, :], scale)
    return out, k_cache, v_cache
