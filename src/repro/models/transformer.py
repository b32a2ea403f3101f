"""Backbone assembly: scan-over-layers transformer for every arch family.

Params are a pytree with all per-layer tensors stacked on a leading
``n_layers`` axis, consumed by ``jax.lax.scan`` -- compile time is
depth-independent (essential for 60-layer dry-runs on 512 devices).
The full-sequence forward scans over the layer index with the stacks
closed over, and hands each GEMM its weight as a
:class:`~repro.models.layers.LayerView`, which the SFC kernels read in
place; the serve scans (prefill, decode) still scan over the stacks.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from .config import ArchConfig
from .layers import DotEngine, init_linear, init_rms, init_swiglu, \
    layer_params, rms_norm, rope, swiglu_mlp

__all__ = ["init_model", "forward", "loss_fn", "init_decode_state",
           "decode_step", "prefill_kv", "prefill_kv_chunk",
           "fused_epilogue_savings_bytes"]


def fused_epilogue_savings_bytes(cfg: ArchConfig, tokens: int) -> float:
    """Modeled HBM bytes one *forward pass* no longer moves because the
    epilogues are fused (DESIGN.md §9).

    Each fused site eliminates one full C round trip (re-read + re-write
    of the projection output) that the dot-then-elementwise composition
    paid: the MLP up-projection's activation (2*T*d_ff), the MLP
    down-projection's residual add (2*T*d), the attention out-
    projection's residual add (2*T*d), and the vocab head's dtype cast
    (2*T*V_padded in the activation dtype; the f32 logits write itself
    is unchanged).  Launch-layer summaries report this so a J/step or
    ms/step reading can be attributed (train.py / serve.py).
    """
    act_bytes = jnp.dtype(cfg.act_jdtype()).itemsize
    per_tok = 0.0
    if cfg.family in ("dense", "encoder", "vlm"):
        per_tok += 2.0 * cfg.d_model          # attn out-proj residual
        per_tok += 2.0 * cfg.d_ff             # MLP up-proj activation
        per_tok += 2.0 * cfg.d_model          # MLP down-proj residual
    elif cfg.family == "moe":
        per_tok += 2.0 * cfg.d_model          # attn out-proj residual
    elif cfg.family == "hybrid":
        per_tok += 2.0 * cfg.d_ff + 2.0 * cfg.d_model   # MLP sites only
    saved = cfg.n_layers * per_tok * tokens * act_bytes
    if cfg.vocab:
        saved += 2.0 * tokens * cfg.padded_vocab * act_bytes  # head cast
    return saved


# --------------------------------------------------------------- init ------
def _init_layer(key, cfg: ArchConfig, dtype, moe_pad: int | None):
    ks = jax.random.split(key, 8)
    p: dict[str, Any] = {"norm1": init_rms(cfg.d_model, dtype)}
    if cfg.family in ("dense", "encoder", "vlm"):
        p["attn"] = attn_mod.init_attention(ks[0], cfg, dtype)
        p["norm2"] = init_rms(cfg.d_model, dtype)
        p["mlp"] = init_swiglu(ks[1], cfg.d_model, cfg.d_ff, dtype)
    elif cfg.family == "moe":
        p["attn"] = attn_mod.init_attention(ks[0], cfg, dtype)
        p["norm2"] = init_rms(cfg.d_model, dtype)
        p["moe"] = moe_mod.init_moe(ks[1], cfg, dtype, moe_pad)
    elif cfg.family == "ssm":
        p["ssm"] = ssm_mod.init_ssm(ks[0], cfg, dtype)
    elif cfg.family == "hybrid":
        p["attn"] = attn_mod.init_attention(ks[0], cfg, dtype)
        p["ssm"] = ssm_mod.init_ssm(ks[1], cfg, dtype)
        p["attn_out_norm"] = init_rms(cfg.d_model, dtype)
        p["ssm_out_norm"] = init_rms(cfg.d_model, dtype)
        p["norm2"] = init_rms(cfg.d_model, dtype)
        p["mlp"] = init_swiglu(ks[2], cfg.d_model, cfg.d_ff, dtype)
    else:
        raise ValueError(cfg.family)
    return p


def _init_dense_layer(key, cfg: ArchConfig, dtype):
    """A leading dense layer of an expert model: attention and a SwiGLU
    of width ``d_ff``."""
    ks = jax.random.split(key, 2)
    return {"norm1": init_rms(cfg.d_model, dtype),
            "attn": attn_mod.init_attention(ks[0], cfg, dtype),
            "norm2": init_rms(cfg.d_model, dtype),
            "mlp": init_swiglu(ks[1], cfg.d_model, cfg.d_ff, dtype)}


def init_model(cfg: ArchConfig, key, moe_pad: int | None = None):
    """moe_pad: model-axis size to pad expert count to (EP divisibility).

    Per-layer weights are stacked under ``layers``; an expert model's
    leading dense layers (``cfg.moe_first_dense``) under
    ``dense_layers``."""
    dtype = cfg.param_jdtype()
    keys = jax.random.split(key, cfg.n_layers + 3)
    first = cfg.moe_first_dense
    layers = jax.vmap(lambda k: _init_layer(k, cfg, dtype, moe_pad))(
        keys[first:cfg.n_layers])
    params: dict[str, Any] = {
        "layers": layers,
        "final_norm": init_rms(cfg.d_model, dtype),
    }
    if first:
        params["dense_layers"] = jax.vmap(
            lambda k: _init_dense_layer(k, cfg, dtype))(keys[:first])
    if cfg.vocab:
        # vocab padded to a TP-divisible multiple (config.padded_vocab);
        # the loss/decode paths mask the padded logit columns.
        params["embed"] = (jax.random.normal(
            keys[-1], (cfg.padded_vocab, cfg.d_model)) * 0.02).astype(dtype)
        params["lm_head"] = init_linear(
            keys[-2], cfg.d_model, cfg.padded_vocab, dtype)
    if cfg.frontend:
        params["frontend_proj"] = init_linear(
            keys[-3], cfg.frontend_dim, cfg.d_model, dtype)
    return params


# ------------------------------------------------------------- forward -----
def _layer_fwd(x, lp, cfg: ArchConfig, engine: DotEngine, cos, sin, mesh,
               routes: bool = False):
    """One layer: (x, aux), or with ``routes`` (x, (aux, the expert
    layer's choice of experts (T, k)))."""
    from repro.distributed import ctx as dctx

    c = dctx.current()
    if mesh is None and c is not None:
        mesh = c.mesh
    x = dctx.constrain(x, "dp", None, None)
    aux = jnp.zeros((), jnp.float32)
    eps = cfg.norm_eps
    # residual adds ride the out-projection / down-projection GEMMs'
    # fused epilogues instead of separate elementwise passes (DESIGN.md §9)
    if cfg.family in ("dense", "encoder", "vlm", "moe"):
        with jax.named_scope("attn"):
            x = attn_mod.attention(rms_norm(x, lp["norm1"], eps), lp["attn"],
                                   cfg, engine, cos, sin,
                                   q_chunk=cfg.attn_q_chunk, residual=x)
        if "moe" in lp:
            with jax.named_scope("moe"):
                out = moe_mod.moe_ffn(
                    rms_norm(x, lp["norm2"], eps), lp["moe"], cfg, engine,
                    mesh=mesh, data_axes=(c.dp if c is not None
                                          else ("data",)), routes=routes)
            x = x + out[0]
            if routes:
                return x, out[1:]
            aux = out[1]
        else:  # dense, or an expert model's leading dense layer
            with jax.named_scope("mlp"):
                x = swiglu_mlp(rms_norm(x, lp["norm2"], eps), lp["mlp"],
                               engine, residual=x)
    elif cfg.family == "ssm":
        x = x + ssm_mod.ssd_forward(rms_norm(x, lp["norm1"], eps), lp["ssm"],
                                    cfg, engine, chunk=cfg.ssd_chunk)
    elif cfg.family == "hybrid":
        h = rms_norm(x, lp["norm1"], eps)
        a = attn_mod.attention(h, lp["attn"], cfg, engine, cos, sin,
                               q_chunk=cfg.attn_q_chunk)
        s = ssm_mod.ssd_forward(h, lp["ssm"], cfg, engine,
                                chunk=cfg.ssd_chunk)
        x = x + 0.5 * (rms_norm(a, lp["attn_out_norm"], eps)
                       + rms_norm(s, lp["ssm_out_norm"], eps))
        x = swiglu_mlp(rms_norm(x, lp["norm2"], eps), lp["mlp"], engine,
                       residual=x)
    else:
        raise ValueError(cfg.family)
    return x, aux


def embed_inputs(params, cfg: ArchConfig, batch, engine: DotEngine):
    """tokens (+ frontend features) -> (B, S, d) activations."""
    dtype = cfg.act_jdtype()
    if cfg.family == "encoder":
        # audio stub: precomputed frame embeddings (B, S, frontend_dim)
        x = engine.dot(batch["features"].astype(dtype),
                       params["frontend_proj"].astype(dtype))
        return x
    x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(dtype)
    if cfg.family == "vlm" and "vision_embeds" in batch:
        # vision stub: precomputed patch embeddings replace the first
        # ``frontend_tokens`` positions after projection (LLaVA-style).
        v = engine.dot(batch["vision_embeds"].astype(dtype),
                       params["frontend_proj"].astype(dtype))
        pos = jnp.arange(x.shape[1])[None, :, None]
        nv = v.shape[1]
        vpad = jnp.pad(v, ((0, 0), (0, x.shape[1] - nv), (0, 0)))
        x = jnp.where(pos < nv, vpad, x)
    return x


def forward(params, cfg: ArchConfig, batch, engine: DotEngine | None = None,
            mesh=None, routes: bool = False):
    """Full-sequence forward -> (logits (B,S,V) f32, aux_loss); with
    ``routes`` (an expert model on the dropless path), also each expert
    layer's choice of experts, (expert layers, B, S, k) int32."""
    from repro.distributed.ctx import constrain
    engine = engine or DotEngine()
    with jax.named_scope("embed"):
        x = embed_inputs(params, cfg, batch, engine)
        x = constrain(x, "dp", None, None)
        b, s, _ = x.shape
        if cfg.has_attention and cfg.rope:
            cos, sin = rope(jnp.arange(s), cfg.rope_dim, cfg.rope_theta)
        else:
            cos = sin = None

    def scan_layers(stack, routes):
        def body(x, i):
            return _layer_fwd(x, layer_params(stack, i), cfg, engine, cos,
                              sin, mesh, routes)

        if cfg.remat:
            if cfg.remat_policy == "dots":
                # save GEMM outputs, recompute only elementwise chains --
                # cuts backward recompute flops and activation traffic
                policy = \
                    jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                body = jax.checkpoint(body, policy=policy)
            else:
                body = jax.checkpoint(body)
        n = jax.tree.leaves(stack)[0].shape[0]
        return jax.lax.scan(body, x, jnp.arange(n))

    with jax.named_scope("layers"):
        if "dense_layers" in params:
            x, _ = scan_layers(params["dense_layers"], False)
        x, auxs = scan_layers(params["layers"], routes)
    logits = _head(params, cfg, x, engine)
    logits = constrain(logits, "dp", None, "model")
    if routes:
        auxs, idx = auxs
        return logits, auxs.mean(), idx.reshape(idx.shape[0], b, s, -1)
    return logits, auxs.mean()


def _head(params, cfg: ArchConfig, x, engine: DotEngine):
    """Final norm and vocab head: (..., d) -> (..., V) f32 logits,
    padded columns masked (the normed activations where the family has
    no vocab).  The f32 cast is fused into the GEMM's single output
    write instead of a separate full-logits cast pass."""
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if not cfg.vocab:
        return x
    with jax.named_scope("head"):
        logits = engine.dot(x, params["lm_head"], out_dtype=jnp.float32)
        return _mask_padded_vocab(logits, cfg)


def _mask_padded_vocab(logits, cfg: ArchConfig):
    if cfg.vocab and cfg.padded_vocab != cfg.vocab:
        pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab
        logits = jnp.where(pad, -1e30, logits)
    return logits


def loss_fn(params, cfg: ArchConfig, batch, engine: DotEngine | None = None,
            mesh=None, aux_weight: float = 0.01):
    """Next-token (causal) or per-position (encoder) cross entropy."""
    logits, aux = forward(params, cfg, batch, engine, mesh)
    labels = batch["labels"]
    if cfg.causal:
        logits = logits[:, :-1]
        labels = labels[:, 1:]
    mask = batch.get("loss_mask")
    if mask is not None and cfg.causal:
        mask = mask[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        nll = nll * mask
        denom = jnp.maximum(mask.sum(), 1.0)
    else:
        denom = nll.size
    loss = nll.sum() / denom
    return loss + aux_weight * aux, {"ce": loss, "aux": aux}


# --------------------------------------------------------------- decode ----
def init_decode_state(cfg: ArchConfig, batch: int, cache_len: int,
                      dtype=None, *, layout=None, paged: bool | None = None,
                      page_size: int = 8, num_pages: int | None = None,
                      max_pages_per_slot: int | None = None):
    """Allocate per-layer caches (stacked on layer axis for lax.scan).

    ``layout`` is a :class:`repro.serve.state.KVLayout`:
    ``KVLayout.PAGED`` returns the paged-KV state (DESIGN.md §10) -- a
    shared physical page pool in Morton (layer, page) order plus
    per-slot block tables; ``cache_len`` then only sizes the default
    pool (same token footprint as the contiguous strips), it no longer
    bounds any single sequence.  The returned
    :class:`~repro.serve.state.DecodeState` carries the layout as
    static pytree metadata, so ``decode_step``/``prefill_kv`` dispatch
    on it instead of sniffing key names.  The legacy ``paged=`` bool is
    still accepted with a ``DeprecationWarning``.
    """
    from repro.serve.state import DecodeState, KVLayout, resolve_layout
    attn_mod.refuse_mla(cfg, "a decode state")
    layout = resolve_layout(layout, paged)
    if layout is KVLayout.PAGED:
        from repro.serve.paged_kv import init_paged_decode_state
        return init_paged_decode_state(
            cfg, batch, page_size=page_size, num_pages=num_pages,
            max_pages_per_slot=max_pages_per_slot, cache_len=cache_len,
            dtype=dtype)
    dtype = dtype or cfg.act_jdtype()
    st: dict[str, Any] = {}
    if cfg.has_attention:
        c = cache_len if cfg.swa_window is None \
            else min(cache_len, cfg.swa_window)
        st["k"] = jnp.zeros(
            (cfg.n_layers, batch, c, cfg.n_kv_heads, cfg.d_head), dtype)
        st["v"] = jnp.zeros_like(st["k"])
        st["kv_pos"] = jnp.full((c,), -1, jnp.int32)
    if cfg.has_ssm:
        shp = ssm_mod.ssm_state_shape(cfg, batch)
        st["ssm_h"] = jnp.zeros((cfg.n_layers,) + shp["h"], jnp.float32)
        st["ssm_conv"] = jnp.zeros((cfg.n_layers,) + shp["conv"], dtype)
    return DecodeState(st, KVLayout.CONTIGUOUS)


def _is_paged(state) -> bool:
    """Layout dispatch: the DecodeState's static KVLayout when present,
    the historical key sniff as a fallback for hand-built dict states."""
    from repro.serve.state import DecodeState
    if isinstance(state, DecodeState):
        return state.layout.is_paged
    return "k_pages" in state


def _decode_rope(cfg: ArchConfig, pos):
    """(cos, sin) for a decode step's position(s): scalar ``pos`` and
    per-slot (B,) vectors produce (1, 1, dh/2) / (B, 1, dh/2) tables --
    ``apply_rope`` broadcasts either against (B, 1, H, dh)."""
    pvec = jnp.asarray(pos, jnp.int32).reshape(-1)
    cos, sin = rope(pvec, cfg.d_head, cfg.rope_theta)
    return cos[:, None], sin[:, None]


def prefill_kv(params, cfg: ArchConfig, state, tokens, slot: int = 0,
               engine: DotEngine | None = None):
    """Bulk-prefill one slot's KV cache from a prompt in a single forward.

    ``tokens``: (L,) int32 prompt; the computed per-layer post-rope
    (k, v) -- exactly what ``decode_step`` would have cached token by
    token -- are written into ``state`` at positions [0, L), into the
    slot's contiguous cache row or its paged block-table pages
    (layout auto-detected; a paged state must have pages covering
    [0, L) already allocated, see ``PageAllocator.ensure_range``).

    Returns ``(logits (1, L, V) f32, new_state)``.  Attention-only
    families (dense / vlm / moe); ssm and hybrid states decode-prefill
    through ``decode_step`` instead.
    """
    attn_mod.refuse_mla(cfg, "prefill")
    engine = engine or DotEngine()
    if not cfg.has_attention or cfg.has_ssm:
        raise ValueError(
            f"bulk prefill_kv needs a pure-attention family, got "
            f"{cfg.family!r}")
    toks = jnp.asarray(tokens, jnp.int32).reshape(1, -1)
    seq = toks.shape[1]
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], toks, axis=0).astype(cfg.act_jdtype())
    if cfg.rope:
        cos, sin = rope(jnp.arange(seq), cfg.d_head, cfg.rope_theta)
    else:
        cos = sin = None

    def body(x, lp):
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        # q_chunk=seq: one exact-softmax chunk for any prompt length
        x, k, v = attn_mod.attention(h, lp["attn"], cfg, engine, cos, sin,
                                     q_chunk=seq, residual=x,
                                     return_kv=True)
        if cfg.family in ("dense", "vlm"):
            x = swiglu_mlp(rms_norm(x, lp["norm2"], cfg.norm_eps), lp["mlp"],
                           engine, residual=x)
        else:  # moe
            y, _ = moe_mod.moe_ffn(
                rms_norm(x, lp["norm2"], cfg.norm_eps), lp["moe"], cfg, engine,
                impl="dense")
            x = x + y
        return x, (k, v)

    with jax.named_scope("layers"):
        x, (k, v) = jax.lax.scan(body, x, params["layers"])
    k, v = k[:, 0], v[:, 0]          # (L_layers, seq, hkv, dh)
    from repro.serve.state import copy_state
    new_state = copy_state(state)
    if _is_paged(state):
        from repro.serve.paged_kv import pages_needed, physical_rows, \
            zero_row_index
        ps = state["k_pages"].shape[1]
        npg = pages_needed(seq, ps)
        pad = npg * ps - seq
        bt_row = state["block_tables"][slot, :npg]           # (npg,)
        # unallocated entries write zeros into the reserved zero row
        # (keeping it zero) instead of corrupting a live page
        keep = (bt_row >= 0)[None, :, None, None, None]
        phys = physical_rows(state["page_perm"], bt_row,
                             zero_row_index(state["k_pages"]))  # (L, npg)

        def to_pages(a):
            a = jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
            a = a.reshape(a.shape[0], npg, ps, *a.shape[2:])
            return jnp.where(keep, a, 0)

        new_state["k_pages"] = state["k_pages"].at[phys].set(to_pages(k))
        new_state["v_pages"] = state["v_pages"].at[phys].set(to_pages(v))
    else:
        assert seq <= state["k"].shape[2], (seq, state["k"].shape)
        new_state["k"] = state["k"].at[:, slot, :seq].set(k)
        new_state["v"] = state["v"].at[:, slot, :seq].set(v)
        new_state["kv_pos"] = state["kv_pos"].at[:seq].set(
            jnp.arange(seq, dtype=jnp.int32))
    return _head(params, cfg, x, engine), new_state


def prefill_kv_chunk(params, cfg: ArchConfig, state, tokens, slots,
                     starts, lengths, engine: DotEngine | None = None):
    """Chunked, batched prefill: one prompt *chunk* per row, written
    through the block tables (paged) or into the contiguous strips.

    tokens: (G, L) int32 -- G gang rows padded to a common chunk width L;
    slots: (G,) decode-slot ids (distinct); starts: (G,) absolute
    position of each row's first token; lengths: (G,) valid tokens per
    row (0 <= lengths <= L; pad columns -- and whole pad rows with
    length 0 -- are ignored).  Chunk queries
    attend to the slot's *full written span* [0, starts+lengths) -- the
    earlier chunks are read back out of the cache -- so interleaving
    chunks between decode steps reproduces the single-shot
    :func:`prefill_kv` K/V exactly.  Positions must already be writable
    (contiguous: within cache_len; paged: covered by allocated pages,
    see ``PageAllocator.ensure_range``).

    Returns the new state only: chunk logits are never sampled from (the
    serve loop samples the first generated token from a decode step fed
    the prompt's last token, DESIGN.md §11), so the final-norm/lm_head
    compute is skipped.  Attention-only families, like ``prefill_kv``.
    """
    attn_mod.refuse_mla(cfg, "chunked prefill")
    engine = engine or DotEngine()
    if not cfg.has_attention or cfg.has_ssm:
        raise ValueError(
            f"chunked prefill needs a pure-attention family, got "
            f"{cfg.family!r}")
    import math as _math

    from repro.serve.state import copy_state

    toks = jnp.asarray(tokens, jnp.int32)
    g, chunk = toks.shape
    slots_v = jnp.asarray(slots, jnp.int32).reshape(-1)
    starts_v = jnp.asarray(starts, jnp.int32).reshape(-1)
    lens_v = jnp.asarray(lengths, jnp.int32).reshape(-1)
    pos2d = starts_v[:, None] + jnp.arange(chunk, dtype=jnp.int32)  # (G, L)
    valid = jnp.arange(chunk)[None, :] < lens_v[:, None]            # (G, L)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], toks, axis=0).astype(cfg.act_jdtype())
    if cfg.rope:
        cos, sin = rope(pos2d, cfg.d_head, cfg.rope_theta)  # (G, L, dh/2)
    else:
        cos = sin = None
    scale = 1.0 / _math.sqrt(cfg.d_head)
    wsel = valid[:, :, None, None]
    paged = _is_paged(state)
    new_state = copy_state(state)

    if paged:
        from repro.serve.paged_kv import physical_rows, zero_row_index
        ps = state["k_pages"].shape[1]
        zero_row = zero_row_index(state["k_pages"])
        bt = state["block_tables"]
        max_pages = bt.shape[1]
        span = max_pages * ps
        pg2d = jnp.minimum(pos2d // ps, max_pages - 1)        # (G, L)
        off2d = pos2d % ps
        # suppress writes through pad columns and unallocated entries
        wmask = valid & (
            jnp.take_along_axis(bt[slots_v], pg2d, axis=1) >= 0)
        # gather-select-write-back: masked entries (all aliasing the
        # reserved zero row) rewrite their current value, keeping
        # duplicate scatter indices deterministic
        wselp = wmask[:, :, None, None]
    else:
        ps = span = 0

    def _chunk_layer(x, lp, k_cache, v_cache, phys):
        """One layer: project the chunk, scatter K/V, attend over the
        slot's full written span, finish the block.  Returns
        (x', k_cache', v_cache')."""
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        q, k, v = attn_mod._project_qkv(h, lp["attn"], cfg, engine,
                                        cos, sin)
        if paged:
            rows = jnp.take_along_axis(phys[slots_v], pg2d, axis=1)
            k_cache = k_cache.at[rows, off2d].set(
                jnp.where(wselp, k, k_cache[rows, off2d]))
            v_cache = v_cache.at[rows, off2d].set(
                jnp.where(wselp, v, v_cache[rows, off2d]))
            kf = k_cache[phys[slots_v]].reshape(g, span, *k.shape[2:])
            vf = v_cache[phys[slots_v]].reshape(g, span, *v.shape[2:])
            sk = span
        else:
            c = k_cache.shape[1]
            p2 = jnp.minimum(pos2d, c - 1)
            cur = k_cache[slots_v[:, None], p2]
            k_cache = k_cache.at[slots_v[:, None], p2].set(
                jnp.where(wsel, k, cur))
            cur = v_cache[slots_v[:, None], p2]
            v_cache = v_cache.at[slots_v[:, None], p2].set(
                jnp.where(wsel, v, cur))
            kf = k_cache[slots_v]                       # (G, C, hkv, dh)
            vf = v_cache[slots_v]
            sk = c
        # causal over the written extent only: key t visible to chunk
        # query at position p iff t <= min(p, starts+lengths-1)
        kpos = jnp.arange(sk, dtype=jnp.int32)[None, None, :]
        mask = kpos <= jnp.minimum(
            pos2d, (starts_v + lens_v - 1)[:, None])[:, :, None]
        with jax.named_scope("core"):
            o = attn_mod._sdpa(q, kf, vf, mask[:, None, None], scale)
        with jax.named_scope("o"):
            x = engine.dot(o.reshape(g, chunk, -1), lp["attn"]["wo"],
                           residual=x)
        if cfg.family in ("dense", "vlm"):
            x = swiglu_mlp(rms_norm(x, lp["norm2"], cfg.norm_eps), lp["mlp"],
                           engine, residual=x)
        else:  # moe
            y, _ = moe_mod.moe_ffn(
                rms_norm(x, lp["norm2"], cfg.norm_eps), lp["moe"], cfg, engine,
                impl="dense")
            x = x + y
        return x, k_cache, v_cache

    if paged:
        def body(carry, layer):
            x, kp, vp = carry
            phys = physical_rows(layer["perm"], bt, zero_row)
            x, kp, vp = _chunk_layer(x, layer["p"], kp, vp, phys)
            return (x, kp, vp), None

        with jax.named_scope("layers"):
            (x, kp, vp), _ = jax.lax.scan(
                body, (x, state["k_pages"], state["v_pages"]),
                {"p": params["layers"], "perm": state["page_perm"]})
        new_state["k_pages"] = kp
        new_state["v_pages"] = vp
    else:
        def body(x, layer):
            x, kc, vc = _chunk_layer(x, layer["p"], layer["k"],
                                     layer["v"], None)
            return x, (kc, vc)

        with jax.named_scope("layers"):
            x, (kc, vc) = jax.lax.scan(
                body, x, {"p": params["layers"], "k": state["k"],
                          "v": state["v"]})
        new_state["k"] = kc
        new_state["v"] = vc
        # dense discipline: slot p holds position p (the vector decode
        # path never reads kv_pos; scalar lockstep still can)
        flat_idx = jnp.where(valid, pos2d, 0).reshape(-1)
        flat_val = jnp.where(valid, pos2d, -1).reshape(-1)
        new_state["kv_pos"] = state["kv_pos"].at[flat_idx].max(flat_val)
    return new_state


def _decode_step_paged(params, cfg: ArchConfig, state, tokens, pos,
                       engine: DotEngine, row_mask):
    """Paged-cache decode step (DESIGN.md §10): the physical page pool is
    a scan *carry* (Morton interleaving means one layer's rows are not a
    contiguous slice, so the pool cannot be scanned as per-layer xs);
    each layer resolves its block table through its row of the Morton
    permutation and gathers/scatters its own pages.  ``pos`` is a scalar
    (lockstep) or a (B,) per-slot vector (continuous batching)."""
    from repro.serve.paged_kv import physical_rows, zero_row_index
    from repro.serve.state import copy_state

    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(
            cfg.act_jdtype())
    cos, sin = _decode_rope(cfg, pos) if cfg.rope else (None, None)
    zero_row = zero_row_index(state["k_pages"])
    bt = state["block_tables"]

    def body(carry, layer):
        x, kp, vp = carry
        lp = layer["p"]
        # physical rows for this layer; unallocated entries read the
        # reserved zero row (exact parity with never-written contiguous
        # cache rows)
        phys = physical_rows(layer["perm"], bt, zero_row)
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        x, kp, vp = attn_mod.paged_decode_attention(
            h, lp["attn"], cfg, engine, kp, vp, phys, bt, pos, cos, sin,
            row_mask, residual=x)
        if cfg.family in ("dense", "vlm"):
            x = swiglu_mlp(rms_norm(x, lp["norm2"], cfg.norm_eps), lp["mlp"],
                           engine, residual=x)
        else:  # moe (state construction rejects ssm/hybrid)
            y, _ = moe_mod.moe_ffn(
                rms_norm(x, lp["norm2"], cfg.norm_eps), lp["moe"], cfg, engine,
                impl="dense")
            x = x + y
        return (x, kp, vp), None

    with jax.named_scope("layers"):
        (x, kp, vp), _ = jax.lax.scan(
            body, (x, state["k_pages"], state["v_pages"]),
            {"p": params["layers"], "perm": state["page_perm"]})
    new_state = copy_state(state)
    new_state["k_pages"] = kp
    new_state["v_pages"] = vp
    return _head(params, cfg, x, engine), new_state


def decode_step(params, cfg: ArchConfig, state, tokens, pos,
                engine: DotEngine | None = None, row_mask=None):
    """One decode step.  tokens: (B, 1) int32; pos: scalar int32 position
    shared by every row (lockstep), or a (B,) vector of per-row
    positions (continuous batching -- each request advances on its own
    clock, DESIGN.md §11; requires ``cfg.swa_window is None``).

    Returns (logits (B, 1, V), new_state).  The KV cache is a ring buffer
    when SWA bounds it (slot = pos % cache_len); dense otherwise.  The
    layout is read off the :class:`~repro.serve.state.DecodeState`
    (``KVLayout.PAGED`` routes through the paged attention path,
    DESIGN.md §10); hand-built dict states fall back to key sniffing.
    ``row_mask`` (B,) bool: rows with False keep their caches/states
    untouched (slot-isolated writes for continuous batching).
    """
    attn_mod.refuse_mla(cfg, "decode")
    engine = engine or DotEngine()
    if _is_paged(state):
        return _decode_step_paged(params, cfg, state, tokens, pos,
                                  engine, row_mask)
    from repro.serve.state import copy_state
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(
            cfg.act_jdtype())
    if cfg.has_attention and cfg.rope:
        cos, sin = _decode_rope(cfg, pos)  # (1|B, 1, dh/2)
    else:
        cos = sin = None
    cache_len = state["k"].shape[2] if cfg.has_attention else 0
    slot = pos % cache_len if cfg.has_attention else 0

    def body(x, layer):
        lp = layer["p"]
        outs = {}
        if cfg.family in ("dense", "vlm"):
            x, knew, vnew = attn_mod.decode_attention(
                rms_norm(x, lp["norm1"], cfg.norm_eps), lp["attn"], cfg,
                engine, layer["k"], layer["v"], state["kv_pos"], slot, pos,
                cos, sin, row_mask, residual=x)
            x = swiglu_mlp(rms_norm(x, lp["norm2"], cfg.norm_eps), lp["mlp"],
                           engine, residual=x)
            outs.update(k=knew, v=vnew)
        elif cfg.family == "moe":
            x, knew, vnew = attn_mod.decode_attention(
                rms_norm(x, lp["norm1"], cfg.norm_eps), lp["attn"], cfg,
                engine, layer["k"], layer["v"], state["kv_pos"], slot, pos,
                cos, sin, row_mask, residual=x)
            # decode T is tiny: dense all-experts combine is exact
            # (dropless) and avoids sort/scatter under SPMD
            y, _ = moe_mod.moe_ffn(
                rms_norm(x, lp["norm2"], cfg.norm_eps), lp["moe"], cfg, engine,
                impl="dense")
            x = x + y
            outs.update(k=knew, v=vnew)
        elif cfg.family == "ssm":
            y, ssm_new = ssm_mod.ssm_decode(
                rms_norm(x, lp["norm1"], cfg.norm_eps), lp["ssm"], cfg, engine,
                {"h": layer["ssm_h"], "conv": layer["ssm_conv"]},
                row_mask=row_mask)
            x = x + y
            outs.update(ssm_h=ssm_new["h"], ssm_conv=ssm_new["conv"])
        elif cfg.family == "hybrid":
            h = rms_norm(x, lp["norm1"], cfg.norm_eps)
            a, knew, vnew = attn_mod.decode_attention(
                h, lp["attn"], cfg, engine,
                layer["k"], layer["v"], state["kv_pos"], slot, pos, cos,
                sin, row_mask)
            s, ssm_new = ssm_mod.ssm_decode(
                h, lp["ssm"], cfg, engine,
                {"h": layer["ssm_h"], "conv": layer["ssm_conv"]},
                row_mask=row_mask)
            x = x + 0.5 * (rms_norm(a, lp["attn_out_norm"], cfg.norm_eps)
                           + rms_norm(s, lp["ssm_out_norm"], cfg.norm_eps))
            x = swiglu_mlp(rms_norm(x, lp["norm2"], cfg.norm_eps), lp["mlp"],
                           engine, residual=x)
            outs.update(k=knew, v=vnew, ssm_h=ssm_new["h"],
                        ssm_conv=ssm_new["conv"])
        return x, outs

    xs = {"p": params["layers"]}
    for key in ("k", "v", "ssm_h", "ssm_conv"):
        if key in state:
            xs[key] = state[key]
    with jax.named_scope("layers"):
        x, upd = jax.lax.scan(body, x, xs)
    new_state = copy_state(state)
    for key in ("ssm_h", "ssm_conv"):
        if key in upd:
            new_state[key] = upd[key]
    if cfg.has_attention:
        new_state["k"] = upd["k"]
        new_state["v"] = upd["v"]
        new_state["kv_pos"] = state["kv_pos"].at[slot].set(pos)
    return _head(params, cfg, x, engine), new_state
