"""Token-choice top-k MoE (granite-3.0 style) with capacity dispatch.

Three execution paths, trading simplicity for scalability:

* ``moe_dense``    -- compute every expert on every token, mask-combine.
                      O(E) overcompute; only for tiny smoke configs and as
                      the correctness oracle.
* ``moe_capacity`` -- sort-based capacity dispatch on one logical device
                      (GShard-style): tokens are bucketed per expert with
                      capacity C = ceil(T*k/E * cf); overflow drops (router
                      renormalises).  This is what runs under plain pjit.
* ``moe_ep``       -- expert parallelism: local (per data shard) capacity
                      dispatch, then ``all_to_all`` over the model axis to
                      place buckets on their expert's shard, expert GEMMs,
                      and the reverse all_to_all.  shard_map implementation
                      used by the production mesh (the collective shows up
                      in the roofline, as it must).

Experts whose count does not divide the model axis (granite-3b: 40) are
padded with never-routed dummy experts (router logits masked to -inf).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import DotEngine, init_linear

__all__ = ["init_moe", "moe_dense", "moe_capacity", "moe_ep", "moe_ffn"]


def padded_experts(cfg, model_axis_size: int | None = None) -> int:
    e = cfg.moe_experts
    if model_axis_size:
        e = -(-e // model_axis_size) * model_axis_size
    return e


def init_moe(key, cfg, dtype=jnp.float32, model_axis_size: int | None = None):
    d, ff = cfg.d_model, cfg.moe_dff
    e = padded_experts(cfg, model_axis_size)
    ks = jax.random.split(key, 4)
    return {
        "router": init_linear(ks[0], d, e, jnp.float32),
        "w1": init_linear(ks[1], d, ff, dtype)[None].repeat(e, 0)
        * (1 + 0.01 * jnp.arange(e, dtype=dtype)[:, None, None]),
        "w3": init_linear(ks[2], d, ff, dtype)[None].repeat(e, 0),
        "w2": init_linear(ks[3], ff, d, dtype)[None].repeat(e, 0),
    }


def _router(xf, params, cfg):
    """xf: (T, d) -> (weights (T,k), idx (T,k), aux_loss)."""
    e_real = cfg.moe_experts
    logits = (xf.astype(jnp.float32) @ params["router"])
    e_pad = logits.shape[-1]
    if e_pad > e_real:  # mask padded experts
        neg = jnp.full((e_pad - e_real,), -1e30, jnp.float32)
        logits = logits.at[..., e_real:].add(neg)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.moe_topk)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch): E * sum_e f_e * p_e
    pe = probs.mean(0)
    onehot = jax.nn.one_hot(idx[:, 0], e_pad)  # fraction by top-1 choice
    fe = onehot.mean(0)
    aux = e_real * jnp.sum(fe * pe)
    return w, idx, aux


def _expert_ffn(buf, params):
    """buf: (E, C, d) -> (E, C, d) via per-expert SwiGLU."""
    g = jnp.einsum("ecd,edf->ecf", buf, params["w1"])
    u = jnp.einsum("ecd,edf->ecf", buf, params["w3"])
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, params["w2"])


def moe_dense(x, params, cfg, engine: DotEngine):
    """All-experts compute, mask combine (oracle / tiny configs)."""
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    w, idx, aux = _router(xf, params, cfg)
    e = params["w1"].shape[0]
    y_all = _expert_ffn(
        jnp.broadcast_to(xf, (e,) + xf.shape), params)      # (E, T, d)
    # scatter-free gate: sum of one-hots (partitions cleanly under SPMD)
    gate = (jax.nn.one_hot(idx, e, dtype=xf.dtype)
            * w[..., None].astype(xf.dtype)).sum(axis=1)    # (T, E)
    y = jnp.einsum("te,etd->td", gate, y_all)
    return y.reshape(b, s, d), aux


def _dispatch_indices(idx, w, e: int, capacity: int):
    """Sort-based bucket placement.  idx/w: (T, k).

    Returns (bucket_idx (T*k,), keep (T*k,), src_token (T*k,)) where
    bucket_idx in [0, E*C) is each assignment's slot; dropped assignments
    get keep=False (slot 0, weight zeroed by caller).
    """
    t, k = idx.shape
    flat_e = idx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    # rank within expert = position - first position of that expert
    pos = jnp.arange(t * k)
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    rank = pos - seg_start[sorted_e]
    keep_sorted = rank < capacity
    bucket_sorted = sorted_e * capacity + jnp.minimum(rank, capacity - 1)
    # un-sort back to assignment order
    inv = jnp.argsort(order, stable=True)
    bucket = bucket_sorted[inv]
    keep = keep_sorted[inv]
    src_token = pos // k
    return bucket, keep, src_token


def moe_capacity(x, params, cfg, engine: DotEngine,
                 capacity_factor: float = 1.25, capacity: int | None = None):
    """Single-device capacity dispatch (GShard-style)."""
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    t = xf.shape[0]
    e = params["w1"].shape[0]
    k = cfg.moe_topk
    c = capacity or max(1, int(t * k / e * capacity_factor))
    w, idx, aux = _router(xf, params, cfg)

    bucket, keep, src = _dispatch_indices(idx, w, e, c)
    wf = jnp.where(keep, w.reshape(-1), 0.0)
    buf = jnp.zeros((e * c, d), xf.dtype)
    contrib = jnp.where(keep[:, None], xf[src], 0)
    buf = buf.at[bucket].add(contrib)         # each kept slot written once
    out_buf = _expert_ffn(buf.reshape(e, c, d), params).reshape(e * c, d)
    y = jnp.zeros_like(xf)
    y = y.at[src].add(out_buf[bucket] * wf[:, None].astype(xf.dtype))
    return y.reshape(b, s, d), aux


def moe_ep(x, params, cfg, mesh, engine: DotEngine,
           capacity_factor: float = 1.25, data_axes=("data",),
           model_axis: str = "model"):
    """Expert-parallel MoE: local routing + all_to_all to expert shards.

    x sharded (batch over data axes); experts sharded over ``model_axis``.
    Inside shard_map each model shard owns E_loc = E/m experts; token
    buckets travel via two all_to_alls (dispatch + return).
    """
    m = mesh.shape[model_axis]
    e = params["w1"].shape[0]
    assert e % m == 0, (e, m)
    e_loc = e // m
    b, s, d = x.shape
    k = cfg.moe_topk
    assert s % m == 0, (s, m)  # tokens split over model before routing

    dpt = tuple(data_axes) if len(data_axes) > 1 else data_axes[0]
    x_spec = P(dpt, model_axis, None)

    def local(xl, router, w1, w3, w2):
        # xl: (B_loc, S/m, d): every chip routes a DISTINCT token slice
        # (sequence split over the model axis) -- routing work and the
        # capacity buffers scale 1/m, then all_to_all places buckets on
        # their expert's shard.
        bl, sl, dl = xl.shape
        xf = xl.reshape(-1, dl)
        tl = xf.shape[0]
        c = max(1, int(tl * k / e * capacity_factor))
        pr = {"router": router}
        w, idx, aux = _router(xf, pr, cfg)
        bucket, keep, src = _dispatch_indices(idx, w, e, c)
        wf = jnp.where(keep, w.reshape(-1), 0.0)
        buf = jnp.zeros((e * c, dl), xf.dtype)
        buf = buf.at[bucket].add(jnp.where(keep[:, None], xf[src], 0))
        # dispatch: split the expert dim over model shards, gather every
        # peer's buckets for the locally-owned experts on the token dim
        buf = buf.reshape(e, c, dl)
        buf = jax.lax.all_to_all(
            buf, model_axis, split_axis=0, concat_axis=1,
            tiled=True)                                   # (E_loc, m*C, d)
        pl_ = {"w1": w1, "w3": w3, "w2": w2}
        out = _expert_ffn(buf, pl_)
        out = jax.lax.all_to_all(
            out, model_axis, split_axis=1, concat_axis=0,
            tiled=True)                                   # (E, C, d)
        out = out.reshape(e * c, dl)
        y = jnp.zeros_like(xf)
        y = y.at[src].add(out[bucket] * wf[:, None].astype(xf.dtype))
        aux = jax.lax.pmean(aux, model_axis)  # replicated over model
        return y.reshape(bl, sl, dl), aux[None]

    espec = P(model_axis, None, None)
    y, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, P(), espec, espec, espec),
        out_specs=(x_spec, P(dpt)),
        check_vma=False,
    )(x, params["router"], params["w1"], params["w3"], params["w2"])
    return y, aux.mean()


def moe_ffn(x, params, cfg, engine: DotEngine, mesh=None, impl="auto",
            data_axes=("data",), model_axis="model", capacity=None):
    """Dispatcher: pick the MoE path by mesh/impl."""
    if impl == "dense" or (impl == "auto" and x.shape[0] * x.shape[1] <= 256):
        return moe_dense(x, params, cfg, engine)
    if mesh is not None and impl in ("auto", "ep"):
        return moe_ep(x, params, cfg, mesh, engine,
                      data_axes=data_axes, model_axis=model_axis)
    return moe_capacity(x, params, cfg, engine, capacity=capacity)
