"""Token-choice top-k MoE: the router, the dispatch, the experts, and
shared experts.

Routers (``cfg.moe_router``): "softmax" (granite-3.0: softmax scores,
the top k renormalised) or "sigmoid" (DeepSeek-V3's ``noaux_tc``:
sigmoid scores, the top k chosen by score plus a correction bias, the
weights the unbiased scores renormalised and times
``cfg.moe_route_scale``).

A layer holds experts [``cfg.moe_first_held``, + ``cfg.held_experts``)
of the router's ``cfg.moe_experts`` (all of them by default): the
share one chip of an expert-parallel deployment holds.  It routes over
every expert and computes its own experts' part of the result for the
tokens routed to them; shared experts (``cfg.moe_shared_dff``), which
every chip computes alike, are added by :func:`moe_ffn`.

Four execution paths:

* ``moe_dropless`` -- the one-chip path: the assignments to held experts
                      sorted by expert, each expert's rows padded to
                      whole row tiles, the expert GEMMs as grouped GEMMs
                      (``DotEngine.dot_grouped``) over a buffer sized for
                      the worst case, so no token is ever dropped.
* ``moe_dense``    -- compute every expert on every token, mask-combine.
                      O(E) overcompute; the correctness oracle.
* ``moe_capacity`` -- sort-based capacity dispatch on one logical device
                      (GShard-style): tokens are bucketed per expert with
                      capacity C = ceil(T*k/E * cf); overflow drops (router
                      renormalises).  A test oracle.
* ``moe_ep``       -- expert parallelism: local (per data shard) capacity
                      dispatch, then ``all_to_all`` over the model axis to
                      place buckets on their expert's shard, expert GEMMs,
                      and the reverse all_to_all.  shard_map implementation
                      used by the production mesh (the collective shows up
                      in the roofline, as it must).

``moe_dense``, ``moe_capacity`` and ``moe_ep`` take the softmax router
with every expert held (``moe_ffn`` refuses any other config on them).
Experts whose count does not divide the model
axis (granite-3b: 40) are padded with never-routed dummy experts
(router logits masked to -inf).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import DotEngine, init_linear, init_swiglu, swiglu_mlp, weight

__all__ = ["init_moe", "moe_dropless", "moe_dense", "moe_capacity", "moe_ep",
           "moe_ffn"]


def padded_experts(cfg, model_axis_size: int | None = None) -> int:
    e = cfg.moe_experts
    if model_axis_size:
        e = -(-e // model_axis_size) * model_axis_size
    return e


def init_moe(key, cfg, dtype=jnp.float32, model_axis_size: int | None = None):
    """Router over every expert (padded for EP divisibility), the held
    experts' weights, and the correction bias and shared experts where
    the config has them."""
    d, ff = cfg.d_model, cfg.moe_dff
    e = padded_experts(cfg, model_axis_size)
    held = cfg.moe_held or e
    ks = jax.random.split(key, 4)
    p = {
        "router": init_linear(ks[0], d, e, jnp.float32),
        "w1": init_linear(ks[1], d, ff, dtype)[None].repeat(held, 0)
        * (1 + 0.01 * jnp.arange(held, dtype=dtype)[:, None, None]),
        "w3": init_linear(ks[2], d, ff, dtype)[None].repeat(held, 0),
        "w2": init_linear(ks[3], ff, d, dtype)[None].repeat(held, 0),
    }
    if cfg.moe_router == "sigmoid":
        p["router_bias"] = 0.1 * jax.random.normal(
            jax.random.fold_in(key, 4), (e,), jnp.float32)
    if cfg.moe_shared_dff:
        p["shared"] = init_swiglu(jax.random.fold_in(key, 5), d,
                                  cfg.moe_shared_dff, dtype)
    return p


def _router(xf, params, cfg):
    """xf: (T, d) -> (weights (T,k), idx (T,k), aux_loss)."""
    params = dict(params, router=weight(params["router"]))
    if cfg.moe_router == "sigmoid":
        return _sigmoid_router(xf, params, cfg)
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


def _sigmoid_router(xf, params, cfg):
    """DeepSeek-V3's ``noaux_tc`` router, one expert group: scores
    sigmoid(x W) in float32 over every expert; the top k chosen by
    score + correction bias; their weights the unbiased scores,
    renormalised and times ``cfg.moe_route_scale``.  No auxiliary
    loss."""
    logits = jnp.dot(xf.astype(jnp.float32), params["router"],
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + params["router_bias"], cfg.moe_topk)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.moe_route_scale
    return w, idx, jnp.zeros((), jnp.float32)


def _expert_ffn(buf, params):
    """buf: (E, C, d) -> (E, C, d) via per-expert SwiGLU."""
    g = jnp.einsum("ecd,edf->ecf", buf, params["w1"])
    u = jnp.einsum("ecd,edf->ecf", buf, params["w3"])
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, params["w2"])


def moe_dropless(x, params, cfg, engine: DotEngine):
    """The held experts' part of the layer for the tokens routed to
    them, no token dropped (scopes ``router``, ``dispatch``,
    ``experts/{gate,up,down}``, ``combine``): (y, aux, the router's
    choice of experts (T, k)).

    The T*k assignments to held experts are sorted by expert, and each
    expert's rows are padded to whole tiles of ``GROUP_ROWS``; the
    buffer is sized for the worst case, every assignment held, so its
    rows past the live tiles are never computed or read."""
    from repro.kernels.sfc_matmul import GROUP_ROWS as tile

    b, s, d = x.shape
    xf = x.reshape(-1, d)
    t, k = xf.shape[0], cfg.moe_topk
    held = params["w1"].shape[0]
    with jax.named_scope("router"):
        w, idx, aux = _router(xf, params, cfg)
    rows = -(-(t * min(k, held) + held * (tile - 1)) // tile) * tile
    with jax.named_scope("dispatch"):
        local = idx.reshape(-1) - cfg.moe_first_held
        mine = (local >= 0) & (local < held)
        group = jnp.where(mine, local, held)       # not held: a last group
        order = jnp.argsort(group, stable=True)
        counts = jnp.bincount(group, length=held + 1)[:held]
        tiles = -(-counts // tile)
        # an assignment's row: its place in the sorted order, moved by its
        # group's padded offset less its sorted one; the last group's
        # land past the buffer and are dropped
        shift = jnp.cumsum(tiles) * tile - tiles * tile \
            - (jnp.cumsum(counts) - counts)
        dest = jnp.arange(t * k) + jnp.append(shift, rows)[group[order]]
        token = jnp.zeros((rows,), jnp.int32).at[dest].set(
            (order // k).astype(jnp.int32), mode="drop")
        xs = xf[token]
    with jax.named_scope("experts"):
        with jax.named_scope("gate"):
            g = engine.dot_grouped(xs, params["w1"], tiles,
                                   activation="silu")
        with jax.named_scope("up"):
            u = engine.dot_grouped(xs, params["w3"], tiles)
        with jax.named_scope("down"):
            ys = engine.dot_grouped(g * u, params["w2"], tiles)
    with jax.named_scope("combine"):
        pos = jnp.zeros((t * k,), jnp.int32).at[order].set(dest)
        pos = jnp.minimum(pos, rows - 1).reshape(t, k)
        keep = mine.reshape(t, k)
        # rows never computed may hold anything: select, never scale
        y = jnp.where(keep[..., None], ys[pos].astype(jnp.float32)
                      * w[..., None], 0.0).sum(axis=1)
    return y.astype(x.dtype).reshape(b, s, d), aux, idx


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
            data_axes=("data",), model_axis="model", capacity=None,
            routes: bool = False):
    """The expert layer: the routed experts by ``impl`` ("auto": expert
    parallelism on a mesh, else dropless; or "dropless", "dense",
    "capacity", "ep"), plus the shared experts (scope ``shared``)
    where the config has them.  With ``routes``, also the router's
    choice of experts (T, k), from the dropless path only.

    Only the dropless path holds a share of the experts or runs the
    sigmoid router; the others refuse such a config."""
    if impl in ("auto", "ep"):
        impl = "ep" if mesh is not None else "dropless"
    if impl != "dropless" and (routes or cfg.moe_router != "softmax"
                               or cfg.held_experts != cfg.moe_experts
                               or cfg.moe_first_held):
        raise ValueError(
            f"the {impl!r} expert path takes the softmax router with every "
            f"expert held and gives no routes; this config needs the "
            f"dropless path (router {cfg.moe_router!r}, experts "
            f"{cfg.moe_first_held}..+{cfg.held_experts} of "
            f"{cfg.moe_experts})")
    idx = None
    if impl != "dropless":  # these read the experts' weights by XLA ops
        params = {k: weight(v) for k, v in params.items()}
    if impl == "dense":
        y, aux = moe_dense(x, params, cfg, engine)
    elif impl == "capacity":
        y, aux = moe_capacity(x, params, cfg, engine, capacity=capacity)
    elif impl == "ep":
        y, aux = moe_ep(x, params, cfg, mesh, engine,
                        data_axes=data_axes, model_axis=model_axis)
    else:
        y, aux, idx = moe_dropless(x, params, cfg, engine)
    if "shared" in params:
        with jax.named_scope("shared"):
            y = swiglu_mlp(x, params["shared"], engine, residual=y)
    return (y, aux, idx) if routes else (y, aux)
