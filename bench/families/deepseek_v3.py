"""The DeepSeek-V3 family (``model_type`` ``deepseek_v3``: Moonlight,
DeepSeek-V3): a configuration file's sizes, the program's config built
to match them, the weights made from the seed, the GEMMs of a forward,
and the plain reference.

The block: latent attention (MLA) with no q-LoRA; the first
``first_k_dense_replace`` layers with a dense SwiGLU, every later one an
expert layer: a router of sigmoid scores over every routed expert, the
top k chosen by score plus a correction bias (``noaux_tc``, one group),
their weights the unbiased scores renormalised and times
``routed_scaling_factor``; each expert a SwiGLU; shared experts as one
SwiGLU of their summed width.

A chip of the stated deployment holds ``n_routed_experts`` of each
layer's ``n_routed_experts_published``, from ``first_held_expert`` on
(``model-configs`` guide, section 4): the router keeps its published
width and top k, and the layer gives only its held experts' part of the
result, in the program and in the reference alike.

The reference runs one sequence in float32 at
``jax.default_matmul_precision("highest")``, layer by layer, written
from DeepSeek-V3's published modeling code, and imports nothing of the
program.  Its rotary embedding rotates adjacent pairs of the published
layout (``view(d/2, 2).transpose``, then rotate-half); the weights
(the program's layout) keep each rotary part's columns de-interleaved,
so the reference first puts them back in the published order.  Each
held expert runs over every token, masked by the choice.  ``mode="fp8"``
is the control, every matmul's operands rounded to float8 as in the
dense family.  ``reference_routed`` also gives each expert layer's own
choice and how near a tie it was, and runs the experts it is given in
its place (the ``score_routed`` loop's check).

The weights are seeded; the routers' correction bias is set from them
as training would leave it, balancing the load (``make_params``).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from harness.cell import family_module
from harness.model import Gemm, as_run, matmul_flops, seed_key

# the float32 matmul, norm, SwiGLU and rotate-half RoPE of the dense
# family
DENSE = family_module("dense")
F32, HIGHEST, Q_BLOCK, V_BLOCKS = DENSE.F32, DENSE.HIGHEST, DENSE.Q_BLOCK, \
    DENSE.V_BLOCKS

# the file's size -> its key in DeepSeek-V3's config.json
_KEYS = {
    "d": "hidden_size", "layers": "num_hidden_layers",
    "dense_layers": "first_k_dense_replace", "heads": "num_attention_heads",
    "kv_heads": "num_key_value_heads", "nope": "qk_nope_head_dim",
    "rope": "qk_rope_head_dim", "v": "v_head_dim",
    "kv_rank": "kv_lora_rank", "d_ff": "intermediate_size",
    "expert_ff": "moe_intermediate_size", "held": "n_routed_experts",
    "experts": "n_routed_experts_published",
    "first_held": "first_held_expert", "shared": "n_shared_experts",
    "topk": "num_experts_per_tok", "vocab": "vocab_size",
    "eps": "rms_norm_eps", "kv_eps": "kv_a_layernorm_eps",
    "rope_theta": "rope_theta", "route_scale": "routed_scaling_factor",
}
_FLOATS = ("eps", "kv_eps", "rope_theta", "route_scale")
# what the program runs; a file asking for another value is refused
_FIXED = {"q_lora_rank": None, "scoring_func": "sigmoid",
          "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
          "norm_topk_prob": True, "moe_layer_freq": 1,
          "tie_word_embeddings": False, "attention_bias": False,
          "hidden_act": "silu"}


@dataclasses.dataclass(frozen=True)
class Shapes:
    d: int
    layers: int
    dense_layers: int
    heads: int
    kv_heads: int
    nope: int
    rope: int
    v: int
    kv_rank: int
    d_ff: int
    expert_ff: int
    held: int
    experts: int
    first_held: int
    shared: int
    topk: int
    vocab: int
    eps: float
    kv_eps: float
    rope_theta: float
    route_scale: float

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // 128) * 128

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    def gemms(self) -> list[Gemm]:
        """Every GEMM of a forward, by the program's scope of it; each
        held expert's over the share of rows the router sends it."""
        d, h, n, m = self.d, self.heads, self.layers, self.moe_layers
        sff, ne = self.shared * self.expert_ff, m * self.held
        share = self.topk / self.experts
        return [Gemm("attn/q", d, h * (self.nope + self.rope), n),
                Gemm("attn/kv_a", d, self.kv_rank + self.rope, n),
                Gemm("attn/kv_b", self.kv_rank, h * (self.nope + self.v), n),
                Gemm("attn/o", h * self.v, d, n),
                Gemm("mlp/gate", d, self.d_ff, self.dense_layers),
                Gemm("mlp/up", d, self.d_ff, self.dense_layers),
                Gemm("mlp/down", self.d_ff, d, self.dense_layers),
                Gemm("moe/router", d, self.experts, m),
                Gemm("moe/shared/gate", d, sff, m),
                Gemm("moe/shared/up", d, sff, m),
                Gemm("moe/shared/down", sff, d, m),
                Gemm("moe/experts/gate", d, self.expert_ff, ne, share),
                Gemm("moe/experts/up", d, self.expert_ff, ne, share),
                Gemm("moe/experts/down", self.expert_ff, d, ne, share),
                Gemm("head", d, self.vocab, head=True)]

    def token_flops(self, context: float, head: bool) -> float:
        """Model FLOPs of one token attending over ``context`` keys: 2
        per matmul parameter it meets, plus QK^T over the q/k head
        width (plain and rotary) and PV over the value width."""
        attn = 2.0 * self.layers * context * self.heads \
            * (self.nope + self.rope + self.v)
        return matmul_flops(self.gemms(), head) + attn


def shapes(model: dict) -> Shapes:
    return Shapes(**{k: (float if k in _FLOATS else int)(as_run(model, key))
                     for k, key in _KEYS.items()})


def program_config(model: dict):
    """The program's ArchConfig for this file: the named module's
    config holding the file's share of the experts, checked against
    every width the file states; a file that asks for what the program
    does not run is refused."""
    from repro.configs import get_config, get_smoke_config
    from repro.models.attention import KV_A_NORM_EPS

    s = shapes(model)
    bad = {k: model.get(k) for k, v in _FIXED.items() if model.get(k) != v}
    if model.get("rope_scaling") or bad:
        raise SystemExit(f"the program runs none of {bad} "
                         f"{model.get('rope_scaling') or ''}")
    prog = model["program"]
    base = (get_smoke_config if prog.get("preset") == "smoke"
            else get_config)(prog["arch"])
    cfg = dataclasses.replace(base, moe_held=s.held,
                              moe_first_held=s.first_held)
    have = dict(d=cfg.d_model, layers=cfg.n_layers,
                dense_layers=cfg.moe_first_dense, heads=cfg.n_heads,
                kv_heads=cfg.n_kv_heads, nope=cfg.d_head,
                rope=cfg.qk_rope_dim, v=cfg.v_head_dim,
                kv_rank=cfg.kv_lora_rank, d_ff=cfg.d_ff,
                expert_ff=cfg.moe_dff, experts=cfg.moe_experts,
                shared_ff=cfg.moe_shared_dff, topk=cfg.moe_topk,
                vocab=cfg.vocab, eps=cfg.norm_eps, kv_eps=KV_A_NORM_EPS,
                rope_theta=cfg.rope_theta, route_scale=cfg.moe_route_scale,
                router=cfg.moe_router, dtype=cfg.param_dtype,
                act_dtype=cfg.act_dtype, family=cfg.family)
    want = dict(d=s.d, layers=s.layers, dense_layers=s.dense_layers,
                heads=s.heads, kv_heads=s.kv_heads, nope=s.nope,
                rope=s.rope, v=s.v, kv_rank=s.kv_rank, d_ff=s.d_ff,
                expert_ff=s.expert_ff, experts=s.experts,
                shared_ff=s.shared * s.expert_ff, topk=s.topk,
                vocab=s.vocab, eps=s.eps, kv_eps=s.kv_eps,
                rope_theta=s.rope_theta, route_scale=s.route_scale,
                router="sigmoid", dtype=model["torch_dtype"],
                act_dtype=model["torch_dtype"], family="moe")
    if have != want or not 0 <= s.first_held <= s.experts - s.held:
        bad = {k: (have[k], want[k]) for k in have if have[k] != want[k]}
        raise SystemExit(f"program config {prog['arch']} differs from the "
                         f"benchmark's file (program, file): {bad}, or "
                         f"the held experts lie outside the router's")
    return cfg


def make_params(s: Shapes, seed: int, model: dict):
    """Seeded weights in the program's parameter layout, made on the
    device in one jitted call, in the dtype they are run in: linear
    layers N(0, 1/fan_in), norm gains 1 + N(0, 0.01), the router in
    float32 as the program keeps it.  The layers are made one at a
    time.  The correction bias is then set layer by layer as
    ``noaux_tc`` training leaves it, balancing the load: on a seeded
    calibration corpus (``CAL_SEQS`` sequences of ``CAL_SEQ`` tokens)
    run through the reference, each layer's bias gives every routed
    expert the same share of the top-k choices (``balanced_bias``), so
    the held experts take held / experts of them whatever the seed."""
    dt = jnp.dtype(model["torch_dtype"])
    h, qd, vd = s.heads, s.nope + s.rope, s.v
    sff = s.shared * s.expert_ff

    def lin(k, *shape, dtype=dt):
        return (jax.random.normal(k, shape, jnp.float32)
                * shape[-2] ** -0.5).astype(dtype)

    def gain(k, n):
        return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)
                ).astype(dt)

    def attn(ks):
        return {"wq": lin(ks[0], s.d, h * qd),
                "wkv_a": lin(ks[1], s.d, s.kv_rank + s.rope),
                "kv_norm": gain(ks[2], s.kv_rank),
                "wkv_b": lin(ks[3], s.kv_rank, h * (s.nope + vd)),
                "wo": lin(ks[4], h * vd, s.d)}

    def swiglu(ks, ff):
        return {"w1": lin(ks[0], s.d, ff), "w3": lin(ks[1], s.d, ff),
                "w2": lin(ks[2], ff, s.d)}

    def dense_layer(k):
        ks = jax.random.split(k, 10)
        return {"norm1": gain(ks[0], s.d), "attn": attn(ks[1:6]),
                "norm2": gain(ks[6], s.d), "mlp": swiglu(ks[7:], s.d_ff)}

    def moe_layer(k):
        ks = jax.random.split(k, 15)
        e, ff = s.held, s.expert_ff
        return {"norm1": gain(ks[0], s.d), "attn": attn(ks[1:6]),
                "norm2": gain(ks[6], s.d),
                "moe": {"router": lin(ks[7], s.d, s.experts,
                                      dtype=jnp.float32),
                        "router_bias": jnp.zeros((s.experts,), jnp.float32),
                        "w1": lin(ks[9], e, s.d, ff),
                        "w3": lin(ks[10], e, s.d, ff),
                        "w2": lin(ks[11], e, ff, s.d),
                        "shared": swiglu(ks[12:], sff)}}

    def make(key):
        kd, kl, ke, kh, kn, kc = jax.random.split(key, 6)
        params = {"dense_layers": jax.lax.map(
                      dense_layer, jax.random.split(kd, s.dense_layers)),
                  "layers": jax.lax.map(moe_layer,
                                        jax.random.split(kl, s.moe_layers)),
                  "final_norm": gain(kn, s.d),
                  "embed": jax.random.normal(ke, (s.padded_vocab, s.d),
                                             jnp.float32).astype(dt),
                  "lm_head": lin(kh, s.d, s.padded_vocab)}
        return _with_balanced_bias(params, s, kc)

    return jax.jit(make)(seed_key(seed))


# the calibration corpus of the correction bias, CAL_SEQS sequences of
# CAL_SEQ tokens: within one sequence the routers' inputs share a large
# part (attention's average over the context), so the load a bias gives
# moves from sequence to sequence, and the bias is set over many; and the
# bias update: CAL_STEPS steps of DeepSeek-V3's (each expert's bias moved
# by u toward its share), u from CAL_U0 shrinking by CAL_DECAY a step
CAL_SEQS, CAL_SEQ = 16, 2048
CAL_STEPS, CAL_U0, CAL_DECAY = 400, 0.02, 0.98
FIRST_TOKEN_ID = 2   # ids 0 and 1 are pad and eos


def balanced_bias(scores, k):
    """A correction bias (E,) under which the top k of scores + bias
    (T, E) give every expert T * k / E of the choices, to within a few:
    DeepSeek-V3's update, every expert's bias raised by u where it
    takes fewer than its share and lowered where it takes more, with u
    shrinking."""
    t, e = scores.shape

    def step(i, b):
        _, idx = jax.lax.top_k(scores + b, k)
        load = jnp.zeros((e,), F32).at[idx.reshape(-1)].add(1.0)
        return b + CAL_U0 * CAL_DECAY ** i * jnp.sign(t * k / e - load)
    return jax.lax.fori_loop(0, CAL_STEPS, step, jnp.zeros((e,), F32))


def _with_balanced_bias(params, s, key):
    """The weights with each expert layer's correction bias balanced on
    a seeded calibration corpus, layer by layer: a layer's bias is set
    from its router's scores over every token of the corpus, then the
    layer runs with it (the reference, in float32, one sequence at a
    time), and the next layer sees what it gave."""
    tokens = jax.random.randint(key, (CAL_SEQS, CAL_SEQ), FIRST_TOKEN_ID,
                                s.vocab)
    pos = jnp.arange(CAL_SEQ)

    def layer(x, lp):                                   # x (seqs, L, d)
        x = x + jax.lax.map(lambda xs: _mla(DENSE._rms(xs, lp["norm1"], s.eps),
                                            lp["attn"], s, pos, "f32"), x)
        h = DENSE._rms(x, lp["norm2"], s.eps)
        scores = jax.nn.sigmoid(DENSE._mm(h.reshape(-1, s.d),
                                          lp["moe"]["router"], "f32"))
        bias = balanced_bias(scores, s.topk)
        moe = dict(lp["moe"], router_bias=bias)
        return x + jax.lax.map(lambda hs: _experts(hs, moe, s, "f32")[0],
                               h), bias

    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        x, _ = jax.lax.scan(
            lambda x, lp: (jax.lax.map(
                lambda xs: _dense_layer(xs, lp, s, pos, "f32"), x), None),
            x, params["dense_layers"])
        _, bias = jax.lax.scan(layer, x, params["layers"])
    layers = params["layers"]
    return dict(params, layers=dict(layers, moe=dict(layers["moe"],
                                                     router_bias=bias)))


# ------------------------------------------------------------ reference --
def _published(x):
    """A rotary part in the weights' de-interleaved order (the published
    even columns, then the odd ones) -> the published order."""
    half = x.shape[-1] // 2
    return jnp.stack([x[..., :half], x[..., half:]], -1).reshape(x.shape)


def _rope_pairs(x, pos, theta):
    """DeepSeek-V3's ``apply_rotary_pos_emb`` on x (L, H, r) in the
    published order: ``view(r/2, 2).transpose``, then rotate-half (the
    result stays in that order, for q and k alike)."""
    n, h, r = x.shape
    x = x.reshape(n, h, r // 2, 2).swapaxes(-1, -2).reshape(n, h, r)
    return DENSE._rope(x, pos, theta)


def _causal(q, k, v, mode):
    """softmax(q k^T / sqrt(dq)) v, causal, per head: q/k (L, H, dq), v
    (L, H, dv) -> (L, H, dv), in blocks of query rows."""
    n, h, dq = q.shape
    nb = n // Q_BLOCK
    kpos = jnp.arange(n)

    def block(args):
        i, qi = args
        sc = DENSE._ein("qhd,thd->hqt", qi, k, mode) / jnp.sqrt(F32(dq))
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        return DENSE._ein("hqt,thd->qhd", jax.nn.softmax(sc, -1), v, mode)

    out = jax.lax.map(block, (jnp.arange(nb), q.reshape(nb, Q_BLOCK, h, dq)))
    return out.reshape(n, h, v.shape[-1])


def _mla(x, a, s, pos, mode):
    """DeepSeek-V3's attention with no q-LoRA, on the normed x (L, d)."""
    n, h, mm = x.shape[0], s.heads, DENSE._mm
    q = mm(x, a["wq"], mode).reshape(n, h, s.nope + s.rope)
    kv = mm(x, a["wkv_a"], mode)
    c = DENSE._rms(kv[:, :s.kv_rank], a["kv_norm"], s.kv_eps)
    kvb = mm(c, a["wkv_b"], mode).reshape(n, h, s.nope + s.v)
    q_pe = _rope_pairs(_published(q[..., s.nope:]), pos, s.rope_theta)
    k_pe = _rope_pairs(_published(kv[:, None, s.kv_rank:]), pos,
                       s.rope_theta)
    q = jnp.concatenate([q[..., :s.nope], q_pe], -1)
    k = jnp.concatenate([kvb[..., :s.nope],
                         jnp.broadcast_to(k_pe, (n, h, s.rope))], -1)
    o = _causal(q, k, kvb[..., s.nope:], mode)
    return mm(o.reshape(n, -1), a["wo"], mode)


def _swiglu(h, m, mode):
    return DENSE.swiglu(h, m["w1"], m["w3"], m["w2"], mode)


def route(h, m, s, mode, forced=None):
    """The router on the normed h (L, d): (weights (L, k), the experts
    it runs (L, k), its own choice (L, k), the margin of that choice
    (L,)).  Its own choice is the top k of score + bias, and the margin
    the k-th biased score less the (k+1)-th; ``forced`` experts, where
    given, are run in its place, weighted by their own unbiased
    scores."""
    scores = jax.nn.sigmoid(DENSE._mm(h, m["router"], mode))
    top, own = jax.lax.top_k(scores + m["router_bias"], s.topk + 1)
    own = own[:, :s.topk]
    idx = own if forced is None else forced
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * s.route_scale
    return w, idx, own, top[:, s.topk - 1] - top[:, s.topk]


def _experts(h, m, s, mode, forced=None):
    """The held experts' part of the layer plus the shared experts, and
    the router's own choice and its margin."""
    w, idx, own, margin = route(h, m, s, mode, forced)
    held = s.first_held + jnp.arange(s.held)
    mix = ((idx[..., None] == held) * w[..., None]).sum(1)       # (L, held)
    every = jax.vmap(lambda w1, w3, w2: DENSE.swiglu(h, w1, w3, w2, mode))(
        m["w1"], m["w3"], m["w2"])                               # (held, L, d)
    y = jnp.einsum("le,eld->ld", mix, every, precision=HIGHEST) \
        + _swiglu(h, m["shared"], mode)
    return y, own, margin


def _dense_layer(x, lp, s, pos, mode):
    x = x + _mla(DENSE._rms(x, lp["norm1"], s.eps), lp["attn"], s, pos, mode)
    return x + _swiglu(DENSE._rms(x, lp["norm2"], s.eps), lp["mlp"], mode)


def _moe_layer(x, lp, s, pos, mode, forced=None):
    """An expert layer: (x, (its router's own choice, margin))."""
    x = x + _mla(DENSE._rms(x, lp["norm1"], s.eps), lp["attn"], s, pos, mode)
    y, own, margin = _experts(DENSE._rms(x, lp["norm2"], s.eps), lp["moe"],
                              s, mode, forced)
    return x + y, (own, margin)


def _hidden(params, tokens, s, mode, routes=None):
    """The residual stream after every layer, and each expert layer's
    own choice and margin (expert layers, L, k) and (expert layers, L);
    with ``routes`` (expert layers, L, k) the layers run those experts
    instead of their own choice."""
    pos = jnp.arange(tokens.shape[0])
    x = params["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(lambda x, lp: (_dense_layer(x, lp, s, pos, mode),
                                       None), x, params["dense_layers"])
    return jax.lax.scan(lambda x, a: _moe_layer(x, a[0], s, pos, mode, a[1]),
                        x, (params["layers"], routes))


@functools.partial(jax.jit, static_argnames=("s", "mode"))
def reference_routed(params, tokens, s, mode="f32", routes=None):
    """The next-token log-probabilities of ``tokens`` (L,), (L - 1,), in
    float32 or as the float8 control, with each expert layer's own
    choice of experts (expert layers, L, k) and its margin (expert
    layers, L).  Given ``routes`` (expert layers, L, k), the layers run
    those experts, as a program that chose them did: its own choice
    and margin then say whether that choice was the reference's."""
    with jax.default_matmul_precision("highest"):
        x, (own, margin) = _hidden(params, tokens, s, mode, routes)
        h = DENSE._rms(x[:-1], params["final_norm"], s.eps)
        nxt = tokens[1:]
        w = params["lm_head"][:, :s.vocab]
        width = -(-s.vocab // V_BLOCKS)
        lse = jnp.full(nxt.shape, -jnp.inf, F32)
        gold = jnp.zeros(nxt.shape, F32)
        for b in range(V_BLOCKS):
            lo, hi = b * width, min((b + 1) * width, s.vocab)
            logits = DENSE._mm(h, w[:, lo:hi], mode)
            lse = jnp.logaddexp(lse, jax.nn.logsumexp(logits, -1))
            idx = jnp.clip(nxt - lo, 0, hi - lo - 1)
            got = jnp.take_along_axis(logits, idx[:, None], -1)[:, 0]
            gold = jnp.where((nxt >= lo) & (nxt < hi), got, gold)
        return gold - lse, own, margin


def reference(params, tokens, s, mode="f32"):
    """The next-token log-probabilities of ``tokens`` (L,): (L - 1,),
    in float32 (``mode="f32"``) or as the float8 control (``"fp8"``),
    each expert layer running its own choice of experts."""
    return reference_routed(params, tokens, s, mode)[0]
