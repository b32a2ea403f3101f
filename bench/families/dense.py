"""The dense decoder family: a configuration file's sizes, the program's
config built to match them, the weights made from the seed, the GEMMs
of a forward, and the plain reference.

The config file uses the source's own key names, at the source's values
but for the cuts listed in ``reduced``.  ``program`` names the module
under ``repro.configs`` that the system runs it with; the family builds
that module's config with the file's depth and RoPE base and refuses a
program config whose widths differ from the file's, and a file that
asks for what the program lacks without stating the departure.

The reference is a dense decoder forward in float32, written from the
published description, that imports nothing of the program: RMSNorm,
RoPE (rotate-half), grouped-query attention with an exact causal
softmax, optional per-head q/k RMSNorm (Qwen3), SwiGLU, and the output
head.  No kernel, cache or batching: one sequence at a time over its
whole length, every matmul in float32 at
``jax.default_matmul_precision("highest")``.  It runs layer by layer (a
scan over the stacked weights, each layer's weights widened to float32
only inside its step), attention in blocks of query rows, and the head
in blocks of the vocabulary, so that it fits beside the program's bf16
weights.  It computes the configuration as the file runs it: a
departure the file states is the reference's too.

``mode="fp8"`` is the control: the same forward with every matmul's
operands rounded to float8 (e4m3, scaled per row of activations and per
output column of weights, the accumulation in float32), the precision
one step below the configuration's bfloat16.

The weights are the benchmark's own (made from the seed), in the layout
``{"layers": {...stacked...}, "final_norm", "embed", "lm_head"}`` (a
tied head is the embedding's transpose).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from harness.model import PROGRAM_EPS, Gemm, as_run, matmul_flops, seed_key

# source key -> the file's canonical size; a file gives one name of each
_KEYS = {
    "d": ("hidden_size",),
    "layers": ("num_hidden_layers", "num_layers"),
    "heads": ("num_attention_heads",),
    "kv_heads": ("num_key_value_heads", "multi_query_group_num"),
    "head_dim": ("head_dim", "kv_channels"),
    "d_ff": ("intermediate_size", "ffn_hidden_size"),
    "vocab": ("vocab_size", "padded_vocab_size"),
    "eps": ("rms_norm_eps", "layernorm_epsilon"),
    "rope_theta": ("rope_theta",),
}


@dataclasses.dataclass(frozen=True)
class Shapes:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    eps: float
    rope_theta: float
    qk_norm: bool

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // 128) * 128

    def gemms(self) -> list[Gemm]:
        """Every GEMM of a forward, by the program's scope of it."""
        d, q, kv, n = self.d, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim, self.layers
        return [Gemm("attn/q", d, q, n), Gemm("attn/k", d, kv, n),
                Gemm("attn/v", d, kv, n), Gemm("attn/o", q, d, n),
                Gemm("mlp/gate", d, self.d_ff, n),
                Gemm("mlp/up", d, self.d_ff, n),
                Gemm("mlp/down", self.d_ff, d, n),
                Gemm("head", d, self.vocab, head=True)]

    def token_flops(self, context: float, head: bool) -> float:
        """Model FLOPs of one token attending over ``context`` keys:
        2 per matmul parameter (the embedding is a lookup, not a
        matmul), plus QK^T and PV over the context in every layer."""
        attn = 4.0 * self.layers * context * self.heads * self.head_dim
        return matmul_flops(self.gemms(), head) + attn


def shapes(model: dict) -> Shapes:
    vals = {}
    for field, names in _KEYS.items():
        hit = [as_run(model, n) for n in names if n in model]
        if len(hit) != 1:
            raise ValueError(f"config needs exactly one of {names}")
        vals[field] = hit[0]
    return Shapes(qk_norm=bool(model.get("qk_norm", False)),
                  **{k: (float(v) if k in ("eps", "rope_theta") else int(v))
                     for k, v in vals.items()})


def program_config(model: dict):
    """The program's ArchConfig for this file: the named module's
    config at the file's depth and RoPE base, checked against every
    width the file states."""
    from repro.configs import get_config, get_smoke_config

    s = shapes(model)
    prog = model["program"]
    base = (get_smoke_config if prog.get("preset") == "smoke"
            else get_config)(prog["arch"])
    cfg = dataclasses.replace(base, n_layers=s.layers,
                              rope_theta=s.rope_theta)
    have = dict(d=cfg.d_model, heads=cfg.n_heads, kv_heads=cfg.n_kv_heads,
                head_dim=cfg.d_head, d_ff=cfg.d_ff, vocab=cfg.vocab,
                qk_norm=cfg.qk_norm, dtype=cfg.param_dtype,
                act_dtype=cfg.act_dtype, family=cfg.family)
    want = dict(d=s.d, heads=s.heads, kv_heads=s.kv_heads,
                head_dim=s.head_dim, d_ff=s.d_ff, vocab=s.vocab,
                qk_norm=s.qk_norm, dtype=model["torch_dtype"],
                act_dtype=model["torch_dtype"], family="dense")
    if have != want:
        bad = {k: (have[k], want[k]) for k in have if have[k] != want[k]}
        raise SystemExit(f"program config {prog['arch']} differs from the "
                         f"benchmark's file (program, file): {bad}")
    lacks = {"eps": s.eps != PROGRAM_EPS,
             "add_qkv_bias": bool(model.get("add_qkv_bias")
                                  and as_run(model, "add_qkv_bias")),
             "rotary_share_of_head": "rotary_share_of_head" in model
             and float(as_run(model, "rotary_share_of_head")) != 1.0}
    lacking = sorted(k for k, v in lacks.items() if v)
    if lacking:
        raise SystemExit(f"the program cannot run {lacking} as the file "
                         f"states: state the departure under 'departures'")
    return cfg


def make_params(s: Shapes, seed: int, model: dict):
    """Seeded weights in the program's parameter layout, made on the
    device in one jitted call, in the dtype they are run in.  Norm
    gains are drawn around 1 so that every norm is exercised.  The
    layers are made one at a time (``lax.map``) so that no float32 copy
    of a whole stacked weight exists.  Where the file ties the
    embeddings, the program's output head holds the embedding's
    transpose (drawn at the head's scale), so the two are one matrix."""
    dt = jnp.dtype(model["torch_dtype"])
    tied = bool(model.get("tie_word_embeddings"))
    v = s.padded_vocab

    def lin(k, din, dout):
        return (jax.random.normal(k, (din, dout), jnp.float32)
                * din ** -0.5).astype(dt)

    def gain(k, n):
        return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)
                ).astype(dt)

    def layer(k):
        ks = jax.random.split(k, 11)
        q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
        attn = {"wq": lin(ks[0], s.d, q), "wk": lin(ks[1], s.d, kv),
                "wv": lin(ks[2], s.d, kv), "wo": lin(ks[3], q, s.d)}
        if s.qk_norm:
            attn["q_norm"] = gain(ks[4], s.head_dim)
            attn["k_norm"] = gain(ks[5], s.head_dim)
        return {"norm1": gain(ks[6], s.d), "attn": attn,
                "norm2": gain(ks[7], s.d),
                "mlp": {"w1": lin(ks[8], s.d, s.d_ff),
                        "w3": lin(ks[9], s.d, s.d_ff),
                        "w2": lin(ks[10], s.d_ff, s.d)}}

    def make(key):
        kl, ke, kh, kn = jax.random.split(key, 4)
        head = lin(kh, s.d, v)
        embed = head.T if tied else \
            jax.random.normal(ke, (v, s.d), jnp.float32).astype(dt)
        return {"layers": jax.lax.map(layer, jax.random.split(kl, s.layers)),
                "final_norm": gain(kn, s.d), "embed": embed,
                "lm_head": head}

    return jax.jit(make)(seed_key(seed))


# ------------------------------------------------------------ reference --
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
Q_BLOCK = 256
V_BLOCKS = 8


def _fq(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``
    (``None``: one scale for the whole array)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(FP8).astype(F32) * scale


def _mm(x, w, mode):
    """x (..., K) f32 @ w (K, N) stored narrow, in float32."""
    w = w.astype(F32)
    if mode == "fp8":
        x, w = _fq(x, -1), _fq(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def _ein(eq, a, b, mode):
    if mode == "fp8":
        a, b = _fq(a, None), _fq(b, None)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(F32)


def _rope(x, pos, theta):
    """x (L, H, dh); rotate-half RoPE over the whole head."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(0, half, dtype=F32) * 2.0 / x.shape[-1])
    ang = pos[:, None].astype(F32) * inv                       # (L, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, mode):
    """Causal GQA: q (L, H, dh), k/v (L, Hkv, dh) -> (L, H, dh); query
    head h reads kv head h // (H / Hkv)."""
    n, h, dh = q.shape
    hkv = k.shape[1]
    g = h // hkv
    nb = n // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, hkv, g, dh)
    kpos = jnp.arange(n)

    def block(args):
        i, qi = args
        s = _ein("qkgd,tkd->kgqt", qi, k, mode) / jnp.sqrt(F32(dh))
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _ein("kgqt,tkd->qkgd", p, v, mode)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.reshape(n, h, dh)


def attention_block(x, lp, s, pos, mode):
    """x + the attention sub-block of one layer (pre-norm)."""
    a = lp["attn"]
    h = _rms(x, lp["norm1"], s.eps)
    n = x.shape[0]
    q = _mm(h, a["wq"], mode).reshape(n, s.heads, s.head_dim)
    k = _mm(h, a["wk"], mode).reshape(n, s.kv_heads, s.head_dim)
    v = _mm(h, a["wv"], mode).reshape(n, s.kv_heads, s.head_dim)
    if s.qk_norm:
        q = _rms(q, a["q_norm"], s.eps)
        k = _rms(k, a["k_norm"], s.eps)
    q, k = _rope(q, pos, s.rope_theta), _rope(k, pos, s.rope_theta)
    o = _attention(q, k, v, mode).reshape(n, -1)
    return x + _mm(o, a["wo"], mode)


def swiglu(h, w1, w3, w2, mode):
    return _mm(jax.nn.silu(_mm(h, w1, mode)) * _mm(h, w3, mode), w2, mode)


def _layer(x, lp, s, pos, mode):
    x = attention_block(x, lp, s, pos, mode)
    h = _rms(x, lp["norm2"], s.eps)
    m = lp["mlp"]
    return x + swiglu(h, m["w1"], m["w3"], m["w2"], mode)


def logprobs(params, tokens, s, mode, layer):
    """Forward ``tokens`` (L,) through ``layer`` (x, layer weights, s,
    positions, mode) -> x, over the stacked layers, and return at every
    position but the last the log-probability of the token that
    follows: (L - 1,).  L must be a multiple of ``Q_BLOCK``."""
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        x = params["embed"][tokens].astype(F32)
        x, _ = jax.lax.scan(lambda x, lp: (layer(x, lp, s, pos, mode), None),
                            x, params["layers"])
        h = _rms(x[:-1], params["final_norm"], s.eps)          # (L-1, d)
        nxt = tokens[1:]
        w = params["lm_head"][:, :s.vocab]
        width = -(-s.vocab // V_BLOCKS)
        lse = jnp.full(nxt.shape, -jnp.inf, F32)
        gold = jnp.zeros(nxt.shape, F32)
        for b in range(V_BLOCKS):
            lo, hi = b * width, min((b + 1) * width, s.vocab)
            logits = _mm(h, w[:, lo:hi], mode)                 # (L-1, hi-lo)
            lse = jnp.logaddexp(lse, jax.nn.logsumexp(logits, -1))
            idx = jnp.clip(nxt - lo, 0, hi - lo - 1)
            got = jnp.take_along_axis(logits, idx[:, None], -1)[:, 0]
            gold = jnp.where((nxt >= lo) & (nxt < hi), got, gold)
        return gold - lse


@functools.partial(jax.jit, static_argnames=("s", "mode"))
def reference(params, tokens, s, mode="f32"):
    """The next-token log-probabilities of ``tokens`` (L,): (L - 1,),
    in float32 (``mode="f32"``) or as the float8 control (``"fp8"``)."""
    return logprobs(params, tokens, s, mode, _layer)
