"""The plain reference: a dense decoder forward in float32, written from
the published description, that imports nothing of the program.

RMSNorm, RoPE (rotate-half), grouped-query attention with an exact
causal softmax, optional per-head q/k RMSNorm (Qwen3), SwiGLU, and the
output head.  No kernel, cache or batching: one sequence at a time over
its whole length, every matmul in float32 at
``jax.default_matmul_precision("highest")``.  It runs layer by layer
(a scan over the stacked weights, each layer's weights widened to
float32 only inside its step), attention in blocks of query rows, and
the head in blocks of the vocabulary, so that it fits beside the
program's bf16 weights.  It computes the configuration as the file runs
it: a departure the file states (``harness.model``) is the reference's
too.

``mode="fp8"`` is the control: the same forward with every matmul's
operands rounded to float8 (e4m3, scaled per row of activations and per
output column of weights, the accumulation in float32), the precision
one step below the configuration's bfloat16.

The weights are the benchmark's own (made from the seed), in the
layout ``{"layers": {...stacked...}, "final_norm", "embed",
"lm_head"}`` (a tied head is the embedding's transpose); sizes come
from the configuration file
(``harness.model.Shapes``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

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


def _layer(x, lp, s, pos, mode):
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
    x = x + _mm(o, a["wo"], mode)
    h = _rms(x, lp["norm2"], s.eps)
    m = lp["mlp"]
    return x + _mm(jax.nn.silu(_mm(h, m["w1"], mode)) * _mm(h, m["w3"], mode),
                   m["w2"], mode)


@functools.partial(jax.jit, static_argnames=("s", "mode"))
def next_token_logprobs(params, tokens, *, s, mode="f32"):
    """Forward ``tokens`` (L,) and return, at every position but the
    last, the log-probability of the token that follows: (L - 1,).  L
    must be a multiple of ``Q_BLOCK``."""
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        x = params["embed"][tokens].astype(F32)
        x, _ = jax.lax.scan(lambda x, lp: (_layer(x, lp, s, pos, mode), None),
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
