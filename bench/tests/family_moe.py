"""A mixture-of-experts decoder family, kept with the tests: the proof
that a family is files only (``test_family_files.py`` copies it into
``bench/families/moe.py`` of a copy of the benchmark, beside a
configuration, a mix, a check and a cell of its own).

Granite-3.0's block: the dense family's pre-norm attention, then a
token-choice MoE, softmax router scores, the top k renormalised, each
expert a SwiGLU; no shared expert, no tied head.  The reference runs
every expert over every token in float32 and mixes the chosen ones.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from harness.cell import family_module
from harness.model import PROGRAM_EPS, Gemm, as_run, matmul_flops, seed_key

# attention, norms, the head and the forward's frame, as the dense
# family runs them
DENSE = family_module("dense")

# source key -> the file's size (Granite-MoE's config.json names)
_KEYS = {
    "d": "hidden_size", "layers": "num_hidden_layers",
    "heads": "num_attention_heads", "kv_heads": "num_key_value_heads",
    "expert_ff": "intermediate_size", "experts": "num_local_experts",
    "topk": "num_experts_per_tok", "vocab": "vocab_size",
    "eps": "rms_norm_eps", "rope_theta": "rope_theta",
}


@dataclasses.dataclass(frozen=True)
class Shapes:
    d: int
    layers: int
    heads: int
    kv_heads: int
    expert_ff: int
    experts: int
    topk: int
    vocab: int
    eps: float
    rope_theta: float
    qk_norm: bool = False

    @property
    def head_dim(self) -> int:
        return self.d // self.heads

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // 128) * 128

    def gemms(self) -> list[Gemm]:
        d, q, kv, n = self.d, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim, self.layers
        ne, share = n * self.experts, self.topk / self.experts
        return [Gemm("attn/q", d, q, n), Gemm("attn/k", d, kv, n),
                Gemm("attn/v", d, kv, n), Gemm("attn/o", q, d, n),
                Gemm("moe/router", d, self.experts, n),
                Gemm("moe/experts/gate", d, self.expert_ff, ne, share),
                Gemm("moe/experts/up", d, self.expert_ff, ne, share),
                Gemm("moe/experts/down", self.expert_ff, d, ne, share),
                Gemm("head", d, self.vocab, head=True)]

    def token_flops(self, context: float, head: bool) -> float:
        attn = 4.0 * self.layers * context * self.heads * self.head_dim
        return matmul_flops(self.gemms(), head) + attn


def shapes(model: dict) -> Shapes:
    return Shapes(**{k: (float if k in ("eps", "rope_theta") else int)(
        as_run(model, key)) for k, key in _KEYS.items()})


def program_config(model: dict):
    from repro.configs import get_config, get_smoke_config

    s = shapes(model)
    prog = model["program"]
    base = (get_smoke_config if prog.get("preset") == "smoke"
            else get_config)(prog["arch"])
    cfg = dataclasses.replace(base, n_layers=s.layers,
                              rope_theta=s.rope_theta)
    have = dict(d=cfg.d_model, heads=cfg.n_heads, kv_heads=cfg.n_kv_heads,
                head_dim=cfg.d_head, expert_ff=cfg.moe_dff,
                experts=cfg.moe_experts, topk=cfg.moe_topk, vocab=cfg.vocab,
                qk_norm=cfg.qk_norm, dtype=cfg.param_dtype,
                act_dtype=cfg.act_dtype, family=cfg.family)
    want = dict(d=s.d, heads=s.heads, kv_heads=s.kv_heads,
                head_dim=s.head_dim, expert_ff=s.expert_ff,
                experts=s.experts, topk=s.topk, vocab=s.vocab,
                qk_norm=s.qk_norm, dtype=model["torch_dtype"],
                act_dtype=model["torch_dtype"], family="moe")
    if have != want:
        bad = {k: (have[k], want[k]) for k in have if have[k] != want[k]}
        raise SystemExit(f"program config {prog['arch']} differs from the "
                         f"benchmark's file (program, file): {bad}")
    if s.eps != PROGRAM_EPS or model.get("tie_word_embeddings"):
        raise SystemExit("the program cannot run the file's epsilon or a "
                         "tied head: state the departure under "
                         "'departures'")
    return cfg


def make_params(s: Shapes, seed: int, model: dict):
    dt = jnp.dtype(model["torch_dtype"])
    q, kv, e, ff = s.heads * s.head_dim, s.kv_heads * s.head_dim, \
        s.experts, s.expert_ff

    def lin(k, *shape, dtype=dt):
        return (jax.random.normal(k, shape, jnp.float32)
                * shape[-2] ** -0.5).astype(dtype)

    def gain(k, n):
        return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)
                ).astype(dt)

    def layer(k):
        ks = jax.random.split(k, 10)
        return {"norm1": gain(ks[0], s.d),
                "attn": {"wq": lin(ks[1], s.d, q), "wk": lin(ks[2], s.d, kv),
                         "wv": lin(ks[3], s.d, kv), "wo": lin(ks[4], q, s.d)},
                "norm2": gain(ks[5], s.d),
                # the program keeps its router in float32
                "moe": {"router": lin(ks[6], s.d, e, dtype=jnp.float32),
                        "w1": lin(ks[7], e, s.d, ff),
                        "w3": lin(ks[8], e, s.d, ff),
                        "w2": lin(ks[9], e, ff, s.d)}}

    def make(key):
        kl, ke, kh, kn = jax.random.split(key, 4)
        return {"layers": jax.lax.map(layer, jax.random.split(kl, s.layers)),
                "final_norm": gain(kn, s.d),
                "embed": jax.random.normal(ke, (s.padded_vocab, s.d),
                                           jnp.float32).astype(dt),
                "lm_head": lin(kh, s.d, s.padded_vocab)}

    return jax.jit(make)(seed_key(seed))


def _layer(x, lp, s, pos, mode):
    x = DENSE.attention_block(x, lp, s, pos, mode)
    h = DENSE._rms(x, lp["norm2"], s.eps)
    m = lp["moe"]
    probs = jax.nn.softmax(DENSE._mm(h, m["router"], mode), -1)
    w, idx = jax.lax.top_k(probs, s.topk)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    mix = (jax.nn.one_hot(idx, s.experts) * w[..., None]).sum(1)  # (L, E)
    every = jax.vmap(lambda w1, w3, w2: DENSE.swiglu(h, w1, w3, w2, mode))(
        m["w1"], m["w3"], m["w2"])                               # (E, L, d)
    return x + jnp.einsum("le,eld->ld", mix, every,
                          precision=DENSE.HIGHEST)


@functools.partial(jax.jit, static_argnames=("s", "mode"))
def reference(params, tokens, s, mode="f32"):
    return DENSE.logprobs(params, tokens, s, mode, _layer)
