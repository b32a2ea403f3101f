"""A configuration file's sizes, the program's config built to match
them, the weights made from the seed, and the operations and bytes of
the model's work, all computed from the file's shapes.

The config file uses the source's own key names, at the source's
values but for the cuts listed in ``reduced``.  Where the program
cannot run the published architecture, ``departures`` names the key,
its source value and the value as run; the harness and the reference
run the value as run.  ``program`` names the module under
``repro.configs`` that the system runs it with; the harness builds that
module's config with the file's depth and RoPE base and refuses a
program config whose widths differ from the file's, and a file that
asks for what the program lacks without stating the departure.
"""
from __future__ import annotations

import dataclasses

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

    # ------------------------------------------------ weights per layer --
    def layer_gemms(self) -> list[tuple[int, int]]:
        """(K, N) of every matmul of one layer."""
        d, q, kv = self.d, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        return [(d, q), (d, kv), (d, kv), (q, d),
                (d, self.d_ff), (d, self.d_ff), (self.d_ff, d)]

    def matmul_params(self, head: bool) -> int:
        n = self.layers * sum(k * n for k, n in self.layer_gemms())
        return n + (self.d * self.vocab if head else 0)

    # ---------------------------------------------------- operations ----
    def token_flops(self, context: int, head: bool) -> float:
        """Model FLOPs of one token attending over ``context`` keys:
        2 per matmul parameter (the embedding is a lookup, not a
        matmul), plus QK^T and PV over the context in every layer."""
        attn = 4.0 * self.layers * context * self.heads * self.head_dim
        return 2.0 * self.matmul_params(head) + attn

    def gemm_min_seconds(self, rows: int, head_rows: int, peak_flops: float,
                         bw: float, dtype_bytes: int = 2) -> float:
        """The least time the chip needs for one step's matmuls: every
        layer's over ``rows`` tokens and the head's over ``head_rows``;
        per matmul the larger of its FLOPs over the peak and its bytes
        (weights once, inputs and outputs once) over the bandwidth,
        summed."""
        total = 0.0
        for k, n in (self.layer_gemms() * self.layers if rows > 0 else []):
            flops = 2.0 * rows * k * n
            byts = dtype_bytes * (k * n + rows * k + rows * n)
            total += max(flops / peak_flops, byts / bw)
        if head_rows > 0:
            k, n = self.d, self.vocab
            flops = 2.0 * head_rows * k * n
            byts = dtype_bytes * (k * n + head_rows * k) + 4 * head_rows * n
            total += max(flops / peak_flops, byts / bw)
        return total


# the program's RMSNorm epsilon (repro.models.layers.rms_norm), fixed
PROGRAM_EPS = 1e-6


def as_run(model: dict, key: str):
    """The value of ``key`` as the benchmark runs it."""
    dep = model.get("departures", {}).get(key)
    return dep["run"] if dep is not None else model[key]


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
    import jax
    import jax.numpy as jnp

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

    seed = int(seed) % 2 ** 64
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return jax.jit(make)(key)


def check_layout(params, cfg) -> None:
    """Refuse weights whose tree, shapes or dtypes differ from what the
    program's own initialiser would make (shapes only: nothing runs)."""
    import jax

    from repro.models import init_model

    want = jax.eval_shape(lambda k: init_model(cfg, k), jax.random.key(0))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise SystemExit("the benchmark's weights do not match the "
                         "program's parameter layout")


