"""Mamba2 SSD (state-space duality) mixer [arXiv:2405.21060].

Chunked SSD form: within a chunk the recurrence is computed as a masked
(quadratic-in-chunk) matmul -- MXU friendly -- while states are passed
between chunks by a (associative-scannable) linear recurrence:

    h_c = (prod decay_c) * h_{c-1} + sum_j decay_{j->end} * dt_j B_j x_j^T
    y_i = C_i h_{c-1} * decay_{0->i}  +  intra-chunk term  +  D * x_i

Decode is the O(1) recurrent update.  Single B/C group (G=1), per-head
scalar decay a = -exp(A_log), softplus dt -- the standard Mamba2 setup.
A short causal depthwise conv precedes x/B/C as in the reference model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import DotEngine, init_linear, init_rms, rms_norm, weight

__all__ = ["init_ssm", "ssd_forward", "ssm_decode", "ssm_state_shape"]

CONV_K = 4  # depthwise conv kernel width


def _dims(cfg):
    d_inner = cfg.ssm_heads * cfg.ssm_head_dim
    conv_dim = d_inner + 2 * cfg.ssm_state  # x, B, C
    return d_inner, conv_dim


def init_ssm(key, cfg, dtype=jnp.float32):
    d = cfg.d_model
    d_inner, conv_dim = _dims(cfg)
    h = cfg.ssm_heads
    ks = jax.random.split(key, 4)
    # in_proj emits [z (gate), x, B, C, dt]
    proj_out = d_inner + conv_dim + h
    p = {
        "in_proj": init_linear(ks[0], d, proj_out, dtype),
        "out_proj": init_linear(ks[1], d_inner, d, dtype),
        "conv_w": (jax.random.normal(ks[2], (CONV_K, conv_dim)) * 0.1
                   ).astype(dtype),
        "A_log": jnp.zeros((h,), jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "norm": init_rms(d_inner, dtype),
    }
    return p


def ssm_state_shape(cfg, batch: int):
    return {
        "h": (batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
        "conv": (batch, CONV_K - 1, _dims(cfg)[1]),
    }


def _split_proj(proj, cfg):
    d_inner, _ = _dims(cfg)
    n = cfg.ssm_state
    z, xbc, dt = jnp.split(proj, [d_inner, 2 * d_inner + 2 * n], axis=-1)
    return z, xbc, dt


def _causal_conv(xbc, conv_w):
    """Depthwise causal conv along seq: xbc (B,S,C), conv_w (K,C)."""
    k = conv_w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1], :] * conv_w[i] for i in range(k))
    return jax.nn.silu(out)


def ssd_forward(x, p, cfg, engine: DotEngine, chunk: int = 128):
    """x: (B, S, d) -> (B, S, d).  Chunked SSD scan."""
    b, s, _ = x.shape
    h, ph, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    d_inner, _ = _dims(cfg)
    c = min(chunk, s)
    assert s % c == 0, (s, c)
    nc = s // c

    proj = engine.dot(x, p["in_proj"])
    z, xbc, dt = _split_proj(proj, cfg)
    xbc = _causal_conv(xbc, weight(p["conv_w"]))
    xs, bs, cs = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)
    xs = xs.reshape(b, s, h, ph)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    a = -jnp.exp(p["A_log"])                                      # (H,)
    logdec = dt * a                                               # (B,S,H) <=0

    # chunked views -- chunk dim (NC) sequence-sharded over "model": the
    # intra-chunk einsums are embarrassingly parallel over chunks, and the
    # inter-chunk recurrence below is an associative scan (log-depth), so
    # the whole SSD block partitions instead of replicating (DESIGN §5)
    from repro.distributed.ctx import constrain
    xs_c = constrain(xs.reshape(b, nc, c, h, ph),
                     "dp", "model", None, None, None)
    bs_c = constrain(bs.reshape(b, nc, c, n).astype(jnp.float32),
                     "dp", "model", None, None)
    cs_c = constrain(cs.reshape(b, nc, c, n).astype(jnp.float32),
                     "dp", "model", None, None)
    dt_c = constrain(dt.reshape(b, nc, c, h), "dp", "model", None, None)
    ld_c = constrain(logdec.reshape(b, nc, c, h),
                     "dp", "model", None, None)
    cum = jnp.cumsum(ld_c, axis=2)                       # (B,NC,C,H)
    total = cum[:, :, -1, :]                             # (B,NC,H)

    # ---- intra-chunk (quadratic within chunk, matmul-friendly) ----------
    # att[h,i,j] = (C_i . B_j) * exp(cum_i - cum_j) for i >= j (per head):
    # the single (B,NC,H,C,C) "attention" buffer of the reference SSD.
    cum_h = jnp.moveaxis(cum, -1, 2)                     # (B,NC,H,C)
    ldiff = cum_h[..., :, None] - cum_h[..., None, :]    # (B,NC,H,C,C)
    mask = jnp.tril(jnp.ones((c, c), bool))
    lmat = jnp.where(mask, jnp.exp(ldiff), 0.0)
    cb = jnp.einsum("bgin,bgjn->bgij", cs_c, bs_c)       # (B,NC,C,C)
    # the (B,NC,H,C,C) buffer is the SSD memory hot-spot: store it bf16
    # (values in [0,1]*cb), accumulate the einsum in f32
    att = (cb[:, :, None] * lmat).astype(jnp.bfloat16)   # (B,NC,H,C,C)
    xdt = xs_c.astype(jnp.float32) * dt_c[..., None]     # (B,NC,C,H,P)
    y_intra = jnp.einsum("bghij,bgjhp->bgihp", att,
                         xdt.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)

    # ---- chunk states + inter-chunk recurrence ---------------------------
    # state contribution of chunk g: sum_j exp(total - cum_j) * B_j xdt_j
    decay_to_end = jnp.exp(total[:, :, None, :] - cum)   # (B,NC,C,H)
    sb = jnp.einsum("bgjh,bgjn,bgjhp->bghpn",
                    decay_to_end, bs_c, xdt)             # (B,NC,H,P,N)

    # inter-chunk recurrence h_c = a_c h_{c-1} + sb_c as an associative
    # scan over the chunk dim: log-depth instead of NC sequential steps
    # (the Mamba/S5 parallel-scan trick), and it shards over "model".
    a_c = jnp.exp(total)                                 # (B,NC,H)

    def combine(left, right):
        a1, b1 = left
        a2, b2 = right
        return a1 * a2, b1 * a2[..., None, None] + b2

    hs_a, hs_b = jax.lax.associative_scan(
        combine, (a_c, sb), axis=1)                      # inclusive scan
    del hs_a
    hprevs = jnp.concatenate(
        [jnp.zeros_like(hs_b[:, :1]), hs_b[:, :-1]], axis=1)  # exclusive

    # y_inter[i] = exp(cum_i) * C_i . h_prev
    y_inter = jnp.einsum("bgin,bghpn->bgihp", cs_c, hprevs) \
        * jnp.exp(cum)[..., None]

    y = (y_intra + y_inter).reshape(b, s, h, ph)
    y = y + p["D"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(b, s, d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"])
    return engine.dot(y, p["out_proj"])


def ssm_decode(x, p, cfg, engine: DotEngine, state, row_mask=None):
    """One-token recurrent decode.  x: (B, 1, d); state: {"h", "conv"}.
    ``row_mask`` (B,) bool: masked rows keep their previous state."""
    b = x.shape[0]
    h, ph, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    d_inner, conv_dim = _dims(cfg)

    proj = engine.dot(x, p["in_proj"])[:, 0]             # (B, proj)
    z, xbc, dt = _split_proj(proj, cfg)
    # conv over ring of last K-1 inputs + current
    conv_in = jnp.concatenate([state["conv"], xbc[:, None, :]], axis=1)
    xbc = jax.nn.silu(jnp.einsum("bkc,kc->bc", conv_in, p["conv_w"]))
    new_conv = conv_in[:, 1:, :]
    xs, bs, cs = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)
    xs = xs.reshape(b, h, ph).astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,H)
    dec = jnp.exp(dt * (-jnp.exp(p["A_log"])))           # (B,H)
    bx = jnp.einsum("bhp,bn->bhpn", xs * dt[..., None],
                    bs.astype(jnp.float32))
    h_new = state["h"] * dec[..., None, None] + bx
    y = jnp.einsum("bn,bhpn->bhp", cs.astype(jnp.float32), h_new)
    y = y + p["D"][None, :, None] * xs
    y = y.reshape(b, d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"])
    out = engine.dot(y, p["out_proj"])[:, None, :]
    if row_mask is not None:
        h_new = jnp.where(row_mask[:, None, None, None], h_new, state["h"])
        new_conv = jnp.where(row_mask[:, None, None], new_conv,
                             state["conv"])
    return out, {"h": h_new, "conv": new_conv}
