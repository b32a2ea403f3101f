"""Pure-jnp oracles for the SFC matmul kernels (+ the fused epilogue)
and the paged decode-attention kernel."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["matmul_ref", "matmul_blocked_ref", "ACTIVATIONS",
           "apply_activation", "apply_epilogue_ref", "matmul_fused_ref",
           "paged_decode_attention_ref"]

# epilogue activations the fused kernels support (DESIGN.md §9)
ACTIVATIONS = ("none", "relu", "gelu", "silu")


def apply_activation(x, activation: str):
    """Elementwise activation shared by the Pallas flush epilogue and the
    XLA fallback -- one definition so fused and unfused paths cannot
    drift (gelu is the tanh approximation in both)."""
    if activation == "none":
        return x
    if activation == "relu":
        return jnp.maximum(x, 0)
    if activation == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if activation == "silu":
        return jax.nn.silu(x)
    raise ValueError(
        f"unknown activation {activation!r}; choose from {ACTIVATIONS}")


def apply_epilogue_ref(acc, bias=None, activation: str = "none",
                       residual=None, out_dtype=None):
    """out = act(acc + bias) + residual, computed in f32, then one cast.

    ``acc`` is the f32 accumulator; this is the exact math the fused
    kernels apply at the ``k == kt-1`` flush, exposed as the oracle the
    property tests (and the XLA fallback) compare against.
    """
    acc = acc.astype(jnp.float32)
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    acc = apply_activation(acc, activation)
    if residual is not None:
        acc = acc + residual.astype(jnp.float32)
    return acc.astype(out_dtype) if out_dtype is not None else acc


def matmul_ref(a, b, out_dtype=None):
    """f32-accumulated matmul, the semantics every kernel must match."""
    out_dtype = out_dtype or a.dtype
    return jnp.dot(
        a, b, preferred_element_type=jnp.float32
    ).astype(out_dtype)


def matmul_fused_ref(a, b, bias=None, activation: str = "none",
                     residual=None, out_dtype=None):
    """dot -> bias -> activation -> residual -> cast, f32 throughout.

    The unfused composition the fused kernel must match bitwise-close,
    and the XLA fallback executed on non-TPU backends."""
    out_dtype = out_dtype or a.dtype
    acc = jnp.dot(a, b, preferred_element_type=jnp.float32)
    return apply_epilogue_ref(acc, bias, activation, residual, out_dtype)


def paged_decode_attention_ref(q, k_pages, v_pages, phys_tables, cur_pos):
    """Gather-then-softmax oracle for the paged decode-attention kernel
    (DESIGN.md §10) -- also the XLA fallback on non-TPU backends.

    q: (B, H, dh); k_pages/v_pages: (R, page_size, Hkv, dh) physical
    page pool whose *last row is the reserved zero row* (unallocated
    block-table entries point at it); phys_tables: (B, max_pages)
    physical row ids; cur_pos: newest valid position -- a scalar shared
    by every slot (lockstep decode) or a (B,) vector of per-slot
    positions (continuous batching, DESIGN.md §11).

    The math mirrors the contiguous ``_sdpa`` exactly -- f32 scores, a
    single direct softmax over the masked span, probabilities cast back
    to the value dtype -- so at f32 the paged and contiguous decode
    paths are bitwise-comparable, and the Pallas kernel's online
    rescaling agrees to ulp level.
    """
    b, h, dh = q.shape
    _, page_size, hkv, _ = k_pages.shape
    g = h // hkv
    max_pages = phys_tables.shape[1]
    span = max_pages * page_size
    k = k_pages[phys_tables].reshape(b, span, hkv, dh)
    v = v_pages[phys_tables].reshape(b, span, hkv, dh)
    pos = jnp.broadcast_to(
        jnp.asarray(cur_pos, jnp.int32).reshape(-1), (b,))
    valid = jnp.arange(span)[None, :] <= pos[:, None]        # (B, span)
    qg = q.reshape(b, hkv, g, dh)
    scores = jnp.einsum("bhgd,bkhd->bhgk", qg, k).astype(jnp.float32)
    scores = scores * (1.0 / math.sqrt(dh))
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgk,bkhd->bhgd", w, v)
    return out.reshape(b, h, dh)


def matmul_blocked_ref(a, b, bm: int, bn: int, bk: int, order, out_dtype=None):
    """Loop-nest oracle that accumulates block-by-block in the given output
    tile ``order`` -- proves the schedule does not change the result beyond
    f32 addition reordering (it must not: k-order is fixed per tile)."""
    out_dtype = out_dtype or a.dtype
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    kt = k // bk
    out = jnp.zeros((m, n), dtype=jnp.float32)
    for (i, j) in order:
        i, j = int(i), int(j)
        acc = jnp.zeros((bm, bn), dtype=jnp.float32)
        for kk in range(kt):
            ab = a[i * bm:(i + 1) * bm, kk * bk:(kk + 1) * bk]
            bb = b[kk * bk:(kk + 1) * bk, j * bn:(j + 1) * bn]
            acc += jnp.dot(ab, bb, preferred_element_type=jnp.float32)
        out = out.at[i * bm:(i + 1) * bm, j * bn:(j + 1) * bn].set(acc)
    return out.astype(out_dtype)
