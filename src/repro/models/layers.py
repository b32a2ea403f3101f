"""Common model layers (pure JAX, pytree params, scan-friendly).

All GEMMs route through :class:`DotEngine`, the integration point for the
paper's technique: the engine can execute matmuls through the SFC-scheduled
Pallas kernel (TPU) or XLA dot (CPU/default).  The engine is *static*
configuration -- it never enters pytrees.

A layer scan hands its body :func:`layer_params`: each weight matrix as
a :class:`LayerView` (the stacked array and the layer index), which the
engine's kernels read in place; a Pallas call needs its operand as a
buffer of its own, so a plain slice would be copied out on every
iteration.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

__all__ = ["DotEngine", "LayerView", "layer_params", "weight", "rms_norm",
           "layer_norm", "rope", "apply_rope", "swiglu_mlp", "init_linear",
           "init_rms", "Param"]

Param = Any  # pytree of arrays


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LayerView:
    """Layer ``index`` (an int32 scalar, traced in a scan) of ``stack``,
    an array stacked on a leading layer axis, left unsliced: the SFC
    kernels read the layer's blocks in place.  ``shape`` is the
    layer's."""
    stack: Any
    index: Any

    @property
    def shape(self):
        return self.stack.shape[1:]


def weight(w):
    """The plain array of ``w``: its layer's slice where it is a
    :class:`LayerView` (for XLA ops, which fuse the slice)."""
    return w.stack[w.index] if isinstance(w, LayerView) else w


def layer_params(stack, i):
    """Layer ``i`` of a pytree stacked on a leading layer axis: each
    leaf of two or more dims per layer (the weights the GEMMs read) as
    a :class:`LayerView`, each vector (norm gains, biases) its plain
    slice.  A matrix an XLA op reads (the router, a convolution) goes
    through :func:`weight`."""
    return jax.tree.map(
        lambda w: LayerView(w, i) if w.ndim >= 3 else w[i], stack)


def _unview(w):
    """(stack, layer): a view's stacked array and index, or (w, None)."""
    return (w.stack, w.index) if isinstance(w, LayerView) else (w, None)


@dataclasses.dataclass(frozen=True)
class DotEngine:
    """GEMM dispatcher.

    schedule: "xla" (native dot), an SFC schedule name executed by the
    Pallas kernel ("morton", "hilbert", "rowmajor", ...), or "auto" --
    the autotuner policy: every GEMM's (schedule, block sizes, prefetch,
    supertile factor) is resolved per shape bucket and epilogue through
    ``repro.tune.resolve`` (cached winners on disk, analytic cost model
    otherwise; DESIGN.md §6), here and nowhere below: the kernel
    wrappers take a concrete schedule.  "auto" may resolve to the XLA
    baseline where the model predicts the library wins -- the engine
    stays the single integration point either way.

    objective: the tuner's adjudication metric under schedule="auto" --
    "time" (default), "energy" (joules), or "edp" (energy-delay
    product); DESIGN.md §8.  Ignored for explicit schedules.  Under
    "energy"/"edp" the winner also carries a DVFS point
    (``TuneConfig.f_scale``): that never changes the kernel launch, but
    launch-layer telemetry reads it back via
    ``repro.tune.resolved_f_scale`` so J accounting runs at the
    frequency the objective selected.

    comm: the :class:`repro.tune.CommSpec` of the collective each GEMM's
    output feeds on a sharded mesh (DESIGN.md §15) -- the TP all-reduce
    ring size and the mean physical hop count of the mesh's curve
    embedding (:func:`repro.launch.mesh.link_distance`).  Only consulted
    under schedule="auto": winners are then scored with the hop-weighted
    bytes-over-links term and cached under the mesh keyspace.  None
    (default) keeps every single-chip cache key byte-identical.

    block: the kernel's (bm, bn, bk) for every GEMM, or None (default):
    each GEMM's block is then derived from its shape, dtypes and
    epilogue (:func:`repro.kernels.sfc_matmul.sfc_blocks`).  Ignored
    under "xla" and "auto" (the tuner picks blocks).
    """
    schedule: str = "xla"
    block: tuple | None = None
    use_prefetch: bool = True
    interpret: bool = False
    objective: str = "time"
    comm: Any = None  # repro.tune.CommSpec | None (hashable, frozen)

    def dot(self, x, w, *, bias=None, activation: str = "none",
            residual=None, out_dtype=None):
        """x: (..., d_in) @ w: (d_in, d_out) -> (..., d_out).

        ``bias`` (d_out,), ``activation`` and ``residual`` (same shape
        as the output) form the fused epilogue (DESIGN.md §9): on the
        Pallas path they ride the kernel's accumulator flush -- no
        post-matmul HBM round trips; on the XLA path the identical math
        runs as (library-fusable) elementwise ops.  ``out_dtype`` folds
        a dtype cast into the same single write (the vocab head's
        f32-logits cast).  ``w`` may be a :class:`LayerView`, which the
        kernel reads in place."""
        if self.schedule == "xla":
            w = weight(w)
            if bias is None and activation == "none" and residual is None:
                out = jnp.einsum("...d,df->...f", x, w)
                return out.astype(out_dtype) if out_dtype else out
            # epilogue present: accumulate in f32 like every other path
            # (matmul_fused_ref / the Pallas flush), so "identical math"
            # holds at bf16 too -- epilogue on the raw f32 product
            from repro.kernels.ref import apply_epilogue_ref
            acc = jnp.einsum("...d,df->...f", x, w,
                             preferred_element_type=jnp.float32)
            return apply_epilogue_ref(acc, bias, activation, residual,
                                      out_dtype or jnp.result_type(x, w))
        from repro.kernels.ops import sfc_matmul

        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        res2 = residual.reshape(-1, w.shape[-1]) \
            if residual is not None else None
        if self.schedule == "auto":
            kw = self._tuned(x2, w.shape[-1], bias, activation, residual)
        else:
            bm, bn, bk = self.block or (None, None, None)
            kw = dict(schedule=self.schedule, bm=bm, bn=bn, bk=bk,
                      use_prefetch=self.use_prefetch)
        stack, layer = _unview(w)
        out = sfc_matmul(
            x2, stack, **kw, interpret=self.interpret, out_dtype=out_dtype,
            bias=bias, activation=activation, residual=res2, layer=layer)
        return out.reshape(*lead, w.shape[-1])

    def _tuned(self, x2, n: int, bias, activation: str, residual) -> dict:
        """The tuner's winner for the GEMM x2 (M, K) @ (K, n), as
        ``sfc_matmul`` keywords.  Its key holds the GEMM's epilogue (a
        fused one moves the block optimum, DESIGN.md §9), ``comm`` and
        ``objective``.  The winner's DVFS point (``f_scale``) never
        reaches the kernel, as userspace cannot set the device clock;
        the launch layer reads it through ``repro.tune.resolved_f_scale``.
        Shapes are static, so this is safe at trace time."""
        from repro.tune import EpilogueSpec, GemmSpec, resolve

        ep = EpilogueSpec(bias=bias is not None, activation=activation,
                          residual=residual is not None)
        (m, k), dtype = x2.shape, jnp.dtype(x2.dtype).name
        cfg = resolve(GemmSpec(m, n, k, dtype,
                               epilogue=None if ep.is_noop else ep,
                               comm=self.comm), objective=self.objective)
        return dict(schedule=cfg.schedule, bm=cfg.bm, bn=cfg.bn, bk=cfg.bk,
                    use_prefetch=cfg.use_prefetch, g=cfg.g)

    def dot_grouped(self, x, w, group_tiles, *, activation: str = "none",
                    out_dtype=None):
        """Grouped GEMM of a routed expert layer: x (R, d_in), rows
        sorted by group and each group padded to whole tiles of
        :data:`repro.kernels.sfc_matmul.GROUP_ROWS` rows, group ``e``
        taking ``group_tiles[e]`` tiles, times its own w[e] (d_in,
        d_out) -> (R, d_out), ``activation`` fused.  Rows past the
        groups' tiles are undefined.  Under an SFC schedule on a TPU it
        is the grouped SFC kernel (``sfc_gmm_pallas``), whose blocks
        are derived from the shape (``block`` does not apply); under
        "xla" it is ``lax.ragged_dot``; "auto" raises ``ValueError``
        (the tuner has no grouped GEMM).  ``w`` may be a
        :class:`LayerView`, read in place."""
        from repro.kernels.ops import sfc_gmm

        stack, layer = _unview(w)
        return sfc_gmm(x, stack, group_tiles, schedule=self.schedule,
                       interpret=self.interpret, activation=activation,
                       out_dtype=out_dtype, layer=layer)


def init_linear(key, d_in: int, d_out: int, dtype=jnp.float32, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), dtype=jnp.float32)
            * scale).astype(dtype)


def init_rms(d: int, dtype=jnp.float32):
    return jnp.ones((d,), dtype=dtype)


def rms_norm(x, gamma, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dt) * gamma


def layer_norm(x, gamma, beta, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps)).astype(dt) * gamma + beta


def rope(positions, d_head: int, theta: float = 10000.0):
    """Rotary embedding tables: positions (...,) -> cos/sin (..., d_head/2)."""
    half = d_head // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (B, S, H, dh); cos/sin: (B, S, dh/2) or (S, dh/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:  # (S, half)
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:  # (B, S, half)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    xr1 = x1 * cos - x2 * sin
    xr2 = x2 * cos + x1 * sin
    return jnp.concatenate([xr1, xr2], axis=-1).astype(x.dtype)


def swiglu_mlp(x, params, engine: DotEngine, residual=None):
    """SwiGLU: w2(silu(w1 x) * w3 x). params: {w1, w3, w2}.

    The silu rides the up-projection's fused epilogue (applied to the
    f32 accumulator in-kernel on the Pallas path) and ``residual`` rides
    the down-projection's -- the layer's post-matmul elementwise HBM
    passes collapse into the GEMM flushes (DESIGN.md §9)."""
    with jax.named_scope("gate"):
        g = engine.dot(x, params["w1"], activation="silu")
    with jax.named_scope("up"):
        u = engine.dot(x, params["w3"])
    with jax.named_scope("down"):
        return engine.dot(g * u, params["w2"], residual=residual)


def init_swiglu(key, d: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": init_linear(k1, d, d_ff, dtype),
        "w3": init_linear(k2, d, d_ff, dtype),
        "w2": init_linear(k3, d_ff, d, dtype),
    }
