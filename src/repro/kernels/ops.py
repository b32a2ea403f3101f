"""Public jit'd wrappers around the SFC matmul kernels.

``sfc_matmul`` is the framework-wide GEMM entry point: every model matmul
can be routed through it (see ``repro.models.layers.DotEngine``).  On
non-TPU backends it falls back to XLA dot by default (the Pallas kernel is
TPU-targeted; ``interpret=True`` runs it on CPU for tests).

Both entry points carry the **fused epilogue** (DESIGN.md §9): optional
``bias=`` / ``activation=`` / ``residual=`` are applied to the kernel's
f32 accumulator inside the last-k flush -- one cast, one HBM write, no
post-matmul elementwise passes.  The XLA fallback reproduces the exact
same math (``repro.kernels.ref.matmul_fused_ref``), so callers never
branch on backend.

``schedule="auto"`` consults the autotuner (``repro.tune``, DESIGN.md §6):
the (shape-bucket, dtype, backend, epilogue) winner comes from the
on-disk cache when present, otherwise from the analytic cost model (plus
wall-time adjudication on real TPU hardware).  The epilogue is part of
the tuning key because fusion changes the traffic the candidate
generates -- and therefore which block sizes win.  Resolution uses only
static shape / dtype information, so it is safe at trace time.

``sfc_matmul_batched`` is the einsum-style ``bij,bjk->bik`` entry: any
number of leading batch dims, executed by a 3-D-grid Pallas kernel with
the SFC schedule on the (i, j) tile plane (or by ``vmap`` over the 2-D
kernel with ``via_vmap=True``).

``sfc_gmm`` is the grouped GEMM of a routed expert layer: rows sorted
by expert, each expert's rows padded to whole tiles of
:data:`~repro.kernels.sfc_matmul.GROUP_ROWS`, each times its expert's
weight, in one kernel (``sfc_gmm_pallas``) whose grid is sized for the
static bound on rows.  Each GEMM traced onto it adds its grid steps and
its row bound to the ``sfc_gmm.grid_steps`` / ``sfc_gmm.row_bound``
counters.

``use_prefetch`` defaults to ``True`` across the whole stack (kernels,
wrappers, engine): the scalar-prefetch schedule table works on any grid
and amortises index cost to zero.  ``False`` (the paper-faithful
in-``index_map`` decode) is an explicit opt-in everywhere.

Blocks: a block the caller names (``bm``/``bn``/``bk``, all three, or
the tuner's under ``schedule="auto"``) is run as named; a block left
``None`` is derived from the GEMM's shape, dtypes and epilogue
(:func:`repro.kernels.sfc_matmul.sfc_blocks`).  Either way the operands
go to the kernel as they are: a last block may overhang M, N or K, and
the kernel crops or masks it, so no operand is padded or copied.  Every
GEMM traced onto the kernel adds its grid steps and the steps that keep
a resident A or B block to the ``sfc.grid_steps`` /
``sfc.copies_elided`` counters of ``repro.obs``'s default registry
(host arithmetic while the jitted wrapper traces, so once per trace and
never per call).

``layer=`` (both ``sfc_matmul`` and ``sfc_gmm``): ``b`` is a weight
stacked on a leading layer axis and ``layer`` the traced index of the
GEMM's layer.  The kernel reads that layer in place (the index in its
scalar prefetch); the XLA fallback takes ``b[layer]``, which fuses into
its dot.  Blocks are derived from the layer's shape.  Each GEMM traced
onto a kernel with a stacked weight adds one to
``sfc.weights_in_place``, counted where the wrapper is called rather
than in its jitted body, which traces once per shape (so k and v, which
share one, count apart).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.obs.metrics import default_registry

from .ref import apply_epilogue_ref, matmul_batched_fused_ref, \
    matmul_fused_ref
from .sfc_matmul import GROUP_ROWS, grid_step_counts, sfc_blocks, \
    sfc_gmm_pallas, sfc_matmul_batched_pallas, sfc_matmul_pallas

__all__ = ["sfc_matmul", "sfc_matmul_batched", "sfc_gmm",
           "default_backend_is_tpu"]


def default_backend_is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _uses_kernel(schedule: str, interpret, force_pallas: bool) -> bool:
    """Whether the call runs the Pallas kernel: not the XLA baseline,
    and on a TPU, in interpret mode, or forced."""
    return schedule != "xla" and (
        force_pallas or bool(interpret) or default_backend_is_tpu())


def _resolve_blocks(m, n, k, a_dtype, out_dtype, bm, bn, bk, *,
                    has_bias: bool, has_residual: bool):
    """(bm, bn, bk): the named block, or the one derived from the shape
    where all three are ``None``."""
    if (bm, bn, bk) == (None, None, None):
        return sfc_blocks(m, n, k, jnp.dtype(a_dtype).itemsize,
                          jnp.dtype(out_dtype or a_dtype).itemsize,
                          bias=has_bias, residual=has_residual)
    if None in (bm, bn, bk):
        raise ValueError(f"name all of bm, bn, bk or none: {(bm, bn, bk)}")
    return bm, bn, bk


def _count_grid(schedule: str, m, n, k, bm, bn, bk, g, batch=1) -> None:
    """Add one traced kernel GEMM to the counters (called from the
    jitted wrappers' bodies, which run only while tracing)."""
    reg = default_registry()
    if not reg.enabled:
        return
    steps, elided = grid_step_counts(schedule, -(-m // bm), -(-n // bn),
                                     -(-k // bk), g, batch)
    reg.counter("sfc.grid_steps").inc(steps)
    reg.counter("sfc.copies_elided").inc(elided)


def _count_in_place(layer, schedule: str, interpret, force_pallas: bool
                     ) -> None:
    """Count a GEMM whose stacked weight a kernel reads in place (see
    the module docstring for why here and not in a jitted body)."""
    reg = default_registry()
    if reg.enabled and layer is not None and _uses_kernel(
            schedule, interpret, force_pallas):
        reg.counter("sfc.weights_in_place").inc()


def _resolve_auto(m: int, n: int, k: int, dtype, batched: bool = False,
                  objective: str = "time", has_bias: bool = False,
                  activation: str = "none", has_residual: bool = False,
                  comm=None):
    """Map schedule="auto" to a concrete (schedule, blocks, prefetch, g).

    The epilogue shape (bias / activation / residual presence) keys the
    tuner: a fused epilogue removes whole HBM passes from the traffic
    model, which moves the block-size optimum (DESIGN.md §9).  ``comm``
    (a :class:`repro.tune.CommSpec` or None) adds the mesh's collective
    term to the scoring and keys the winner under the mesh keyspace
    (DESIGN.md §15).

    The winner's DVFS dimension (``TuneConfig.f_scale``) is stripped
    here: it parameterises the tuner's scoring and the launch layer's
    energy accounting (``repro.tune.resolved_f_scale``), never the
    kernel launch -- userspace cannot set the device clock.

    Imported lazily: the tuner depends on this module for measurement."""
    from repro.tune import resolve_config
    from repro.tune.cost import EpilogueSpec

    ep = EpilogueSpec(bias=has_bias, activation=activation,
                      residual=has_residual)
    cfg = resolve_config(int(m), int(n), int(k), jnp.dtype(dtype).name,
                         batched=batched, objective=objective,
                         epilogue=None if ep.is_noop else ep, comm=comm)
    return cfg.schedule, cfg.bm, cfg.bn, cfg.bk, cfg.use_prefetch, cfg.g


@functools.partial(
    jax.jit,
    static_argnames=("schedule", "bm", "bn", "bk", "out_dtype",
                     "use_prefetch", "interpret", "force_pallas", "g",
                     "activation"),
)
def _sfc_matmul(
    a,
    b,
    *,
    schedule: str,
    bm: int,
    bn: int,
    bk: int,
    out_dtype,
    use_prefetch: bool,
    interpret: bool | None,
    force_pallas: bool,
    g: int,
    bias=None,
    activation: str = "none",
    residual=None,
    layer=None,
):
    out_dtype = out_dtype or a.dtype
    if not _uses_kernel(schedule, interpret, force_pallas):
        # CPU/GPU fallback for real execution paths; kernels are still
        # exercised on CPU via interpret=True in tests/benchmarks.  The
        # fused math is reproduced exactly (f32 epilogue, single cast);
        # a stacked weight's slice fuses into XLA's dot.
        return matmul_fused_ref(a, b if layer is None else b[layer],
                                bias=bias, activation=activation,
                                residual=residual, out_dtype=out_dtype)

    _count_grid(schedule, a.shape[0], b.shape[-1], a.shape[1], bm, bn, bk,
                g)
    return sfc_matmul_pallas(
        a, b, schedule=schedule, bm=bm, bn=bn, bk=bk,
        out_dtype=out_dtype, use_prefetch=use_prefetch,
        interpret=bool(interpret), g=g,
        bias=bias, activation=activation, residual=residual, layer=layer,
    )


def sfc_matmul(
    a,
    b,
    *,
    schedule: str = "morton",
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    out_dtype=None,
    use_prefetch: bool = True,
    interpret: bool | None = None,
    force_pallas: bool = False,
    g: int = 0,
    objective: str = "time",
    comm=None,
    bias=None,
    activation: str = "none",
    residual=None,
    layer=None,
):
    """C = act(A @ B + bias) + residual, tiles visited in ``schedule`` order.

    * ``bm``/``bn``/``bk``: a named block (all three) runs as named; a
      block left ``None`` is derived from the shape
      (:func:`repro.kernels.sfc_matmul.sfc_blocks`); neither pads an
      operand (the kernel crops or masks a block that overhangs);
    * ``bias`` (N,), ``activation`` in {none, relu, gelu, silu} and
      ``residual`` (M, N) form the fused epilogue: applied to the f32
      accumulator in the kernel's flush step, they cost zero extra HBM
      output traffic (DESIGN.md §9);
    * ``schedule="auto"`` resolves (schedule, block sizes, prefetch)
      through the autotuner's cache/cost model for this (shape bucket,
      epilogue), adjudicated under ``objective`` ("time", "energy" or
      "edp" -- DESIGN.md §8; ignored for explicit schedules);
    * ``schedule="xla"`` or a non-TPU backend (unless ``force_pallas``)
      uses the native XLA dot -- the "tuned library" baseline (ATLAS
      analogue in the paper's comparison) -- with the same epilogue math;
    * ``use_prefetch=True`` (default) amortises curve-index computation
      via scalar prefetch (beyond-paper; handles non-square grids),
      ``False`` decodes in ``index_map`` (paper-faithful trade of compute
      for locality);
    * ``layer`` (an int32 scalar): ``b`` is a stack (L, K, N) and the
      GEMM's weight its layer ``layer``, which the kernel reads in place
      (the index in its scalar prefetch) and the XLA path slices.
    """
    if schedule == "auto":
        schedule, bm, bn, bk, use_prefetch, g = _resolve_auto(
            a.shape[0], b.shape[-1], a.shape[1], a.dtype,
            objective=objective, has_bias=bias is not None,
            activation=activation, has_residual=residual is not None,
            comm=comm)
    bm, bn, bk = _resolve_blocks(
        a.shape[0], b.shape[-1], a.shape[1], a.dtype, out_dtype, bm, bn, bk,
        has_bias=bias is not None, has_residual=residual is not None)
    _count_in_place(layer, schedule, interpret, force_pallas)
    return _sfc_matmul(
        a, b, schedule=schedule, bm=bm, bn=bn, bk=bk,
        out_dtype=out_dtype, use_prefetch=use_prefetch, interpret=interpret,
        force_pallas=force_pallas, g=g,
        bias=bias, activation=activation, residual=residual, layer=layer)


@functools.partial(
    jax.jit,
    static_argnames=("schedule", "bm", "bn", "bk", "out_dtype",
                     "use_prefetch", "interpret", "force_pallas",
                     "via_vmap", "g", "activation"),
)
def _sfc_matmul_batched(
    a,
    b,
    *,
    schedule: str,
    bm: int,
    bn: int,
    bk: int,
    out_dtype,
    use_prefetch: bool,
    interpret: bool | None,
    force_pallas: bool,
    via_vmap: bool,
    g: int,
    bias=None,
    activation: str = "none",
    residual=None,
):
    out_dtype = out_dtype or a.dtype

    if not _uses_kernel(schedule, interpret, force_pallas):
        return matmul_batched_fused_ref(
            a, b, bias=bias, activation=activation, residual=residual,
            out_dtype=out_dtype)

    # flatten leading dims only on the kernel path: the XLA fallback above
    # consumes the original arrays (no dead reshapes on the fallback)
    lead = a.shape[:-2]
    m, k = a.shape[-2:]
    n = b.shape[-1]
    a3 = a.reshape((-1, m, k))
    b3 = b.reshape((-1, k, n))
    res3 = residual.reshape((-1, m, n)) if residual is not None else None
    _count_grid(schedule, m, n, k, bm, bn, bk, g, batch=a3.shape[0])

    if via_vmap:
        out = jax.vmap(
            lambda x, y, r: sfc_matmul_pallas(
                x, y, schedule=schedule, bm=bm, bn=bn, bk=bk,
                out_dtype=out_dtype, use_prefetch=use_prefetch,
                interpret=bool(interpret), g=g,
                bias=bias, activation=activation, residual=r),
            in_axes=(0, 0, 0 if res3 is not None else None),
        )(a3, b3, res3)
    else:
        out = sfc_matmul_batched_pallas(
            a3, b3, schedule=schedule, bm=bm, bn=bn, bk=bk,
            out_dtype=out_dtype, use_prefetch=use_prefetch,
            interpret=bool(interpret), g=g,
            bias=bias, activation=activation, residual=res3)
    return out.reshape(lead + (m, n))


def sfc_matmul_batched(
    a,
    b,
    *,
    schedule: str = "morton",
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    out_dtype=None,
    use_prefetch: bool = True,
    interpret: bool | None = None,
    force_pallas: bool = False,
    via_vmap: bool = False,
    g: int = 0,
    objective: str = "time",
    comm=None,
    bias=None,
    activation: str = "none",
    residual=None,
):
    """Einsum ``bij,bjk->bik`` with SFC tile traversal per batch element.

    ``a``: (..., M, K) and ``b``: (..., K, N) with identical leading
    dims; leading dims are flattened into one batch axis for the 3-D-grid
    kernel and restored on return.  ``bias`` (N,) is shared across batch
    elements; ``residual`` matches the (..., M, N) output -- both fused
    into the kernel flush (DESIGN.md §9).  Blocks as in
    :func:`sfc_matmul`, derived from the per-element GEMM's shape where
    left ``None``.  ``schedule="auto"`` consults
    the autotuner (keyed on the per-element GEMM shape + epilogue,
    adjudicated under ``objective``).  ``via_vmap=True`` runs the 2-D
    kernel under ``jax.vmap`` instead of the 3-D grid -- the two must
    agree (tested), and vmap is the fallback for callers that are
    themselves inside a ``vmap``.
    """
    assert a.shape[:-2] == b.shape[:-2], (a.shape, b.shape)
    assert a.shape[-1] == b.shape[-2], (a.shape, b.shape)
    if residual is not None:
        assert residual.shape == a.shape[:-1] + (b.shape[-1],), (
            residual.shape, a.shape, b.shape)
    if schedule == "auto":
        schedule, bm, bn, bk, use_prefetch, g = _resolve_auto(
            a.shape[-2], b.shape[-1], a.shape[-1], a.dtype, batched=True,
            objective=objective, has_bias=bias is not None,
            activation=activation, has_residual=residual is not None,
            comm=comm)
    bm, bn, bk = _resolve_blocks(
        a.shape[-2], b.shape[-1], a.shape[-1], a.dtype, out_dtype, bm, bn,
        bk, has_bias=bias is not None, has_residual=residual is not None)
    return _sfc_matmul_batched(
        a, b, schedule=schedule, bm=bm, bn=bn, bk=bk,
        out_dtype=out_dtype, use_prefetch=use_prefetch, interpret=interpret,
        force_pallas=force_pallas, via_vmap=via_vmap, g=g,
        bias=bias, activation=activation, residual=residual)


def gmm_ref(a, b, group_tiles, *, activation: str = "none", out_dtype=None):
    """The grouped GEMM in XLA (``lax.ragged_dot``): the same math as
    ``sfc_gmm``, rows past the groups' tiles zero."""
    acc = jax.lax.ragged_dot(a, b, group_tiles * GROUP_ROWS,
                             preferred_element_type=jnp.float32)
    return apply_epilogue_ref(acc, None, activation, None,
                              out_dtype or a.dtype)


def sfc_gmm(a, b, group_tiles, *, schedule: str = "morton",
            out_dtype=None, interpret: bool | None = None,
            force_pallas: bool = False, activation: str = "none",
            layer=None):
    """Grouped GEMM: ``a`` (R, K), rows sorted by group and each group
    padded to whole tiles of ``GROUP_ROWS`` rows, ``group_tiles[e]``
    tiles for group ``e``; ``b`` (G, K, N) -> (R, N), ``activation`` in
    the flush.  The column and K blocks are those :func:`sfc_blocks`
    derives for one row tile.  With ``layer``, ``b`` is a stack (L, G,
    K, N) whose layer ``layer`` the kernel reads in place.  Off the
    kernel path (the XLA baseline, or a CPU without
    ``interpret``/``force_pallas``) the same math runs as
    ``lax.ragged_dot``, on the layer's slice."""
    if schedule == "auto":
        raise ValueError("the tuner has no grouped GEMM: name a schedule")
    _count_in_place(layer, schedule, interpret, force_pallas)
    return _sfc_gmm(a, b, group_tiles, layer, schedule=schedule,
                    out_dtype=out_dtype, interpret=interpret,
                    force_pallas=force_pallas, activation=activation)


@functools.partial(
    jax.jit, static_argnames=("schedule", "out_dtype", "interpret",
                              "force_pallas", "activation"))
def _sfc_gmm(a, b, group_tiles, layer, *, schedule: str, out_dtype,
             interpret: bool | None, force_pallas: bool, activation: str):
    out_dtype = out_dtype or a.dtype
    if not _uses_kernel(schedule, interpret, force_pallas):
        return gmm_ref(a, b if layer is None else b[layer], group_tiles,
                       activation=activation, out_dtype=out_dtype)
    (r, k), n = a.shape, b.shape[-1]
    _, bn, bk = sfc_blocks(GROUP_ROWS, n, k, jnp.dtype(a.dtype).itemsize,
                           jnp.dtype(out_dtype).itemsize)
    reg = default_registry()
    if reg.enabled:
        reg.counter("sfc_gmm.grid_steps").inc(
            -(-r // GROUP_ROWS) * -(-n // bn) * -(-k // bk))
        reg.counter("sfc_gmm.row_bound").inc(r)
    return sfc_gmm_pallas(a, b, group_tiles, schedule=schedule, bn=bn, bk=bk,
                          out_dtype=out_dtype, interpret=bool(interpret),
                          activation=activation, layer=layer)
