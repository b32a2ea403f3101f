"""Pipeline parallelism (GPipe-style) over a mesh axis.

Off by default (TP x DP covers the assigned meshes; DESIGN.md §5) but
provided as a first-class utility for deeper-than-memory models at
1000+-node scale: layers are split into S stages along a mesh axis
(canonically "pod"), microbatches stream through with ppermute hand-offs,
and the bubble is the standard (S-1)/(S-1+M) fraction.

Forward-only building block (inference pipelines / activation servers);
training integration would pair it with the mirrored backward schedule.

    y = pipeline_apply(stage_fn, stage_params, x_mb, mesh, axis="pod")

* ``stage_params``: pytree whose leaves have leading dim L (stacked
  layers); split contiguously into S = mesh.shape[axis] stages.
* ``x_mb``: (M, mb, ...) microbatched input.
* ``stage_fn(stage_layers, x) -> y``: applies one stage's layers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply", "split_stages"]


def split_stages(layer_params, n_stages: int):
    """Reshape stacked-layer leaves (L, ...) -> (S, L/S, ...)."""
    def one(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape((n_stages, l // n_stages) + x.shape[1:])

    return jax.tree.map(one, layer_params)


def pipeline_apply(stage_fn, layer_params, x_mb, mesh, axis: str = "pod"):
    """GPipe forward: returns (M, mb, ...) outputs (replicated over axis).

    Schedule: T = M + S - 1 ticks; at tick t stage s runs microbatch
    t - s (if in range); activations hop s -> s+1 via ppermute.
    """
    s_count = mesh.shape[axis]
    m = x_mb.shape[0]
    stages = split_stages(layer_params, s_count)

    def local(stage_layers, mbs):
        # stage_layers: (1, L/S, ...) -> (L/S, ...); mbs replicated
        stage_layers = jax.tree.map(lambda x: x[0], stage_layers)
        sid = jax.lax.axis_index(axis)
        zero = jnp.zeros_like(mbs[0])

        def tick(carry, t):
            prev_out = carry
            recv = jax.lax.ppermute(
                prev_out, axis,
                [(i, i + 1) for i in range(s_count - 1)])
            mb_idx = t - sid
            x0 = jax.lax.dynamic_index_in_dim(
                mbs, jnp.clip(mb_idx, 0, m - 1), keepdims=False)
            x_in = jnp.where(sid == 0, x0, recv)
            active = (mb_idx >= 0) & (mb_idx < m)
            y = stage_fn(stage_layers, x_in)
            y = jnp.where(active, y, zero)
            out = jnp.where((sid == s_count - 1) & active, y, zero)
            return y, out

        _, outs = jax.lax.scan(tick, zero, jnp.arange(m + s_count - 1))
        # outputs of microbatch j leave the last stage at tick s-1+j
        outs = jax.lax.dynamic_slice_in_dim(outs, s_count - 1, m, axis=0)
        # only the last stage holds non-zero outputs: psum broadcasts
        outs = jax.lax.psum(outs, axis)
        return outs

    lspec = jax.tree.map(
        lambda x: P(*( (axis,) + (None,) * (x.ndim - 1) )), stages)
    # fully manual (all mesh axes): non-pipeline axes see replicated
    # inputs + deterministic compute, so results stay replicated
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(lspec, P()),
        out_specs=P(),
        check_vma=False,
    )(stages, x_mb)
