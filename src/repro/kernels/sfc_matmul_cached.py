"""SFC matmul with an explicit software VMEM block cache.

The deepest TPU analogue of the paper's mechanism (DESIGN.md §2): the
Pallas pipeline's consecutive-equal elision is only a 1-step reuse window,
while the paper's CPU exploits a multi-line LRU.  Here the kernel manages
its own **direct-mapped block cache in VMEM scratch** (tags in SMEM,
explicit HBM->VMEM DMAs), so a schedule with good *temporal* locality --
Morton/Hilbert -- re-hits cached A/B panels across non-adjacent grid
steps, exactly like the paper's cache hits.

The kernel also emits a DMA counter, so the measured copy count can be
validated against ``repro.core.locality.simulate_direct`` -- the simulator
and the kernel agree block-for-block (tests/test_kernels_cached.py).

TPU notes: inputs live in ``pltpu.ANY`` (compiler-placed, HBM at these
sizes); slots are VMEM scratch; per-slot tags are SMEM scalars; copies use
``pltpu.make_async_copy`` with a DMA semaphore.  Validated in interpret
mode on CPU; the grid is ``(T, KT)`` with the schedule scalar-prefetched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.schedule import grid_schedule

__all__ = ["sfc_matmul_cached"]


def _kernel(sched_ref, a_hbm, b_hbm, o_ref, dma_count,
            a_slots, b_slots, a_tags, b_tags, acc, sem,
            *, kt: int, bm: int, bn: int, bk: int, nslots: int, out_dtype):
    t = pl.program_id(0)
    k = pl.program_id(1)
    i = sched_ref[t, 0]
    j = sched_ref[t, 1]

    @pl.when((t == 0) & (k == 0))
    def _init():
        for s in range(nslots):
            a_tags[s] = -1
            b_tags[s] = -1
        dma_count[0, 0] = 0
        dma_count[0, 1] = 0

    # ---- A block (i, k): direct-mapped on the block id ----
    a_id = i * kt + k
    a_slot = jax.lax.rem(a_id, nslots)

    @pl.when(a_tags[a_slot] != a_id)
    def _fetch_a():
        cp = pltpu.make_async_copy(
            a_hbm.at[pl.ds(i * bm, bm), pl.ds(k * bk, bk)],
            a_slots.at[a_slot], sem)
        cp.start()
        cp.wait()
        a_tags[a_slot] = a_id
        dma_count[0, 0] += 1

    # ---- B block (k, j) ----
    b_id = j * kt + k  # unique id per (k, j)
    b_slot = jax.lax.rem(b_id, nslots)

    @pl.when(b_tags[b_slot] != b_id)
    def _fetch_b():
        cp = pltpu.make_async_copy(
            b_hbm.at[pl.ds(k * bk, bk), pl.ds(j * bn, bn)],
            b_slots.at[b_slot], sem)
        cp.start()
        cp.wait()
        b_tags[b_slot] = b_id
        dma_count[0, 1] += 1

    @pl.when(k == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(a_slots[a_slot], b_slots[b_slot],
                        preferred_element_type=jnp.float32)

    @pl.when(k == kt - 1)
    def _flush():
        o_ref[...] = acc[...].astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("schedule", "bm", "bn", "bk", "nslots", "out_dtype",
                     "interpret"),
)
def sfc_matmul_cached(a, b, *, schedule: str = "morton", bm: int = 128,
                      bn: int = 128, bk: int = 128, nslots: int = 8,
                      out_dtype=None, interpret: bool = False):
    """C = A @ B through a ``nslots``-way software VMEM cache per operand.

    Returns (C, dma_counts) where dma_counts = [A copies, B copies] --
    the kernel-measured HBM traffic in blocks.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0
    mt, nt, kt = m // bm, n // bn, k // bk
    out_dtype = out_dtype or a.dtype
    sched = jnp.asarray(grid_schedule(schedule, mt, nt), jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(mt * nt, kt),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda t, kk, s: (s[t, 0], s[t, 1])),
            pl.BlockSpec((1, 2), lambda t, kk, s: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((nslots, bm, bk), a.dtype),
            pltpu.VMEM((nslots, bk, bn), b.dtype),
            pltpu.SMEM((nslots,), jnp.int32),
            pltpu.SMEM((nslots,), jnp.int32),
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    out, counts = pl.pallas_call(
        functools.partial(_kernel, kt=kt, bm=bm, bn=bn, bk=bk,
                          nslots=nslots, out_dtype=out_dtype),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m, n), out_dtype),
            jax.ShapeDtypeStruct((1, 2), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(sched, a, b)
    return out, counts[0]
