"""Per-kernel shape/dtype sweeps vs the pure-jnp oracle (interpret mode)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.schedule import grid_schedule
from repro.kernels.ops import sfc_matmul
from repro.kernels.ref import matmul_blocked_ref, matmul_ref
from repro.kernels.sfc_matmul import sfc_matmul_pallas


def _rand(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype=dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=1e-5, atol=1e-5)


SCHEDULES = ["rowmajor", "morton", "hilbert"]


@pytest.mark.parametrize("schedule", SCHEDULES)
@pytest.mark.parametrize("use_prefetch", [False, True])
def test_square_pow2_grids(schedule, use_prefetch):
    m = n = k = 64
    a = _rand((m, k), jnp.float32, 0)
    b = _rand((k, n), jnp.float32, 1)
    out = sfc_matmul_pallas(a, b, schedule=schedule, bm=16, bn=16, bk=16,
                            use_prefetch=use_prefetch, interpret=True)
    ref = matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_tol(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("schedule", SCHEDULES)
def test_dtype_sweep(schedule, dtype):
    a = _rand((64, 32), dtype, 2)
    b = _rand((32, 64), dtype, 3)
    out = sfc_matmul(a, b, schedule=schedule, bm=16, bn=16, bk=16,
                     interpret=True, force_pallas=True)
    ref = matmul_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        **_tol(dtype))


@pytest.mark.parametrize(
    "mnk", [(48, 16, 32), (16, 48, 16), (100, 36, 52), (8, 8, 8)]
)
@pytest.mark.parametrize("schedule", SCHEDULES)
def test_nonsquare_and_ragged_shapes(schedule, mnk):
    """Prefetch path handles non-square, non-pow2 grids; wrapper pads."""
    m, n, k = mnk
    a = _rand((m, k), jnp.float32, 4)
    b = _rand((k, n), jnp.float32, 5)
    out = sfc_matmul(a, b, schedule=schedule, bm=16, bn=16, bk=16,
                     interpret=True, force_pallas=True)
    assert out.shape == (m, n)
    ref = matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_tol(jnp.float32))


def test_out_dtype_override():
    a = _rand((32, 32), jnp.bfloat16, 6)
    b = _rand((32, 32), jnp.bfloat16, 7)
    out = sfc_matmul(a, b, schedule="morton", bm=16, bn=16, bk=16,
                     out_dtype=jnp.float32, interpret=True,
                     force_pallas=True)
    assert out.dtype == jnp.float32
    ref = matmul_ref(a, b, jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_blocked_ref_matches_dense_ref():
    """The schedule cannot change the result: k-order is fixed per tile."""
    a = _rand((32, 32), jnp.float32, 8)
    b = _rand((32, 32), jnp.float32, 9)
    for sched in SCHEDULES:
        order = grid_schedule(sched, 4, 4)
        blocked = matmul_blocked_ref(a, b, 8, 8, 8, order)
        np.testing.assert_allclose(np.asarray(blocked),
                                   np.asarray(matmul_ref(a, b)),
                                   rtol=1e-5, atol=1e-5)


def test_xla_schedule_fallback():
    a = _rand((33, 17), jnp.float32, 10)
    b = _rand((17, 29), jnp.float32, 11)
    out = sfc_matmul(a, b, schedule="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(matmul_ref(a, b)),
                               rtol=1e-6, atol=1e-6)


def test_cpu_fallback_without_interpret():
    """On CPU without interpret/force flags the wrapper must route to XLA."""
    a = _rand((32, 32), jnp.float32, 12)
    b = _rand((32, 32), jnp.float32, 13)
    out = sfc_matmul(a, b, schedule="morton")
    np.testing.assert_allclose(np.asarray(out), np.asarray(matmul_ref(a, b)),
                               rtol=1e-6, atol=1e-6)


def test_grad_through_wrapper():
    """XLA-fallback path is differentiable (models train on CPU)."""
    a = _rand((16, 16), jnp.float32, 14)
    b = _rand((16, 16), jnp.float32, 15)

    def loss(a, b):
        return jnp.sum(sfc_matmul(a, b, schedule="xla") ** 2)

    ga, gb = jax.grad(loss, argnums=(0, 1))(a, b)
    ga_ref = 2 * (a @ b) @ b.T
    np.testing.assert_allclose(np.asarray(ga), np.asarray(ga_ref),
                               rtol=1e-4, atol=1e-4)
    assert gb.shape == b.shape


def test_tpu_lowering_compiles():
    """The kernel must lower for the TPU target (structural check: trace +
    lower with a TPU-style mesh absent; we verify HLO contains custom-call).
    On a CPU container we can only check abstract lowering of the jitted
    wrapper in interpret mode compiles and runs; the real-TPU lowering is
    exercised by the dry-run."""
    a = _rand((32, 32), jnp.float32, 16)
    b = _rand((32, 32), jnp.float32, 17)
    fn = jax.jit(lambda a, b: sfc_matmul_pallas(
        a, b, schedule="morton", bm=16, bn=16, bk=16, interpret=True))
    txt = fn.lower(a, b).as_text()
    assert "custom_call" in txt or "pallas" in txt.lower()
    out = fn(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(matmul_ref(a, b)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("schedule", ["supertile", "boustrophedon",
                                      "colmajor"])
def test_prefetch_extended_schedules(schedule):
    """Schedule-table (scalar prefetch) path supports every schedule in
    repro.core.schedule, not just the closed-form decodable ones."""
    a = _rand((64, 48), jnp.float32, 20)
    b = _rand((48, 32), jnp.float32, 21)
    out = sfc_matmul(a, b, schedule=schedule, bm=16, bn=16, bk=16,
                     interpret=True, force_pallas=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(matmul_ref(a, b)),
                               rtol=1e-5, atol=1e-5)


def test_kernel_block_alignment_assertion():
    """Blocks must stay MXU-aligned through the wrapper's padding."""
    a = _rand((130, 70), jnp.float32, 22)
    b = _rand((70, 20), jnp.float32, 23)
    out = sfc_matmul(a, b, schedule="morton", bm=32, bn=32, bk=32,
                     interpret=True, force_pallas=True)
    assert out.shape == (130, 20)
    np.testing.assert_allclose(np.asarray(out), np.asarray(matmul_ref(a, b)),
                               rtol=1e-5, atol=1e-5)


def test_paper_cost_locality_tradeoff_end_to_end():
    """The paper's central object, end to end on the kernel: all schedules
    give identical results; their traffic differs (locality sim); their
    index cost differs (curves module) -- the trade is real and measured."""
    from repro.core.curves import hilbert_index_cost_ops, \
        morton_index_cost_ops
    from repro.core.locality import matmul_hbm_traffic
    from repro.core.schedule import grid_schedule

    a = _rand((64, 64), jnp.float32, 24)
    b = _rand((64, 64), jnp.float32, 25)
    outs = {}
    for s in ("rowmajor", "morton", "hilbert"):
        outs[s] = np.asarray(sfc_matmul(
            a, b, schedule=s, bm=16, bn=16, bk=16, interpret=True,
            force_pallas=True))
    np.testing.assert_array_equal(outs["rowmajor"], outs["morton"])
    np.testing.assert_array_equal(outs["morton"], outs["hilbert"])
    # locality ordering holds in the memory-bound regime (grid >> cache,
    # cache >= ~4 k-panels -- see test_locality.py for the regime map)
    traffic = {s: matmul_hbm_traffic(
        grid_schedule(s, 16, 16), 16, {"A": 1, "B": 1, "C": 1},
        model="lru", capacity=64)["misses"] for s in outs}
    assert traffic["hilbert"] <= traffic["morton"] <= traffic["rowmajor"]
    assert 2 < morton_index_cost_ops() < hilbert_index_cost_ops(16)


def test_peano_kernel_matches_ref():
    """Peano schedule through the scalar-prefetch kernel path."""
    a = _rand((48, 48), jnp.float32, 30)
    b = _rand((48, 48), jnp.float32, 31)
    out = sfc_matmul(a, b, schedule="peano", bm=16, bn=16, bk=16,
                     interpret=True, force_pallas=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(matmul_ref(a, b)),
                               rtol=1e-5, atol=1e-5)


# an epilogue per case of the stacked-weight test, and one with the
# walk decoded in the index map (no walk table in the scalar prefetch)
STACKED = {
    "none": {},
    "silu": {"activation": "silu"},
    "residual": {"residual": True},
    "f32_out": {"out_dtype": jnp.float32},
    "closed_form_walk": {"use_prefetch": False, "schedule": "rowmajor"},
    "hilbert_walk": {"schedule": "hilbert"},
    "supertile_walk": {"schedule": "supertile", "g": 2},
    # the paper's in-index-map decodes need a square power-of-two grid:
    # M 64 and N 512 give 4 x 4 tiles
    "closed_form_morton": {"use_prefetch": False, "schedule": "morton",
                           "m": 64, "n": 512},
    "closed_form_hilbert": {"use_prefetch": False, "schedule": "hilbert",
                            "m": 64, "n": 512},
}


@pytest.mark.parametrize("layer", [0, 2, 4])
@pytest.mark.parametrize("case", sorted(STACKED))
def test_stacked_weight_reads_its_layer_in_place(case, layer):
    """The kernel on a (L, K, N) stack and a traced layer index equals
    the kernel on that layer's slice, bit for bit, under each walk and
    index strategy; so do the wrapper's kernel path and its XLA
    fallback.  K 200 over blocks of 128 takes two k steps, the last
    masked; N 300 (the default) a cropped last block."""
    kw = dict(STACKED[case])
    m, n = kw.pop("m", 48), kw.pop("n", 300)
    res = _rand((m, n), jnp.bfloat16, 7) if kw.pop("residual", False) \
        else None
    a = _rand((m, 200), jnp.bfloat16, 5)
    w = _rand((5, 200, n), jnp.bfloat16, 6)
    blocks = dict(bm=16, bn=128, bk=128)

    def kernel(b, i=None):
        return sfc_matmul_pallas(a, b, interpret=True, residual=res,
                                 layer=i, **blocks, **kw)

    got = jax.jit(kernel)(w, jnp.int32(layer))
    assert np.array_equal(np.asarray(got), np.asarray(kernel(w[layer])))
    ep = {k: v for k, v in kw.items() if k in ("activation", "out_dtype")}
    for path in (dict(interpret=True, **blocks), {}):   # kernel, fallback
        got = jax.jit(lambda b, i: sfc_matmul(a, b, layer=i, residual=res,
                                              **ep, **path))(w, layer)
        want = sfc_matmul(a, w[layer], residual=res, **ep, **path)
        assert np.array_equal(np.asarray(got), np.asarray(want)), path
