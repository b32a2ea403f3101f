"""The SFC kernel's shape-derived blocks (``kernels/sfc_matmul.sfc_blocks``).

Host-only checks of the rule at every GEMM of the benchmark cells'
configurations (a 2,048-row window and a 16-slot decode step), the
grid-step counters against the block access trace of
``core/schedule``, and interpret-mode parity of the derived blocks with
``kernels/ref.matmul_fused_ref`` on small shapes.
"""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _gemms import cell_gemm_cases, forward_gemms
from repro.core.schedule import grid_schedule, matmul_block_trace
from repro.kernels.ops import _resolve_blocks, sfc_matmul
from repro.kernels.ref import matmul_fused_ref
from repro.obs.metrics import default_registry

# the module (the package's ``sfc_matmul`` attribute is the wrapper)
K = importlib.import_module("repro.kernels.sfc_matmul")


def _out_bytes(ep) -> int:
    return jnp.dtype(ep.get("out_dtype", jnp.bfloat16)).itemsize


def _derived(n, k, m, ep):
    return K.sfc_blocks(m, n, k, 2, _out_bytes(ep),
                        bias=bool(ep.get("bias")),
                        residual=bool(ep.get("residual")))


@pytest.mark.parametrize("arch,role,m", cell_gemm_cases())
def test_rule_at_the_cells_gemms(arch, role, m):
    n, k, ep = forward_gemms(arch)[role]
    bm, bn, bk = _derived(n, k, m, ep)
    # alignment: lanes of 128, bf16 sublane tiles of 16, M covered
    # without padding it past one tile
    assert bn % 128 == 0 and bk % 128 == 0 and bm % 16 == 0
    assert bm <= K.BM_MAX and bm < m + 16
    assert bn in [min(b, -(-n // 128) * 128) for b in K.BN_STEPS]
    # the double-buffered working set fits the rule's budget, and the
    # kernel raises the compiler's limit to hold it wherever needed
    kt, k_tail = -(-k // bk), k % bk
    need = K.sfc_vmem_bytes(bm, bn, bk, 2, _out_bytes(ep),
                            residual=bool(ep.get("residual")), kt=kt,
                            k_tail=k_tail)
    assert need <= K.BLOCK_VMEM_BUDGET
    limit = K._vmem_limit(need)
    assert (limit or K.SCOPED_VMEM_BYTES) >= need + K.VMEM_HEADROOM_BYTES
    # the wrapper derives the same block
    assert _resolve_blocks(
        m, n, k, jnp.bfloat16, ep.get("out_dtype"), None, None, None,
        has_bias=False, has_residual=bool(ep.get("residual"))) \
        == (bm, bn, bk)
    # and the traced wrapper pads no operand at this shape
    a = jax.ShapeDtypeStruct((m, k), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((k, n), jnp.bfloat16)
    res = jax.ShapeDtypeStruct((m, n), jnp.bfloat16) \
        if ep.get("residual") else None
    jaxpr = jax.make_jaxpr(lambda a, w, r: sfc_matmul(
        a, w, force_pallas=True, residual=r,
        activation=ep.get("activation", "none"),
        out_dtype=ep.get("out_dtype")))(a, w, res)
    assert not [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "pad"]
    # the curve keeps a grid to walk at a window's rows
    if m == 2048:
        assert -(-m // bm) >= 4


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for p in e.params.values():
            inner = getattr(p, "jaxpr", p)
            if hasattr(inner, "eqns"):
                yield from _eqns(inner)


@pytest.mark.parametrize("block", [(16, 16, 16), (128, 256, 128),
                                   (16, 128, 32)])
def test_named_block_is_run_as_named(block):
    """A named block is returned untouched, and its kernel takes the
    operands unpadded where the block overhangs them."""
    assert _resolve_blocks(2048, 6144, 2048, jnp.bfloat16, None, *block,
                           has_bias=False, has_residual=False) == block
    a = _rand((40, 200), jnp.bfloat16, 10)
    b = _rand((200, 300), jnp.bfloat16, 11)
    res = _rand((40, 300), jnp.bfloat16, 12)
    bm, bn, bk = block
    out = sfc_matmul(a, b, bm=bm, bn=bn, bk=bk, interpret=True,
                     residual=res)
    ref = matmul_fused_ref(a, b, residual=res, out_dtype=jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_partly_named_block_is_refused():
    with pytest.raises(ValueError, match="none"):
        _resolve_blocks(2048, 6144, 2048, jnp.bfloat16, None, 16, None, 32,
                        has_bias=False, has_residual=False)


@pytest.mark.parametrize("schedule,mt,nt,kt", [
    ("morton", 4, 4, 1), ("morton", 4, 2, 1), ("hilbert", 4, 4, 1),
    ("rowmajor", 3, 5, 1), ("morton", 4, 4, 3), ("supertile", 4, 6, 1),
])
def test_grid_step_counts_match_the_block_trace(schedule, mt, nt, kt):
    """A step skips a copy where its A or B block is the previous
    step's, read off ``core/schedule.matmul_block_trace`` (A, B, C per
    step, k innermost as in the kernel)."""
    trace = matmul_block_trace(grid_schedule(schedule, mt, nt), kt)
    steps = [trace[i:i + 3] for i in range(0, len(trace), 3)]
    elided = sum(1 for prev, cur in zip(steps, steps[1:])
                 if cur[0] == prev[0] or cur[1] == prev[1])
    assert K.grid_step_counts(schedule, mt, nt, kt) == (len(steps), elided)
    if kt > 1:
        assert elided == 0


def test_counters_add_each_kernel_gemm():
    """Each GEMM traced onto the kernel counts once; a call that reuses
    its trace, the XLA baseline and the CPU fallback count nothing."""
    reg = default_registry()
    steps, elided = (reg.counter("sfc.grid_steps"),
                     reg.counter("sfc.copies_elided"))
    s0, e0 = steps.value, elided.value
    # a shape no other test traces, so that this call traces
    a = jnp.ones((64, 264), jnp.float32)
    b = jnp.ones((264, 512), jnp.float32)
    sfc_matmul(a, b, bm=16, bn=128, bk=264, interpret=True)
    want = K.grid_step_counts("morton", 4, 4, 1)
    assert (steps.value - s0, elided.value - e0) == want
    assert want[1] > 0
    sfc_matmul(a, b, bm=16, bn=128, bk=264, interpret=True)  # cached
    sfc_matmul(a, b, schedule="xla")       # no kernel, nothing counted
    sfc_matmul(a, b)                       # the CPU's XLA fallback
    assert (steps.value - s0, elided.value - e0) == want
    # traced inside a caller's jit: counted there, once
    jax.jit(lambda x, y: sfc_matmul(x, y, bm=16, bn=128, bk=264,
                                    interpret=True, schedule="hilbert")
            )(a, b)
    assert steps.value - s0 == 2 * want[0]


def _rand(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype=dtype)


# (m, n, k, epilogue): the derived block overhangs N in its last block
# (n 2,200 over 2,048-wide blocks), K in its only block (k 200 -> bk
# 256), or neither
PARITY = {
    "ragged_n": (32, 2200, 256, {}),
    "f32_head": (48, 2200, 256, {"out_dtype": jnp.float32}),
    "silu": (40, 384, 256, {"activation": "silu"}),
    "residual": (40, 384, 256, {"residual": True}),
    "bias_gelu_residual": (40, 300, 200, {"bias": True,
                                          "activation": "gelu",
                                          "residual": True}),
    "k_overhang": (16, 256, 200, {}),
    "bk_is_k": (64, 256, 384, {}),
}


@pytest.mark.parametrize("case", sorted(PARITY))
def test_derived_blocks_match_reference(case):
    m, n, k, ep = PARITY[case]
    a = _rand((m, k), jnp.bfloat16, 1)
    b = _rand((k, n), jnp.bfloat16, 2)
    bias = _rand((n,), jnp.bfloat16, 3) if ep.get("bias") else None
    res = _rand((m, n), jnp.bfloat16, 4) if ep.get("residual") else None
    act, od = ep.get("activation", "none"), ep.get("out_dtype")
    out = sfc_matmul(a, b, interpret=True, bias=bias, activation=act,
                     residual=res, out_dtype=od)
    ref = matmul_fused_ref(a, b, bias=bias, activation=act, residual=res,
                           out_dtype=od or jnp.bfloat16)
    assert out.shape == (m, n) and out.dtype == ref.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)
    blocks = _derived(n, k, m, ep)
    if case == "ragged_n":
        assert n % blocks[1] and -(-n // blocks[1]) > 1
    if case in ("k_overhang", "bias_gelu_residual"):
        assert blocks[2] > k
    if case == "bk_is_k":
        assert blocks[2] == k


def test_engine_default_derives_blocks():
    from repro.models.layers import DotEngine

    eng = DotEngine(schedule="morton", interpret=True)
    assert eng.block is None
    x = _rand((2, 8, 200), jnp.float32, 8)
    w = _rand((200, 300), jnp.float32, 9)
    np.testing.assert_allclose(np.asarray(eng.dot(x, w)),
                               np.asarray(jnp.einsum("bsd,df->bsf", x, w)),
                               rtol=1e-4, atol=1e-4)


# GEMMs a forward's layer scans read in place: a dense layer's q, k, v,
# o, gate, up, down; an expert layer's MLA q, kv_a, kv_b, o, shared
# gate, up, down and expert gate, up, down, beside Moonlight's one
# leading dense layer (MLA and a SwiGLU)
IN_PLACE = {"qwen3_1_7b": 7, "moonlight_16b_a3b": 7 + 10}


@pytest.mark.parametrize("schedule", ["morton", "xla"])
@pytest.mark.parametrize("arch", sorted(IN_PLACE))
def test_forward_counts_each_weight_read_in_place(arch, schedule):
    """One traced forward raises ``sfc.weights_in_place`` by the GEMMs
    of its scan bodies on the kernels, k and v apart though they share
    a shape; the XLA engine reads none in place."""
    from repro.configs import get_smoke_config
    from repro.models import DotEngine, forward, init_model

    cfg = get_smoke_config(arch)
    params = jax.eval_shape(lambda k: init_model(cfg, k), jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    engine = DotEngine(schedule, interpret=True)
    count = default_registry().counter("sfc.weights_in_place")
    before = count.value
    jax.jit(lambda p, t: forward(p, cfg, {"tokens": t}, engine)).lower(
        params, tokens)
    want = IN_PLACE[arch] if schedule == "morton" else 0
    assert count.value - before == want
