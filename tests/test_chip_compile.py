"""The main-path Pallas kernels compile for a TPU v5e chip.

Interpret mode runs a kernel's logic but not the TPU compiler, so a
block the compiler refuses (alignment, VMEM, partitioning) would first
show on the chip.  These tests compile for one chip of a *described*
``v5e:2x2`` topology -- no chip needed -- at the qwen3-1.7B widths:

* ``sfc_matmul_pallas`` in bf16 at every projection and vocab-head
  shape of the model, under every config the tuner proposes there
  (schedules x the blocks of ``tune/autotune._BLOCK_CANDIDATES``), and
  under each epilogue;
* the blocks ``sfc_blocks`` derives, through the ``sfc_matmul`` wrapper,
  at every GEMM of the benchmark cells' configurations (qwen3-1.7B and
  GLM-4-9B, a 2,048-row window and a 16-slot decode step) under the
  epilogue the forward gives it;
* ``paged_decode_attention_pallas`` with pages of 8 and 16;
* at the Moonlight-16B-A3B cell's shapes (two 2,048-token windows a
  call): the grouped expert GEMM ``sfc_gmm_pallas`` (16 held experts of
  width 1,408, its grid sized for the worst case), and latent
  attention's kv_a (N 576) and kv_b (K 512) projections.

The topology is described inside a fixture, never at import, so that
only the test worker that runs this file loads the TPU library.
"""
import dataclasses
import os
import re

import pytest

import jax
import jax.numpy as jnp

from _gemms import cell_gemm_cases, forward_gemms
from repro.configs import get_config
from repro.kernels.ops import sfc_gmm, sfc_matmul
from repro.kernels.paged_attention import paged_decode_attention_pallas
from repro.kernels.sfc_matmul import GROUP_ROWS, sfc_matmul_pallas
from repro.serve.paged_kv import default_pool_pages, default_slot_pages
from repro.tune import candidate_configs

CFG = get_config("qwen3_1_7b")
D, DFF, DH = CFG.d_model, CFG.d_ff, CFG.d_head
# (N, K) of each GEMM a decode or prefill step runs, by role
GEMMS = {
    "q_o": (CFG.n_heads * DH, D),
    "kv": (CFG.n_kv_heads * DH, D),
    "gate_up": (DFF, D),
    "down": (D, DFF),
    "head": (CFG.vocab, D),
}
# M: 128 rows is the decode step (4 slots padded to one 128-row block)
# and the prefill chunk (4 slots x 32 tokens); 2048 is a long prefill,
# where every block of the tuner's candidate list fits
ROWS = (128, 2048)
EPILOGUES = {
    "none": {},
    "bias": {"bias": True},
    "relu": {"activation": "relu"},
    "gelu": {"activation": "gelu"},
    "silu": {"activation": "silu"},
    "residual": {"residual": True},
    "bias_gelu_residual": {"bias": True, "activation": "gelu",
                           "residual": True},
    "f32_out": {"out_dtype": jnp.float32},
}
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with JAX's persistent cache off
    (a compile for a described chip is written to it but cannot be read
    back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "skip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


def _compile_gemm(sharding, cfg, m, n, k, *, bias=False, residual=False,
                  activation="none", out_dtype=None) -> str:
    """Compiled HLO of one bf16 kernel call at the padded (m, n, k)."""
    def pad(x, b):
        return -(-x // b) * b

    mp, np_, kp = pad(m, cfg.bm), pad(n, cfg.bn), pad(k, cfg.bk)

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)

    def call(a, b, bias_, res):
        return sfc_matmul_pallas(
            a, b, schedule=cfg.schedule, bm=cfg.bm, bn=cfg.bn, bk=cfg.bk,
            use_prefetch=cfg.use_prefetch, g=cfg.g, bias=bias_,
            residual=res, activation=activation, out_dtype=out_dtype)

    args = (spec(mp, kp), spec(kp, np_), spec(np_) if bias else None,
            spec(mp, np_) if residual else None)
    return jax.jit(call).lower(*args).compile().as_text()


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("role", sorted(GEMMS))
def test_sfc_matmul_tuner_candidates_compile(one_chip, role, rows):
    n, k = GEMMS[role]
    cands = [c for c in candidate_configs(rows, n, k, dtype_bytes=2)
             if c.schedule != "xla"]
    assert {c.schedule for c in cands} >= {"rowmajor", "morton",
                                           "hilbert", "supertile"}
    for c in cands:
        assert CUSTOM_CALL in _compile_gemm(one_chip, c, rows, n, k), c


def test_every_candidate_block_is_compiled():
    """The shapes above reach every block the tuner can propose."""
    from repro.tune.autotune import _BLOCK_CANDIDATES

    seen = {(c.bm, c.bn, c.bk) for role in GEMMS for rows in ROWS
            for c in candidate_configs(rows, *GEMMS[role], dtype_bytes=2)
            if c.schedule != "xla"}
    assert seen == set(_BLOCK_CANDIDATES)


@pytest.mark.parametrize("epilogue", sorted(EPILOGUES))
def test_sfc_matmul_epilogues_compile(one_chip, epilogue):
    """Each epilogue, under each schedule the tuner proposes, at the
    MLP up-projection of a long prefill."""
    n, k = GEMMS["gate_up"]
    for c in candidate_configs(2048, n, k, dtype_bytes=2):
        if c.schedule == "xla" or (c.bm, c.bn, c.bk) != (256, 256, 128):
            continue
        hlo = _compile_gemm(one_chip, c, 2048, n, k, **EPILOGUES[epilogue])
        assert CUSTOM_CALL in hlo, c


@pytest.mark.parametrize("arch,role,rows", cell_gemm_cases())
def test_sfc_matmul_derived_blocks_compile(one_chip, arch, role, rows):
    """The wrapper's shape-derived blocks, operands unpadded, with the
    kernel's op still named ``sfc_matmul_pallas`` (the trace readers
    find it by that name)."""
    n, k, ep = forward_gemms(arch)[role]

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def call(a, b, res):
        return sfc_matmul(a, b, force_pallas=True, residual=res,
                          activation=ep.get("activation", "none"),
                          out_dtype=ep.get("out_dtype"))

    res = spec(rows, n) if ep.get("residual") else None
    hlo = jax.jit(call).lower(spec(rows, k), spec(k, n), res) \
        .compile().as_text()
    assert CUSTOM_CALL in hlo
    assert "%sfc_matmul_pallas" in hlo


@pytest.mark.parametrize("page_size", [8, 16])
def test_paged_attention_compiles(one_chip, page_size):
    """The serve pool of 4 slots x 256 tokens at the qwen3 widths."""
    slots, cache_len = 4, 256
    pages = default_pool_pages(slots, cache_len, page_size)
    rows = CFG.n_layers * pages + 1          # + the reserved zero row

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = spec((rows, page_size, CFG.n_kv_heads, DH))
    hlo = jax.jit(paged_decode_attention_pallas).lower(
        spec((slots, CFG.n_heads, DH)), pool, pool,
        spec((slots, default_slot_pages(pages, cache_len, page_size)),
             jnp.int32),
        spec((slots,), jnp.int32)).compile().as_text()
    assert CUSTOM_CALL in hlo


MOON = get_config("moonlight_16b_a3b")
# the cell's tokens a call (2 windows of 2,048), and its held experts
MOON_ROWS, MOON_HELD = 4096, 16


@pytest.mark.parametrize("role", ["gate", "down"])
def test_grouped_expert_gemm_compiles(one_chip, role):
    """The grouped kernel over the worst case of rows (every token's
    top 6 held, each expert's group padded to whole row tiles)."""
    d, ff = MOON.d_model, MOON.moe_dff
    k, n = (d, ff) if role == "gate" else (ff, d)
    bound = MOON_ROWS * MOON.moe_topk + MOON_HELD * (GROUP_ROWS - 1)
    rows = -(-bound // GROUP_ROWS) * GROUP_ROWS

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(a, b, tiles):
        return sfc_gmm(a, b, tiles, force_pallas=True,
                       activation="silu" if role == "gate" else "none")

    hlo = jax.jit(call).lower(spec((rows, k)), spec((MOON_HELD, k, n)),
                              spec((MOON_HELD,), jnp.int32)) \
        .compile().as_text()
    assert CUSTOM_CALL in hlo
    assert "%sfc_gmm_pallas" in hlo and "%sfc_matmul_pallas" not in hlo


@pytest.mark.parametrize("role", ["kv_a", "kv_b"])
def test_mla_projections_compile(one_chip, role):
    """kv_a's 576 columns (512 latent + 64 rotary: a ragged last block)
    and kv_b's 512-deep K, with their derived blocks."""
    d, r, rd = MOON.d_model, MOON.kv_lora_rank, MOON.qk_rope_dim
    hv = MOON.n_heads * (MOON.d_head + MOON.v_head_dim)
    k, n = (d, r + rd) if role == "kv_a" else (r, hv)

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    hlo = jax.jit(lambda a, b: sfc_matmul(a, b, force_pallas=True)).lower(
        spec(MOON_ROWS, k), spec(k, n)).compile().as_text()
    assert CUSTOM_CALL in hlo and "%sfc_matmul_pallas" in hlo


# ------------------------------------------- layer weights read in place --
def _hlo_instructions(hlo: str) -> dict:
    """{computation: {instruction: (opcode, dims, operand names)}} of a
    compiled HLO text; dims () for a tuple."""
    comps: dict = {}
    cur = None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) ", line)
        if head and not line[0].isspace():
            cur = comps.setdefault(head.group(1), {})
            continue
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.+?) ([\w\-]+)\((.*)",
                     line)
        if m and cur is not None:
            shape = re.match(r"\w+\[([\d,]*)\]", m.group(2))
            dims = tuple(int(d) for d in shape.group(1).split(",") if d) \
                if shape else ()
            cur[m.group(1)] = (m.group(3), dims, re.findall(
                r"%([\w.\-]+)", m.group(4).split(")", 1)[0]))
    return comps


# the cells' configurations at their full depth (the compile costs what
# two layers would: one scan body), rows a call, and the custom calls of
# the two kernels a forward holds: 7 GEMMs a scan body (Moonlight's
# expert body 7 more on sfc_matmul, 3 grouped) and the head
IN_PLACE_CELLS = {
    "qwen3": (CFG, 1, 8, 0),
    "moonlight": (dataclasses.replace(MOON, moe_held=MOON_HELD), 2, 15, 3),
}


@pytest.mark.parametrize("name", sorted(IN_PLACE_CELLS))
def test_layer_scan_reads_weights_in_place(one_chip, monkeypatch, name):
    """The forward through the Morton engine, compiled for the chip:
    each SFC kernel in a layer loop reads its weight's whole stack as
    the loop holds it (no copy of the stack, no slice of the layer),
    and no dynamic slice of a layer's weight is left anywhere.  Kernels
    outside the loops (the head, Moonlight's one dense layer, its loop
    of one removed) run once a call and are not held to it."""
    from repro.kernels import ops
    from repro.models import DotEngine, forward, init_model

    cfg, rows, n_matmul, n_gmm = IN_PLACE_CELLS[name]
    monkeypatch.setattr(ops, "default_backend_is_tpu", lambda: True)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda k: init_model(cfg, k), jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((rows, 2048), jnp.int32,
                                  sharding=one_chip)
    engine = DotEngine("morton")
    hlo = jax.jit(lambda p, t: forward(p, cfg, {"tokens": t}, engine)[0]) \
        .lower(params, tokens).compile().as_text()
    comps = _hlo_instructions(hlo)
    stacks = {s.shape for s in jax.tree.leaves(params["layers"])
              if s.ndim >= 3}
    found = {k: [(c, n) for c, ins in comps.items() for n, (op, _, _)
                 in ins.items() if op == "custom-call"
                 and n.startswith(k + ".")]
             for k in ("sfc_matmul_pallas", "sfc_gmm_pallas")}
    assert (len(found["sfc_matmul_pallas"]),
            len(found["sfc_gmm_pallas"])) == (n_matmul, n_gmm)
    bodies = set(re.findall(r"body=%([\w.\-]+)", hlo))
    in_loops = [(c, n) for calls in found.values() for c, n in calls
                if c in bodies]
    assert len(in_loops) == 7 + 3 * (n_gmm > 0)
    layer_dims = set()       # the layer shapes of the kernels' weights
    for c, n in in_loops:
        ins = comps[c]
        weights = [o for o in ins[n][2] if ins.get(o, ("", ()))[1] in stacks]
        assert len(weights) == 1, (n, ins[n])
        assert ins[weights[0]][0] in ("get-tuple-element", "parameter"), (
            n, weights[0], ins[weights[0]])
        layer_dims.add(ins[weights[0]][1][1:])
    slices = [(n, dims) for ins in comps.values()
              for n, (op, dims, _) in ins.items()
              if (op == "dynamic-slice" or n.startswith("dynamic-slice"))
              and dims[-2:] and (dims in layer_dims
                                 or dims[1:] in layer_dims)]
    assert not slices, slices
