"""The dense family (``bench/families/dense.py``): its plain reference
against the program's own full-sequence forward
(``repro.models.forward``), at the smoke size on the CPU, both in
float32 on the benchmark's weights; and the weights, reference numbers
and work counts it gives, held to those the harness gave before the
family had a file of its own."""
import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import SMOKE_MODEL, dense
from harness import model
from harness.cell import BENCH

F = dense()
L = F.Q_BLOCK     # one query block of the reference
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]


@pytest.fixture(scope="module")
def setup():
    from repro.models import forward

    s = F.shapes(SMOKE_MODEL)
    cfg = dataclasses.replace(F.program_config(SMOKE_MODEL),
                              attn_q_chunk=L)
    params = F.make_params(s, 123, SMOKE_MODEL)
    model.check_layout(params, cfg)
    toks = np.random.default_rng(0).integers(2, s.vocab, L).astype(np.int32)
    logits, _ = forward(params, cfg, {"tokens": jnp.asarray(toks[None])})
    lp = np.asarray(jax.nn.log_softmax(logits[0, :-1], -1), np.float32)
    want = np.take_along_axis(lp, toks[1:, None], -1)[:, 0]
    return s, params, toks, want


def _logprobs(s, params, toks, mode):
    return np.asarray(F.reference(params, jnp.asarray(toks), s, mode))


def test_reference_matches_the_program_forward(setup):
    s, params, toks, want = setup
    got = _logprobs(s, params, toks, "f32")
    # both sides are float32 over 2 layers: they differ only by the order
    # of f32 sums (XLA's dot against the reference's HIGHEST einsums),
    # some 1e-6 of a log-probability of order 5; 1e-4 leaves room and is
    # still far below what a wrong weight, head or position would move
    # (order 1)
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_tied_embeddings_are_one_matrix(setup):
    s, params, _, _ = setup
    np.testing.assert_array_equal(np.asarray(params["embed"]),
                                  np.asarray(params["lm_head"]).T)


def test_the_float8_control_is_far_coarser(setup):
    s, params, toks, want = setup
    err32 = np.abs(_logprobs(s, params, toks, "f32") - want).max()
    err8 = np.abs(_logprobs(s, params, toks, "fp8") - want).max()
    assert err8 > 100 * err32


def test_gqa_reads_kv_head_of_its_group():
    """Query head h attends with kv head h // (H / Hkv): zeroing one kv
    head's values changes exactly its group of query heads."""
    n, h, hkv, dh = L, 4, 2, 8
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal(sh), jnp.float32)
               for sh in [(n, h, dh), (n, hkv, dh), (n, hkv, dh)])
    out = F._attention(q, k, v, "f32")
    out2 = F._attention(q, k, v.at[:, 1].set(0.0), "f32")
    changed = np.abs(np.asarray(out - out2)).max(axis=(0, 2)) > 0
    assert changed.tolist() == [False, False, True, True]


# What the harness gave for SMOKE_MODEL before the family had a file of
# its own (bench/harness/model.make_params and bench/reference.py), on
# the CPU: sha256 of the weights' leaves in tree order, and of the
# reference's log-probabilities of 256 tokens drawn by
# numpy.random.default_rng(0) from [2, vocab): their sum and positions
# 0, 127 and 254.  The weights come out bit for bit on any CPU; the
# log-probabilities to float32 rounding, since XLA's CPU dot sums in an
# order of the CPU's own: a log-probability of order 5 moves some 1e-6
# from one CPU to another.  The float8 control is not held here: those
# last bits move values across float8's rounding boundaries, and its
# sum moved by 0.66 of 1,383 from one CPU to another.
BEFORE = {
    123: ("23963d444ca95a88415c7e922ff1c9726fb3c45886c09bb1c3d734ddf3a1237f",
          [-1381.1312773227692, -7.245530128479004, -5.7124505043029785,
           -5.256797790527344]),
    2**33 + 7: (
        "059e4bf44ff062446c975f680fcc7ebe0d22a44f947f164a2c16c02b525173f4",
        [-1376.7374041080475, -5.214193820953369, -6.134997844696045,
         -5.725545883178711]),
}


@pytest.mark.parametrize("seed", sorted(BEFORE))
def test_weights_and_reference_are_as_before(seed):
    s = F.shapes(SMOKE_MODEL)
    params = F.make_params(s, seed, SMOKE_MODEL)
    weights, (total, *at) = BEFORE[seed]
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == weights
    toks = np.random.default_rng(0).integers(2, s.vocab, 256).astype(np.int32)
    lp = _logprobs(s, params, toks, "f32")
    assert lp.astype(np.float64).sum() == pytest.approx(total, rel=1e-6)
    np.testing.assert_allclose(lp[[0, 127, 254]], at, rtol=0, atol=1e-5)


# The same, of the benchmarked configurations: a token's FLOPs at
# contexts 1, 1024.5 and 2048, with and without the head; the least GEMM
# time of a step at (rows, head rows) (2048, 2048), (4096, 128), (1, 1),
# (512, 0) and (0, 64); and at (2048, 2048) that of the attention
# projections, the MLP and the head, on the v5e's peaks.
WORK = {
    "qwen3-1.7b": (
        [3441131520.0, 2818801664.0, 3675897856.0, 3053568000.0,
         3910664192.0, 3288334336.0],
        [0.03577140909092374, 0.059458900492315586, 0.004204753426129422,
         0.00732542645409134, 0.0008076771868131868],
        [0.007325426454091371, 0.02197627936227411, 0.006469703274558375]),
    "glm4-9b-20L": (
        [9399762944.0, 8158248960.0, 9735143424.0, 8493629440.0,
         10070523904.0, 8829009920.0],
        [0.09781691943024112, 0.1713299198830616, 0.011481304302808307,
         0.021304372674027384, 0.001563901811965812],
        [0.014926230803885884, 0.06998398487390863, 0.0129067037524467]),
}
ROLE_GROUPS = [("attn/q", "attn/k", "attn/v", "attn/o"),
               ("mlp/gate", "mlp/up", "mlp/down"), ("head",)]


@pytest.mark.parametrize("config", sorted(WORK))
def test_work_counts_are_as_before(config):
    s = F.shapes(json.loads((BENCH / "configs" / f"{config}.json")
                            .read_text()))
    f, bw = PEAKS["bf16_flops_per_s"], PEAKS["hbm_bytes_per_s"]
    flops, gemm, roles = WORK[config]
    assert [s.token_flops(c, h) for c in (1, 1024.5, 2048)
            for h in (True, False)] == flops
    assert [model.gemm_min_seconds(s.gemms(), r, hr, f, bw)
            for r, hr in ((2048, 2048), (4096, 128), (1, 1), (512, 0),
                          (0, 64))] == pytest.approx(gemm, rel=1e-12)
    assert [model.gemm_min_seconds(s.gemms(), 2048, 2048, f, bw, roles=g)
            for g in ROLE_GROUPS] == pytest.approx(roles, rel=1e-12)
