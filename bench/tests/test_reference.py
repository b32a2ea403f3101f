"""The plain reference against the program's own full-sequence forward
(``repro.models.forward``), at the smoke size on the CPU, both in
float32 on the benchmark's weights."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
from conftest import SMOKE_MODEL
from harness import model

L = reference.Q_BLOCK     # one query block of the reference


@pytest.fixture(scope="module")
def setup():
    from repro.models import forward

    s = model.shapes(SMOKE_MODEL)
    cfg = dataclasses.replace(model.program_config(SMOKE_MODEL),
                              attn_q_chunk=L)
    params = model.make_params(s, 123, SMOKE_MODEL)
    model.check_layout(params, cfg)
    toks = np.random.default_rng(0).integers(2, s.vocab, L).astype(np.int32)
    logits, _ = forward(params, cfg, {"tokens": jnp.asarray(toks[None])})
    lp = np.asarray(jax.nn.log_softmax(logits[0, :-1], -1), np.float32)
    want = np.take_along_axis(lp, toks[1:, None], -1)[:, 0]
    return s, params, toks, want


def _logprobs(s, params, toks, mode):
    return np.asarray(reference.next_token_logprobs(
        params, jnp.asarray(toks), s=s, mode=mode))


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
    out = reference._attention(q, k, v, "f32")
    out2 = reference._attention(q, k, v.at[:, 1].set(0.0), "f32")
    changed = np.abs(np.asarray(out - out2)).max(axis=(0, 2)) > 0
    assert changed.tolist() == [False, False, True, True]
