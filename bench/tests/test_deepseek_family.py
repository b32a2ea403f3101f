"""The DeepSeek-V3 family (``bench/families/deepseek_v3.py``) at the
program's ``moonlight_16b_a3b`` SMOKE size on the CPU: the program's
forward agrees with the family's float32 reference on seeded weights,
for every share of the experts, with the grouped kernel in interpret
mode and with XLA's ragged dot; the float8 control does not; the
reference runs the experts it is given; the correction bias balances
the load; and a whole scoring run through the harness (its
``score_routed`` loop) comes out correct, and not with a moved route or
answer."""
import json
import time

import jax
import jax.numpy as jnp
import pytest

from harness import runner
from harness.cell import BENCH, ROOT, Cell, family_module
from harness.model import check_layout

FAMILY = family_module("deepseek_v3")
CELL = "moonlight-16b-a3b-ep4.score_2x2k"
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]


def smoke_model(first_held=0):
    """The cell's configuration file at the SMOKE preset's sizes: 3
    layers (one dense), 4 of 8 experts held, float32."""
    model = json.loads((BENCH / "configs" / "moonlight-16b-a3b-ep4.json")
                       .read_text())
    model.update({
        "program": {"arch": "moonlight_16b_a3b", "preset": "smoke"},
        "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 3,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "kv_lora_rank": 32, "moe_intermediate_size": 32,
        "n_routed_experts": 4, "n_routed_experts_published": 8,
        "first_held_expert": first_held, "num_experts_per_tok": 2,
        "vocab_size": 128, "torch_dtype": "float32"})
    return model


def _scored(cfg, params, tokens, engine):
    from repro.models import forward

    logits, _ = jax.jit(lambda p, t: forward(p, cfg, {"tokens": t}, engine)
                        )(params, tokens)
    lp = jax.nn.log_softmax(logits[:, :-1], -1)
    return jnp.take_along_axis(lp, tokens[:, 1:, None], -1)[..., 0]


@pytest.mark.parametrize("first_held", [0, 4])
@pytest.mark.parametrize("schedule", ["xla", "morton"])
def test_the_program_is_the_reference(first_held, schedule):
    from repro.models import DotEngine

    model = smoke_model(first_held)
    cfg, s = FAMILY.program_config(model), FAMILY.shapes(model)
    params = FAMILY.make_params(s, 2**33 + 5, model)
    check_layout(params, cfg)
    tokens = jax.random.randint(jax.random.key(3), (2, 256), 2, s.vocab)
    got = _scored(cfg, params, tokens,
                  DotEngine(schedule, interpret=schedule != "xla"))
    for r in range(2):
        want = FAMILY.reference(params, tokens[r], s, "f32")
        assert float(jnp.max(jnp.abs(got[r] - want))) < 1e-4
        ctl = FAMILY.reference(params, tokens[r], s, "fp8")
        assert float(jnp.max(jnp.abs(ctl - want))) > 0.1


def test_the_reference_rotates_published_pairs():
    """Rotating halves of the de-interleaved rotary columns, as the
    program does, equals DeepSeek-V3's rotation of adjacent pairs in the
    published order."""
    from repro.models.layers import apply_rope, rope

    x = jax.random.normal(jax.random.key(0), (6, 2, 8))       # published
    pos = jnp.arange(6)
    deint = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    cos, sin = rope(pos, 8, 50000.0)
    prog = apply_rope(deint[None], cos, sin)[0]
    assert jnp.allclose(FAMILY._published(deint), x)
    assert jnp.allclose(FAMILY._rope_pairs(x, pos, 50000.0), prog,
                        atol=1e-6)


def test_a_file_the_program_cannot_run_is_refused():
    for key, value in (("q_lora_rank", 1536), ("n_group", 8),
                       ("n_routed_experts_published", 16),
                       ("first_held_expert", 6)):
        with pytest.raises(SystemExit):
            FAMILY.program_config(dict(smoke_model(), **{key: value}))


def test_work_counts():
    """The cell's per-token FLOPs (PERF.md §4): 3.42 GFLOP at the mean
    context of a 2,048-token window, the routed experts at 6/64 of the
    rows each."""
    model = json.loads((BENCH / "configs" / "moonlight-16b-a3b-ep4.json")
                       .read_text())
    s = FAMILY.shapes(model)
    assert s.token_flops(1024.5, True) == pytest.approx(3.42e9, rel=0.01)
    experts = [g for g in s.gemms() if g.role.startswith("moe/experts/")]
    assert {(g.count, g.row_share) for g in experts} == {(26 * 16, 6 / 64)}


def smoke_cell():
    """The cell at the SMOKE size: its loop kind, 2 windows of 256
    tokens a call, limits for a float32 program."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mix = json.loads((BENCH / "traffic" / "score_2x2k.json").read_text())
    return Cell(name=CELL, chips=1, model=smoke_model(),
                mix=dict(mix, seq=256, windows=4096, ahead_s=0.2),
                check={"sample": 2, "max_logprob_gap": 1e-4,
                       "max_route_miss_margin": 1e-4},
                end_to_end=bench["end_to_end"],
                per_layer=[m for m in bench["per_layer"]
                           if CELL in m.get("workloads", [CELL])])


def _smoke_run(tmp_path, monkeypatch, hooks=None, control=False):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    jobs = []

    def keep(job):
        jobs.append(job)
        if hooks is not None:
            hooks(job)
    res = runner.run(smoke_cell(), 2**31 + 9, 1.0, True,
                     t_start=time.perf_counter(), require_chip=False,
                     out_dir=tmp_path, peaks=PEAKS, control=control,
                     hooks=keep)
    return res, jobs[0].last_check


def test_a_smoke_run_is_correct(tmp_path, monkeypatch):
    res, ck = _smoke_run(tmp_path, monkeypatch, control=True)
    assert res["correct"], (res, ck)
    assert ck["misses"] == 0 and ck["pairs"] == 2 * 2 * 256
    assert 0 < ck["held_share"] < 1
    # the float8 control fails the check
    assert res["diag"]["control_max_logprob_gap"] > 0.1
    assert ck["control_misses"] > 0
    assert ck["control_max_route_miss_margin"] > 1e-4
    # the CPU trace has no device plane: the cell's metrics all read it
    assert res["metrics"] == {}


def _routes_moved(job):
    """Each call's second expert of every token at the last expert layer
    replaced by another expert, the log-probabilities untouched."""
    fn = job.fn

    def broken(p, t):
        out = fn(p, t)
        r = out.routes
        moved = (r[:, -1, :, 1] + 1) % job.s.experts
        moved = jnp.where((r[:, -1] == moved[..., None]).any(-1),
                          (moved + 1) % job.s.experts, moved)
        return out._replace(routes=r.at[:, -1, :, 1].set(moved))
    job.fn = broken


def _answer_moved(job):
    """One log-probability of each row moved by 0.01."""
    fn = job.fn

    def broken(p, t):
        out = fn(p, t)
        return out._replace(logprobs=out.logprobs.at[:, 7].add(0.01))
    job.fn = broken


@pytest.mark.parametrize("fault", [_routes_moved, _answer_moved])
def test_a_broken_routed_path_is_not_correct(tmp_path, monkeypatch, fault):
    res, ck = _smoke_run(tmp_path, monkeypatch, hooks=fault)
    assert not res["correct"], (res, ck)


def test_the_reference_runs_the_experts_it_is_given():
    """Given its own choice, the reference is the unforced reference;
    given other experts it runs them, and says where they were not its
    own and by what margin."""
    model = smoke_model()
    s = FAMILY.shapes(model)
    params = FAMILY.make_params(s, 7, model)
    toks = jax.random.randint(jax.random.key(1), (256,), 2, s.vocab)
    lp, own, margin = FAMILY.reference_routed(params, toks, s, "f32")
    assert own.shape == (s.moe_layers, 256, s.topk)
    assert bool((margin >= 0).all())
    same = FAMILY.reference_routed(params, toks, s, "f32", own)
    assert bool((same[0] == lp).all()) and bool((same[1] == own).all())
    other = own.at[-1, :, 0].set((own[-1, :, 0] + 1) % s.experts)
    other = other.at[-1, :, 0].set(jnp.where(
        (own[-1] == other[-1, :, :1]).any(-1), own[-1, :, 0],
        other[-1, :, 0]))
    lp2, own2, margin2 = FAMILY.reference_routed(params, toks, s, "f32",
                                                 other)
    assert float(jnp.max(jnp.abs(lp2 - lp))) > 1e-3
    # the last layer's own choice is made before it runs: unchanged
    assert bool((own2 == own).all()) and bool((margin2 == margin).all())


def test_the_correction_bias_balances_the_load():
    """Each layer's bias gives every expert the same share of the top-k
    choices on the calibration sequence: the held experts' share of a
    seeded corpus lies near held / experts, on every seed."""
    model = smoke_model()
    s = FAMILY.shapes(model)
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.key(0), (512, 16))
                            + 2 * jax.random.normal(jax.random.key(1), (16,)))
    _, idx = jax.lax.top_k(scores + FAMILY.balanced_bias(scores, 3), 3)
    assert set(jnp.bincount(idx.reshape(-1), length=16).tolist()) == {96}
    for seed in (3, 2**40 + 1):
        params = FAMILY.make_params(s, seed, model)
        toks = jax.random.randint(jax.random.key(seed % 7),
                                  (FAMILY.CAL_SEQ,), 2, s.vocab)
        _, own, _ = FAMILY.reference_routed(params, toks, s, "f32")
        held = ((own >= s.first_held) & (own < s.first_held + s.held)).mean()
        assert abs(float(held) - s.held / s.experts) < 0.05
