"""DeepSeek-V3's block on the CPU (float32, the Pallas kernels in
interpret mode): latent attention against a naive one written from the
published code, the sigmoid router, dropless dispatch against the
all-experts oracle, the expert shares adding up to the uncut layer, the
grouped SFC kernel against a plain ragged matmul, the leading dense
layer, the refusals of the paths that need a latent KV cache, and the
dense configs' program unchanged."""
import dataclasses
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.kernels.ops import gmm_ref, sfc_gmm
from repro.kernels.sfc_matmul import GROUP_ROWS, gmm_steps, sfc_gmm_pallas
from repro.models import DotEngine, forward, init_model, make_batch
from repro.models import moe as moe_mod
from repro.models.attention import KV_A_NORM_EPS, init_attention, \
    mla_attention
from repro.models.config import ShapeSpec
from repro.models.layers import rope, swiglu_mlp

MOON = dataclasses.replace(get_smoke_config("moonlight_16b_a3b"),
                           remat=False)
GRANITE = get_smoke_config("granite_moe_1b_a400m")
ENGINES = [DotEngine(), DotEngine("morton", interpret=True)]


def _rand(key, *shape):
    return jax.random.normal(jax.random.key(key), shape, jnp.float32)


# ----------------------------------------------------------------- MLA --
def _naive_mla(x, wq, wkv_a, kv_norm, wkv_b, wo, cfg):
    """DeepSeek-V3's attention (no q-LoRA) on one sequence x (L, d), the
    weights in the published column order: the rotary parts rotated in
    adjacent pairs (``view(r/2, 2).transpose``, then rotate-half)."""
    n, h, dn, r = x.shape[0], cfg.n_heads, cfg.d_head, cfg.kv_lora_rank
    rd, dv = cfg.qk_rope_dim, cfg.v_head_dim
    hi = jax.lax.Precision.HIGHEST
    q = jnp.dot(x, wq, precision=hi).reshape(n, h, dn + rd)
    kv = jnp.dot(x, wkv_a, precision=hi)
    c = kv[:, :r] * jax.lax.rsqrt(jnp.mean(kv[:, :r] ** 2, -1, keepdims=True)
                                  + KV_A_NORM_EPS) * kv_norm
    kvb = jnp.dot(c, wkv_b, precision=hi).reshape(n, h, dn + dv)
    inv = cfg.rope_theta ** (-jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    ang = jnp.arange(n)[:, None] * inv
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None]

    def rot(t):
        t = t.reshape(*t.shape[:-1], rd // 2, 2).swapaxes(-1, -2).reshape(
            t.shape)
        t1, t2 = t[..., :rd // 2], t[..., rd // 2:]
        return t * cos + jnp.concatenate([-t2, t1], -1) * sin

    qf = jnp.concatenate([q[..., :dn], rot(q[..., dn:])], -1)
    kf = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(
        rot(kv[:, None, r:]), (n, h, rd))], -1)
    sc = jnp.einsum("qhd,khd->hqk", qf, kf, precision=hi) / np.sqrt(dn + rd)
    sc = jnp.where(jnp.tril(jnp.ones((n, n), bool)), sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), kvb[..., dn:],
                   precision=hi)
    return jnp.dot(o.reshape(n, h * dv), wo, precision=hi)


def _deinterleave(w, heads, nope, rd):
    """Published rotary columns (per head after ``nope`` plain ones) ->
    the program's order: even columns, then odd ones."""
    w = w.reshape(w.shape[0], heads, nope + rd)
    pe = w[..., nope:]
    pe = jnp.concatenate([pe[..., 0::2], pe[..., 1::2]], -1)
    return jnp.concatenate([w[..., :nope], pe], -1).reshape(w.shape[0], -1)


@pytest.mark.parametrize("engine", ENGINES, ids=["xla", "morton"])
def test_mla_matches_the_published_attention(engine):
    cfg = MOON
    p = init_attention(jax.random.key(0), cfg)
    pub = {"wq": _rand(1, *p["wq"].shape) * 0.2,
           "wkv_a": _rand(2, *p["wkv_a"].shape) * 0.2,
           "kv_norm": 1 + 0.1 * _rand(3, *p["kv_norm"].shape),
           "wkv_b": _rand(4, *p["wkv_b"].shape) * 0.2,
           "wo": _rand(5, *p["wo"].shape) * 0.2}
    prog = dict(pub, wq=_deinterleave(pub["wq"], cfg.n_heads, cfg.d_head,
                                      cfg.qk_rope_dim),
                wkv_a=_deinterleave(pub["wkv_a"], 1, cfg.kv_lora_rank,
                                    cfg.qk_rope_dim))
    x = _rand(6, 2, 32, cfg.d_model)
    cos, sin = rope(jnp.arange(32), cfg.rope_dim, cfg.rope_theta)
    got = mla_attention(x, prog, cfg, engine, cos, sin, q_chunk=16)
    for b in range(2):
        want = _naive_mla(x[b], pub["wq"], pub["wkv_a"], pub["kv_norm"],
                          pub["wkv_b"], pub["wo"], cfg)
        np.testing.assert_allclose(got[b], want, rtol=2e-5, atol=2e-5)


# -------------------------------------------------------------- router --
def test_sigmoid_router_bias_moves_the_choice_not_the_weights():
    cfg = MOON
    xf = _rand(0, 40, cfg.d_model)
    p = moe_mod.init_moe(jax.random.key(1), cfg)
    scores = jax.nn.sigmoid(xf @ p["router"])
    # a bias that favours expert 5 above every score picks it for every
    # token, and its weight is its own unbiased score
    bias = jnp.zeros(cfg.moe_experts).at[5].set(2.0)
    w, idx, aux = moe_mod._router(xf, dict(p, router_bias=bias), cfg)
    assert (idx == 5).any(-1).all() and float(aux) == 0.0
    want = jnp.take_along_axis(scores, idx, -1)
    want = want / want.sum(-1, keepdims=True) * cfg.moe_route_scale
    np.testing.assert_allclose(w, want, rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), cfg.moe_route_scale, rtol=1e-6)
    # without the bias the choice is the top scores themselves
    _, idx0, _ = moe_mod._router(
        xf, dict(p, router_bias=jnp.zeros(cfg.moe_experts)), cfg)
    np.testing.assert_array_equal(
        np.sort(idx0, -1), np.sort(jax.lax.top_k(scores, cfg.moe_topk)[1], -1))
    assert (np.sort(idx0, -1) != np.sort(idx, -1)).any()


# ------------------------------------------------------------ dispatch --
@pytest.mark.parametrize("engine", ENGINES, ids=["xla", "morton"])
@pytest.mark.parametrize("tokens", [6, 300])
def test_dropless_equals_every_expert_on_every_token(engine, tokens):
    """No token dropped, at a few tokens and past the old 256-token
    threshold, against ``moe_dense`` and against capacity dispatch with
    room for every assignment."""
    cfg = GRANITE
    p = moe_mod.init_moe(jax.random.key(0), cfg)
    x = _rand(1, 2, tokens // 2, cfg.d_model)
    got, _, _ = moe_mod.moe_dropless(x, p, cfg, engine)
    want, _ = moe_mod.moe_dense(x, p, cfg, engine)
    cap, _ = moe_mod.moe_capacity(x, p, cfg, engine, capacity=tokens)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got, cap, rtol=2e-5, atol=2e-5)
    got_ffn, _ = moe_mod.moe_ffn(x, p, cfg, engine)
    np.testing.assert_array_equal(got_ffn, got)


def test_dropless_takes_a_single_row_group_and_empty_ones():
    """Route every token to experts 0 and 1 but one, which goes to 2:
    groups of all-but-one rows, one row and none."""
    cfg = dataclasses.replace(GRANITE, moe_topk=2)
    p = moe_mod.init_moe(jax.random.key(0), cfg)
    t = 150
    x = _rand(2, 1, t, cfg.d_model)
    bias = jnp.zeros((t, cfg.moe_experts)).at[:, :2].set(50.0)
    bias = bias.at[7, 1].set(-50.0).at[7, 2].set(50.0)
    real = moe_mod._router

    def router(xf, params, c):
        logits = xf @ params["router"] + bias
        w, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), c.moe_topk)
        return w / w.sum(-1, keepdims=True), idx, jnp.zeros(())
    moe_mod._router = router
    try:
        for engine in ENGINES:
            got, _, _ = moe_mod.moe_dropless(x, p, cfg, engine)
            want, _ = moe_mod.moe_dense(x, p, cfg, engine)
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    finally:
        moe_mod._router = real


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips of 2 experts each: their outputs, the shared experts
    (which every chip computes alike) counted once, add up to the
    layer that holds all 8."""
    whole = dataclasses.replace(MOON, moe_held=0)
    p = moe_mod.init_moe(jax.random.key(0), whole)
    x = _rand(1, 2, 40, whole.d_model)
    engine = ENGINES[1]
    want, _ = moe_mod.moe_ffn(x, p, whole, engine)
    total = -3 * swiglu_mlp(x, p["shared"], engine)
    for r in range(4):
        cfg = dataclasses.replace(whole, moe_held=2, moe_first_held=2 * r)
        pr = dict(p, **{k: p[k][2 * r:2 * r + 2] for k in ("w1", "w3", "w2")})
        y, _ = moe_mod.moe_ffn(x, pr, cfg, engine)
        total = total + y
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------ grouped kernel --
def _plain_grouped(a, b, tiles):
    out = []
    for g, n in enumerate(np.asarray(tiles)):
        lo = int(np.asarray(tiles)[:g].sum()) * GROUP_ROWS
        out.append(a[lo:lo + n * GROUP_ROWS] @ b[g])
    return jnp.concatenate(out)


@pytest.mark.parametrize("schedule", ["morton", "hilbert", "rowmajor"])
@pytest.mark.parametrize("bn,bk", [(128, 128), (256, 384)])
def test_grouped_kernel_matches_a_ragged_matmul(schedule, bn, bk):
    """Empty groups, live tiles followed by dead tail tiles, K and N not
    multiples of the blocks (masked and cropped blocks)."""
    tiles = jnp.array([2, 0, 1, 0, 1], jnp.int32)
    a = _rand(0, 7 * GROUP_ROWS, 200)
    b = _rand(1, 5, 200, 300)
    out = sfc_gmm_pallas(a, b, tiles, schedule=schedule, bn=bn, bk=bk,
                         interpret=True, activation="silu")
    live = int(tiles.sum()) * GROUP_ROWS
    want = jax.nn.silu(_plain_grouped(a, b, tiles))
    np.testing.assert_allclose(out[:live], want, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(gmm_ref(a, b, tiles, activation="silu")[:live],
                               want, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(
        sfc_gmm(a, b, tiles, schedule=schedule, interpret=True)[:live],
        _plain_grouped(a, b, tiles), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_grouped_stacked_weight_reads_its_layer_in_place(layer):
    """The grouped kernel on a (L, G, K, N) stack and a traced layer
    index equals the kernel on that layer's (G, K, N) slice, bit for
    bit, with groups left empty; so do the wrapper's kernel path and its
    XLA fallback (``lax.ragged_dot`` on the slice)."""
    tiles = jnp.array([1, 0, 2, 0], jnp.int32)
    a = _rand(0, 5 * GROUP_ROWS, 200)
    w = _rand(1, 3, 4, 200, 300)
    live = int(tiles.sum()) * GROUP_ROWS

    def kernel(b, i=None):
        return sfc_gmm_pallas(a, b, tiles, bn=128, bk=128, interpret=True,
                              activation="silu", layer=i)

    got = jax.jit(kernel)(w, jnp.int32(layer))
    np.testing.assert_array_equal(got[:live], kernel(w[layer])[:live])
    for path in ({"interpret": True}, {}):      # the kernel, the fallback
        got = jax.jit(lambda b, i: sfc_gmm(a, b, tiles, layer=i, **path))(
            w, layer)
        want = sfc_gmm(a, w[layer], tiles, **path)
        np.testing.assert_array_equal(got[:live], want[:live])


@pytest.mark.parametrize("live_tiles", [0, 3, 8])
def test_grouped_walk_repeats_the_last_live_step(live_tiles):
    """The walk visits every live (row tile, column tile) once, in the
    curve's order, and every step after them repeats the last live one,
    so that those steps find their blocks resident."""
    from repro.core.schedule import grid_schedule

    mt, nt = 8, 3
    steps, live = gmm_steps("morton", mt, nt, jnp.int32(live_tiles))
    steps = np.asarray(steps).reshape(-1, 2)
    assert int(live) == live_tiles * nt
    curve = [tuple(s) for s in grid_schedule("morton", mt, nt)
             if s[0] < live_tiles]
    assert [tuple(s) for s in steps[:int(live)]] == curve
    tail = steps[max(int(live) - 1, 0):]
    assert (tail == tail[0]).all()


def test_grouped_gemm_counts_its_grid_and_row_bound():
    from repro.obs.metrics import MetricsRegistry
    from repro.obs import metrics as obs_metrics

    reg = MetricsRegistry()
    old = obs_metrics._DEFAULT
    obs_metrics._DEFAULT = reg
    try:
        a, b = _rand(0, 3 * GROUP_ROWS, 256), _rand(1, 2, 256, 384)
        jax.jit(lambda a, b: sfc_gmm(a, b, jnp.array([1, 1], jnp.int32),
                                     interpret=True)).lower(a, b)
    finally:
        obs_metrics._DEFAULT = old
    assert reg.counter("sfc_gmm.row_bound").value == 3 * GROUP_ROWS
    assert reg.counter("sfc_gmm.grid_steps").value == 3


# --------------------------------------------------------------- model --
def test_the_leading_dense_layer_runs_before_the_expert_layers():
    from repro.models import transformer as T

    cfg = MOON
    params = init_model(cfg, jax.random.key(0))
    assert jax.tree.leaves(params["dense_layers"])[0].shape[0] == 1
    assert params["layers"]["moe"]["w1"].shape[:2] == (cfg.n_layers - 1,
                                                       cfg.moe_held)
    assert "mlp" in params["dense_layers"] and "moe" not in \
        params["dense_layers"]
    batch = make_batch(cfg, ShapeSpec("s", 32, 2, "train"), seed=1)
    engine = DotEngine()
    got, _ = forward(params, cfg, batch, engine)

    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    cos, sin = rope(jnp.arange(32), cfg.rope_dim, cfg.rope_theta)
    for stack in ("dense_layers", "layers"):
        for i in range(jax.tree.leaves(params[stack])[0].shape[0]):
            lp = jax.tree.map(lambda a: a[i], params[stack])
            x, _ = T._layer_fwd(x, lp, cfg, engine, cos, sin, None)
    np.testing.assert_allclose(got, T._head(params, cfg, x, engine),
                               rtol=1e-5, atol=1e-5)
    # the dense layer's MLP counts
    mlp = jax.tree.map(jnp.zeros_like, params["dense_layers"]["mlp"])
    other, _ = forward(
        dict(params, dense_layers=dict(params["dense_layers"], mlp=mlp)),
        cfg, batch, engine)
    assert not np.allclose(got, other)


def test_the_forward_returns_each_expert_layers_choice():
    """With ``routes`` the forward's logits are the same, and each expert
    layer's choice is its router's on that layer's input."""
    from repro.models import transformer as T

    cfg = MOON
    params = init_model(cfg, jax.random.key(0))
    batch = make_batch(cfg, ShapeSpec("s", 32, 2, "train"), seed=1)
    engine = DotEngine()
    want, _ = forward(params, cfg, batch, engine)
    got, _, routes = forward(params, cfg, batch, engine, routes=True)
    np.testing.assert_array_equal(got, want)
    assert routes.shape == (cfg.n_layers - 1, 2, 32, cfg.moe_topk)

    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    cos, sin = rope(jnp.arange(32), cfg.rope_dim, cfg.rope_theta)
    x, _ = T._layer_fwd(x, jax.tree.map(lambda a: a[0],
                                        params["dense_layers"]),
                        cfg, engine, cos, sin, None)
    for i in range(cfg.n_layers - 1):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x, (_, idx) = T._layer_fwd(x, lp, cfg, engine, cos, sin, None,
                                   routes=True)
        np.testing.assert_array_equal(routes[i].reshape(-1, cfg.moe_topk),
                                      idx)


@pytest.mark.parametrize("impl", ["dense", "capacity", "ep"])
@pytest.mark.parametrize("change", [
    {"moe_router": "sigmoid"}, {"moe_held": 2}, {"moe_first_held": 2},
    {"routes": True}])
def test_the_oracle_expert_paths_refuse_what_they_do_not_run(impl, change):
    """Only the dropless path runs the sigmoid router, holds a share of
    the experts or gives the routes; the others raise rather than
    compute a wrong layer."""
    from jax.sharding import Mesh

    change = dict(change)
    routes = change.pop("routes", False)
    cfg = dataclasses.replace(GRANITE, **change)
    p = moe_mod.init_moe(jax.random.key(0), GRANITE)
    x = _rand(1, 1, 8, cfg.d_model)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model")) if impl == "ep" else None
    with pytest.raises(ValueError, match="dropless"):
        moe_mod.moe_ffn(x, p, cfg, ENGINES[0], mesh=mesh, impl=impl,
                        routes=routes)


def test_the_norm_epsilon_comes_from_the_config():
    cfg = MOON
    params = init_model(cfg, jax.random.key(0))
    batch = make_batch(cfg, ShapeSpec("s", 32, 1, "train"), seed=1)
    a, _ = forward(params, cfg, batch)
    b, _ = forward(params, dataclasses.replace(cfg, norm_eps=1.0), batch)
    assert not np.allclose(a, b)


@pytest.mark.parametrize("what", ["decode_state", "prefill", "chunk",
                                  "decode", "serve"])
def test_paths_that_need_a_latent_cache_refuse_mla(what):
    from repro.launch.serve import ServeLoop
    from repro.models import decode_step, init_decode_state, prefill_kv
    from repro.models.transformer import prefill_kv_chunk

    cfg = MOON
    params = init_model(cfg, jax.random.key(0))
    tok = jnp.zeros((1, 4), jnp.int32)
    calls = {
        "decode_state": lambda: init_decode_state(cfg, 1, 8),
        "prefill": lambda: prefill_kv(params, cfg, {}, tok[0]),
        "chunk": lambda: prefill_kv_chunk(params, cfg, {}, tok, [0], [0],
                                          [4]),
        "decode": lambda: decode_step(params, cfg, {}, tok[:, :1], 0),
        "serve": lambda: ServeLoop(cfg, params),
    }
    with pytest.raises(NotImplementedError, match="ROADMAP Reach 5"):
        calls[what]()
    assert not cfg.has_decode


# the sha256 of each dense SMOKE config's forward as StableHLO, without
# debug information, as the program builds it with the layer scan over
# the layer index (each GEMM reading its weight in place from the
# stack): the expert and latent-attention paths leave it as it is
DENSE_PROGRAMS = {
    "qwen3_1_7b": {
        "xla": "9fc6e842f1aa12e44a8e8edea945805071251e967b750677aaf16e282193755b",
        "morton":
            "63ead8fc2f4fb16d917cdca541533901860aff74dff704486e4dd76eb16106ff"},
    "glm4_9b": {
        "xla": "88d6c38b2a4c24f0da142ce50e68610877e2af23e3fd014f22eb23931fa11479",
        "morton":
            "56d8945ba5c330ee06d529f20599af4bc35f30bb98f3efef5368776bd75682b5"},
}


@pytest.mark.parametrize("arch", sorted(DENSE_PROGRAMS))
def test_dense_configs_compile_to_the_same_program(arch):
    cfg = get_smoke_config(arch)
    params = jax.eval_shape(lambda k: init_model(cfg, k), jax.random.key(0))
    batch = jax.eval_shape(
        lambda: make_batch(cfg, ShapeSpec("s", 32, 2, "train"), seed=1))
    for engine in ENGINES:
        text = jax.jit(lambda p, b: forward(p, cfg, b, engine)).lower(
            params, batch).as_text()
        digest = hashlib.sha256(text.encode()).hexdigest()
        assert digest == DENSE_PROGRAMS[arch][engine.schedule]
