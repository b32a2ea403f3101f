import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

# Distributed-correctness selftests.  Each check runs in its own process
# (tests/test_distributed.py spawns them) because the host device count
# must be set before jax initializes -- see tests/conftest.py.
import sys                      # noqa: E402
import dataclasses              # noqa: E402

import numpy as np              # noqa: E402
import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402

from repro.configs import get_smoke_config          # noqa: E402
from repro.launch.mesh import make_smoke_mesh       # noqa: E402
from repro.launch.steps import build_serve_step, build_train_step, \
    make_train_step                                   # noqa: E402
from repro.models import init_decode_state, init_model, make_batch  # noqa: E402
from repro.models.config import ShapeSpec            # noqa: E402
from repro.models.transformer import decode_step     # noqa: E402
from repro.optim import AdamWConfig                  # noqa: E402
from repro.optim.adamw import init_opt_state         # noqa: E402
import repro.models.config as mcfg                   # noqa: E402

SHAPE = ShapeSpec("st_train", 32, 8, "train")
mcfg.SHAPES[SHAPE.name] = SHAPE


def _train_setup(arch, mesh, **kw):
    cfg = get_smoke_config(arch)
    fn, (p_shd, o_shd, b_shd), _ = build_train_step(
        cfg, mesh, SHAPE.name, opt_cfg=AdamWConfig(peak_lr=1e-2, warmup=0),
        **kw)
    params = init_model(cfg, jax.random.PRNGKey(0),
                        moe_pad=mesh.shape["model"])
    opt = init_opt_state(params)
    if kw.get("pod_compress"):
        pods = mesh.shape.get("pod", 1)
        opt["ef"] = jax.tree.map(
            lambda p: jnp.zeros((pods,) + p.shape, jnp.float32), params)
    batch = make_batch(cfg, SHAPE, seed=1)
    return cfg, fn, (p_shd, o_shd, b_shd), params, opt, batch


def _init_train_state(cfg, moe_pad, p_shd, o_shd):
    """(params, opt state) made in place under the given shardings, so
    a full-width state never passes through one device on its way to
    its shards."""
    params = jax.jit(
        lambda: init_model(cfg, jax.random.PRNGKey(0), moe_pad=moe_pad),
        out_shardings=p_shd)()
    return params, jax.jit(init_opt_state, out_shardings=o_shd)(params)


def check_dp_tp_matches_single(arch="qwen3_1_7b", mesh=None, cfg=None):
    """Sharded step == single-device step (same loss, ~same params).

    ``mesh`` and ``cfg`` default to the (2,2,2) host-device mesh and the
    smoke config.  The sharded result is copied to the host before the
    reference step starts, and the reference donates its state: at the
    published widths one chip holds only one train state."""
    mesh = mesh or make_smoke_mesh((2, 2, 2))
    cfg = cfg or get_smoke_config(arch)
    opt_cfg = AdamWConfig(peak_lr=1e-2, warmup=0)
    fn, (p_shd, o_shd, b_shd), _ = build_train_step(
        cfg, mesh, SHAPE.name, opt_cfg=opt_cfg)
    moe_pad = mesh.shape["model"]
    batch = make_batch(cfg, SHAPE, seed=1)
    params, opt = _init_train_state(cfg, moe_pad, p_shd, o_shd)
    pd, od, md = fn(params, opt, jax.device_put(batch, b_shd))
    lm = float(md["loss"])
    pd = jax.device_get(pd)
    del od

    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    ref_step = jax.jit(make_train_step(cfg, None, opt_cfg),
                       donate_argnums=(0, 1))
    params, opt = _init_train_state(cfg, moe_pad, one, one)
    pr, _, mr = ref_step(params, opt, batch)
    lr_ = float(mr["loss"])
    assert abs(lm - lr_) / max(abs(lr_), 1e-6) < 5e-3, (lm, lr_)
    for a, b in zip(jax.tree.leaves(pd), jax.tree.leaves(pr)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=5e-2, atol=5e-2)
    print(f"OK dp_tp_matches_single {arch} loss {lm:.4f}~{lr_:.4f}")


def check_sp_decode_matches_local(arch="qwen3_1_7b"):
    """Sequence-parallel decode == single-device decode, step by step."""
    mesh = make_smoke_mesh((2, 2, 2))
    cfg = get_smoke_config(arch)
    cfg = dataclasses.replace(cfg, remat=False)
    sh = ShapeSpec("st_dec", 32, 8, "decode")
    mcfg.SHAPES[sh.name] = sh
    fn, (p_shd, s_shd), _ = build_serve_step(cfg, mesh, sh.name,
                                             cache_len=32)
    params = init_model(cfg, jax.random.PRNGKey(0),
                        moe_pad=mesh.shape["model"])
    state_d = jax.device_put(init_decode_state(cfg, 8, 32), s_shd)
    params_d = jax.device_put(params, p_shd)

    state_l = init_decode_state(cfg, 8, 32)
    local = jax.jit(lambda p, s, t, pos: decode_step(p, cfg, s, t, pos))

    rng = np.random.default_rng(0)
    for pos in range(6):
        toks = jnp.asarray(rng.integers(0, cfg.vocab, (8, 1)), jnp.int32)
        ld, state_d = fn(params_d, state_d, toks,
                         jnp.asarray(pos, jnp.int32))
        ll, state_l = local(params, state_l, toks,
                            jnp.asarray(pos, jnp.int32))
        np.testing.assert_allclose(np.asarray(ld), np.asarray(ll),
                                   rtol=3e-3, atol=3e-3)
    print(f"OK sp_decode_matches_local {arch}")


def check_moe_ep_matches_capacity():
    """EP (all_to_all) MoE == single-device capacity dispatch."""
    from repro.models.layers import DotEngine
    from repro.models.moe import init_moe, moe_capacity, moe_ep

    mesh = make_smoke_mesh((2, 2), ("data", "model"))
    cfg = get_smoke_config("granite_moe_1b_a400m")
    key = jax.random.PRNGKey(0)
    params = init_moe(key, cfg, model_axis_size=mesh.shape["model"])
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
    eng = DotEngine()

    from jax.sharding import NamedSharding, PartitionSpec as P
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
    ps = jax.device_put(params, {
        "router": NamedSharding(mesh, P()),
        "w1": NamedSharding(mesh, P("model", None, None)),
        "w3": NamedSharding(mesh, P("model", None, None)),
        "w2": NamedSharding(mesh, P("model", None, None)),
    })
    y_ep, aux_ep = jax.jit(
        lambda x, p: moe_ep(x, p, cfg, mesh, eng, capacity_factor=8.0,
                            data_axes=("data",)))(xs, ps)
    # capacity_factor high enough that neither path drops tokens
    y_c, aux_c = jax.jit(
        lambda x, p: moe_capacity(x, p, cfg, eng, capacity_factor=8.0)
    )(x, params)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_c),
                               rtol=2e-4, atol=2e-4)
    print("OK moe_ep_matches_capacity")


def check_pod_compress_converges(arch="qwen3_1_7b"):
    """EF-bf16 pod sync trains to ~the same loss as exact sync."""
    mesh = make_smoke_mesh((2, 2, 2))
    losses = {}
    for pc in (False, True):
        cfg, fn, shds, params, opt, batch = _train_setup(
            arch, mesh, pod_compress=pc)
        p = jax.device_put(params, shds[0])
        o = jax.device_put(opt, shds[1])
        b = jax.device_put(batch, shds[2])
        for _ in range(8):
            p, o, m = fn(p, o, b)
        losses[pc] = float(m["loss"])
    assert abs(losses[True] - losses[False]) < 0.15 * abs(losses[False]) \
        + 0.05, losses
    print(f"OK pod_compress_converges exact={losses[False]:.4f} "
          f"ef-bf16={losses[True]:.4f}")


def check_checkpoint_elastic_reshard():
    """Save under (2,2,2), restore under (2,2) with new shardings."""
    import tempfile

    from repro.checkpoint import load_checkpoint, save_checkpoint
    from repro.distributed.sharding import param_specs
    from repro.runtime.elastic import plan_elastic_mesh, reshard_tree

    cfg = get_smoke_config("qwen3_1_7b")
    params = init_model(cfg, jax.random.PRNGKey(0), moe_pad=2)
    d = tempfile.mkdtemp()
    save_checkpoint(d, 3, {"params": params})
    # plan: lose 2 chips from a (2,2,2)=8 mesh -> data 2->1
    new_sizes, scale = plan_elastic_mesh(
        ("pod", "data", "model"), (2, 2, 2), failed_chips=2)
    assert new_sizes == (2, 1, 2) and scale == 2, (new_sizes, scale)
    new_mesh = make_smoke_mesh(new_sizes, ("pod", "data", "model"))
    tree, _ = load_checkpoint(d, 3, {"params": params})
    re = reshard_tree(tree["params"], new_mesh, param_specs(cfg))
    for a, b in zip(jax.tree.leaves(re), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("OK checkpoint_elastic_reshard")


def check_train_cli_with_failure():
    """train.py end-to-end on a mesh with an injected failure + resume."""
    import tempfile

    from repro.launch.train import main
    d = tempfile.mkdtemp()
    state = main(["--arch", "qwen3_1_7b", "--smoke", "--steps", "30",
                  "--batch", "8", "--seq", "32", "--mesh", "2,2,2",
                  "--ckpt-dir", d, "--ckpt-every", "10",
                  "--inject-failure-at", "17", "--log-every", "10"])
    assert state["last_loss"] is not None
    print("OK train_cli_with_failure")


def _assert_logits_close(got, want, cfg):
    """f32 configs (the smoke ones): within 3e-3.  bf16 (the published
    widths on the chip): two correct programs round partial sums at
    different points, about nine roundings of u = 2**-8 a layer, and n
    independent roundings grow like sqrt(n) u; so within sqrt(9 L) u of
    the largest |logit| (0.023 of it at 4 layers)."""
    got = np.asarray(got, np.float32)[..., :cfg.vocab]
    want = np.asarray(want, np.float32)[..., :cfg.vocab]
    if cfg.act_jdtype() == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=3e-3, atol=3e-3)
    else:
        rel = np.sqrt(9 * cfg.n_layers) * 2.0 ** -8
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=rel * np.abs(want).max())


def check_paged_sharded_matches_replicated(arch="qwen3_1_7b", mesh=None,
                                           cfg=None):
    """kv-head-sharded paged pool == replicated pool == single device
    (DESIGN.md §15): identical logits under a ragged slot-isolated
    prefill + lockstep greedy decode, with the pool sharding pinned via
    jit in/out shardings so GSPMD cannot quietly replicate it back.
    ``mesh`` and ``cfg`` default to the hilbert-placed (2,2,2)
    host-device mesh and the smoke config.

    ``REPRO_PARITY_SPEC`` (JSON: {"prompts": [[...], ...], "steps": N})
    overrides the deterministic schedule -- the hook the hypothesis
    harness in tests/test_paged_kv.py uses to replay drawn schedules
    through the sharded path."""
    import json

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed import sharding as shd
    from repro.distributed.ctx import mesh_context
    from repro.serve.paged_kv import init_paged_serving
    from repro.serve.state import DecodeState, KVLayout

    spec_env = os.environ.get("REPRO_PARITY_SPEC")
    spec = json.loads(spec_env) if spec_env else {
        "prompts": [[5, 6, 7, 8, 9], [3, 4, 5], [7], [2, 3, 4, 5]],
        "steps": 3}
    prompts, steps = spec["prompts"], int(spec["steps"])
    b = len(prompts)

    # hilbert placement: the parity claim must hold under the curve
    # embedding production would use, not just the identity one
    mesh = mesh or make_smoke_mesh((2, 2, 2), device_order="hilbert")
    cfg = dataclasses.replace(cfg or get_smoke_config(arch), remat=False)
    m = mesh.shape["model"]
    assert cfg.n_kv_heads % m == 0, (cfg.n_kv_heads, m)
    sspec = shd.paged_decode_state_specs(cfg, mesh)
    assert sspec["k_pages"] == P(None, None, "model", None), sspec

    params = init_model(cfg, jax.random.PRNGKey(0))

    def step(p, s, toks, pos, mask):
        with mesh_context(mesh):
            return decode_step(p, cfg, s, toks, pos, row_mask=mask)

    p_shd = shd.to_shardings(shd.param_specs(cfg), mesh)
    s_shd = shd.to_shardings(DecodeState(sspec, KVLayout.PAGED), mesh)
    rep = NamedSharding(mesh, P())
    fn = jax.jit(step,
                 in_shardings=(p_shd, s_shd, rep, rep, rep),
                 out_shardings=(rep, s_shd))
    local = jax.jit(lambda p, s, t, pos, mk:
                    decode_step(p, cfg, s, t, pos, row_mask=mk))

    alloc, state_l = init_paged_serving(cfg, b, 32, page_size=4)
    params_d = jax.device_put(params, p_shd)
    state_d = jax.device_put(
        init_paged_serving(cfg, b, 32, page_size=4)[1], s_shd)

    def both(toks, pos, mask):
        nonlocal state_d, state_l
        state_d["block_tables"] = jnp.asarray(alloc.block_table)
        state_l["block_tables"] = jnp.asarray(alloc.block_table)
        ld, state_d = fn(params_d, state_d, toks,
                         jnp.asarray(pos, jnp.int32), mask)
        ll, state_l = local(params, state_l, toks,
                            jnp.asarray(pos, jnp.int32), mask)
        _assert_logits_close(ld, ll, cfg)
        return ll

    for s, pr in enumerate(prompts):      # ragged slot-isolated prefill
        mask = np.zeros(b, bool)
        mask[s] = True
        for i, tok in enumerate(pr):
            alloc.ensure(s, i)
            toks = np.zeros((b, 1), np.int32)
            toks[s, 0] = tok
            both(jnp.asarray(toks), i, jnp.asarray(mask))
    pos = max(len(p) for p in prompts)
    toks = np.asarray([[p[-1]] for p in prompts], np.int32)
    mask = np.ones(b, bool)
    for _ in range(steps):                # lockstep greedy decode
        for s in range(b):
            alloc.ensure(s, pos)
        ll = both(jnp.asarray(toks), pos, jnp.asarray(mask))
        toks = np.argmax(np.asarray(ll)[:, 0], -1).astype(np.int32)[:, None]
        pos += 1
    print(f"OK paged_sharded_matches_replicated {arch} b={b} steps={steps}")


def main():
    checks = {k[len("check_"):]: v for k, v in globals().items()
              if k.startswith("check_")}
    names = sys.argv[1:] or list(checks)
    for n in names:
        checks[n]()





def check_pipeline_parallel_matches_sequential():
    """GPipe pipeline over the pod axis == sequential scan over layers."""
    import jax.numpy as jnp
    from repro.launch.pp import pipeline_apply

    mesh = make_smoke_mesh((2, 2, 2))
    L, d, m, mb = 4, 16, 3, 8
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (L, d, d)) * (0.5 / np.sqrt(d))
    x = jax.random.normal(jax.random.PRNGKey(1), (m, mb, d))

    def stage_fn(stage_w, xin):
        def body(h, wl):
            return jnp.tanh(h @ wl), None
        out, _ = jax.lax.scan(body, xin, stage_w)
        return out

    y_pp = jax.jit(lambda w, x: pipeline_apply(
        stage_fn, w, x, mesh, axis="pod"))(w, x)

    def seq(xin):
        def body(h, wl):
            return jnp.tanh(h @ wl), None
        out, _ = jax.lax.scan(body, xin, w)
        return out

    y_ref = jax.vmap(seq)(x)
    np.testing.assert_allclose(np.asarray(y_pp), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    print("OK pipeline_parallel_matches_sequential")


if __name__ == "__main__":
    main()
