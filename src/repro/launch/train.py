"""End-to-end training driver.

Composes: config registry (--arch), synthetic packed data + prefetch,
sharded train step (pjit), AdamW(+ZeRO-1), async checkpointing with
auto-resume, fault-tolerant step executor (retry-from-checkpoint),
straggler monitor.  Runs for real at smoke scale on CPU and is the same
code path the production mesh lowers (dryrun.py).

  PYTHONPATH=src python -m repro.launch.train --arch qwen3_1_7b --smoke \
      --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.checkpoint import AsyncCheckpointer, latest_step, load_checkpoint
from repro.configs import get_config, get_smoke_config
from repro.core.energy import hw_for_device
from repro.data import PackedSyntheticData, PrefetchLoader
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import build_train_step
from repro.models import fused_epilogue_savings_bytes, init_model
from repro.models.config import ShapeSpec
from repro.obs import Tracer, default_registry, null_registry, \
    set_default_tracer, trace_span
from repro.optim import AdamWConfig
from repro.optim.adamw import init_opt_state
from repro.power import EnergyMeter, EnergyReport, WorkloadHints, \
    detect_backend
from repro.runtime import FailureInjector, StepExecutor, StragglerMonitor
from repro.tune.objective import OBJECTIVES


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--mesh", default=None,
                    help="e.g. '2,2,2' to build a (pod,data,model) mesh")
    ap.add_argument("--device-order", default="rowmajor",
                    help="embed the logical mesh on the physical torus "
                         "along this curve (rowmajor|hilbert|morton); "
                         "ring collectives then step between physically "
                         "nearby chips (DESIGN.md §15)")
    ap.add_argument("--pod-compress", action="store_true")
    ap.add_argument("--inject-failure-at", type=int, default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--power-backend", default=None,
                    choices=["rapl", "nvml", "model"],
                    help="pin the energy telemetry backend (default: auto)")
    ap.add_argument("--energy-report", default=None, metavar="PATH",
                    help="write the per-step energy report JSON here")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write the span trace as JSONL here (convert / "
                         "validate with python -m repro.obs.trace)")
    ap.add_argument("--metrics-report", default=None, metavar="PATH",
                    help="write the metrics registry snapshot JSON here")
    ap.add_argument("--no-obs", action="store_true",
                    help="disable the metrics + span layer")
    ap.add_argument("--objective", default=None, choices=list(OBJECTIVES),
                    help="route every GEMM through the autotuner "
                         "adjudicated on this metric (DESIGN.md §8); "
                         "default keeps the XLA engine")
    args = ap.parse_args(argv)
    enable_compile_cache()

    # observability (DESIGN.md §12): per-step spans (energy attributed
    # to them by the meter) + a step-latency histogram in the process
    # registry, both written out on request
    tracer = None
    if args.trace and not args.no_obs:
        tracer = Tracer(enabled=True)
        set_default_tracer(tracer)
    metrics = null_registry() if args.no_obs else default_registry()
    m_step_ms = metrics.histogram("train.step_ms")

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    shape = ShapeSpec("cli", seq_len=args.seq, global_batch=args.batch,
                      kind="train")
    import repro.models.config as mcfg
    mcfg.SHAPES[shape.name] = shape

    mesh = None
    if args.mesh:
        from repro.launch.mesh import link_distance, make_smoke_mesh
        dims = tuple(int(x) for x in args.mesh.split(","))
        names = ("pod", "data", "model")[-len(dims):]
        # same validated placement path as production: unknown orders
        # raise here instead of silently training on a row-major mesh
        mesh = make_smoke_mesh(dims, names, device_order=args.device_order)
        if args.device_order != "rowmajor":
            hops = link_distance(mesh)
            print("[train] device_order=%s ring-neighbour hops %s" % (
                args.device_order,
                " ".join(f"{a}={h:.2f}" for a, h in hops.items())))

    opt_cfg = AdamWConfig(peak_lr=args.lr, warmup=min(10, args.steps // 5),
                          total_steps=args.steps)

    if mesh is not None:
        step_fn, (p_shd, o_shd, b_shd), _ = build_train_step(
            cfg, mesh, shape.name, opt_cfg=opt_cfg,
            grad_accum=args.grad_accum, pod_compress=args.pod_compress,
            objective=args.objective)
        moe_pad = mesh.shape["model"]
    else:
        from repro.launch.steps import make_train_step
        step_fn = jax.jit(make_train_step(cfg, None, opt_cfg,
                                          grad_accum=args.grad_accum,
                                          objective=args.objective))
        p_shd = o_shd = b_shd = None
        moe_pad = None

    params = init_model(cfg, jax.random.PRNGKey(args.seed), moe_pad=moe_pad)
    opt_state = init_opt_state(params)
    if args.pod_compress and mesh is not None and "pod" in mesh.axis_names:
        import jax.numpy as jnp
        pods = mesh.shape["pod"]
        opt_state["ef"] = jax.tree.map(
            lambda p: jnp.zeros((pods,) + p.shape, jnp.float32), params)
    if p_shd is not None:
        params = jax.device_put(params, p_shd)
        opt_state = jax.device_put(opt_state, o_shd)

    ckpt = AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if args.ckpt_dir:
        last = latest_step(args.ckpt_dir)
        if last is not None:
            tree, meta = load_checkpoint(
                args.ckpt_dir, last, {"params": params, "opt": opt_state})
            params, opt_state = tree["params"], tree["opt"]
            if p_shd is not None:
                params = jax.device_put(params, p_shd)
                opt_state = jax.device_put(opt_state, o_shd)
            start = last
            print(f"[train] resumed from step {start}")

    data = PackedSyntheticData(cfg, shape, seed=args.seed)
    put = (lambda b: jax.device_put(b, b_shd)) if b_shd is not None else \
        (lambda b: b)
    loader = PrefetchLoader(data, start_step=start, put_fn=put)
    loader_iter = iter(loader)

    injector = FailureInjector(
        {args.inject_failure_at: "simulated-node-loss"}
        if args.inject_failure_at is not None else {})
    monitor = StragglerMonitor()
    state = {"params": params, "opt": opt_state, "last_loss": None}

    # per-step energy telemetry (DESIGN.md §8): counters where the host
    # has them, the analytic model (static power x measured step time +
    # 6*N*tokens FLOPs) in counter-less containers
    hw = hw_for_device(jax.devices()[0])
    power = detect_backend(args.power_backend, hw=hw)
    n_params = sum(int(p.size) for p in jax.tree.leaves(params))
    step_flops = 6.0 * n_params * args.batch * args.seq
    # fused epilogues (DESIGN.md §9): HBM passes the forward no longer
    # makes -- stamped into the report + summary so J/step is attributable
    ep_saved = fused_epilogue_savings_bytes(cfg, args.batch * args.seq)
    # DVFS hints, resolved per GEMM shape (ROADMAP "per-shape f_scale"):
    # the attention out-projection, the MLP up-projection and the vocab
    # head tune under different buckets/epilogues and may land on
    # different operating points -- the report carries each, the scalar
    # hint keeps the dominant projection's point (historical behaviour)
    f_scale = 1.0
    f_scales = {"proj": 1.0, "attn": 1.0, "mlp": 1.0, "vocab": 1.0}
    if args.objective:
        from repro.tune import EpilogueSpec, resolved_f_scale
        tokens = args.batch * args.seq
        # same dtype AND epilogue the engine's GEMMs resolve under, so
        # each hint reads the winner the tuner actually selected, not a
        # sibling bucket: out-proj / down-proj carry a fused residual
        # (.../ep=res), the MLP up-proj a fused silu (.../ep=silu) --
        # DESIGN.md §9
        f_scales["proj"] = resolved_f_scale(
            tokens, cfg.d_model, cfg.d_model, cfg.act_dtype,
            objective=args.objective, epilogue=EpilogueSpec(residual=True))
        if cfg.has_attention and cfg.n_heads:
            f_scales["attn"] = resolved_f_scale(
                tokens, cfg.d_model, cfg.n_heads * cfg.d_head,
                cfg.act_dtype, objective=args.objective,
                epilogue=EpilogueSpec(residual=True))
        if cfg.d_ff:
            f_scales["mlp"] = resolved_f_scale(
                tokens, cfg.d_ff, cfg.d_model, cfg.act_dtype,
                objective=args.objective,
                epilogue=EpilogueSpec(activation="silu"))
        if cfg.vocab:
            f_scales["vocab"] = resolved_f_scale(
                tokens, cfg.padded_vocab, cfg.d_model, cfg.act_dtype,
                objective=args.objective)
        f_scale = f_scales["proj"]
    step_hints = WorkloadHints(flops=step_flops, f_scale=f_scale)
    energy = EnergyReport(backend=power.name, meta={
        "driver": "train", "arch": args.arch, "steps": args.steps,
        "batch": args.batch, "seq": args.seq, "params": n_params,
        "objective": args.objective or "time", "f_scale": f_scale,
        "f_scale_per_shape": dict(f_scales),
        "fused_epilogue_saved_bytes_fwd": ep_saved})

    def one_step(state, step):
        _, batch = next(loader_iter)
        t0 = time.perf_counter()
        with trace_span("train.step", step=step), \
                EnergyMeter(f"step-{step}", backend=power, reporter=energy,
                            hints=step_hints) as em:
            p, o, metrics = step_fn(state["params"], state["opt"], batch)
            state = {"params": p, "opt": o,
                     "last_loss": float(metrics["loss"])}
        m_step_ms.observe((time.perf_counter() - t0) * 1e3)
        if step % args.log_every == 0 or step == start + args.steps - 1:
            print(f"[train] step {step} loss {metrics['loss']:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"E {em.reading.joules:.2f}J "
                  f"EDP {em.reading.edp:.3e}Js", flush=True)
        if ckpt and (step + 1) % args.ckpt_every == 0:
            ckpt.save(step + 1, {"params": p, "opt": o})
        return state

    def restore(step):
        if not args.ckpt_dir:
            return state
        ckpt.wait()
        last = latest_step(args.ckpt_dir)
        if last is None:
            return state
        tree, _ = load_checkpoint(
            args.ckpt_dir, last,
            {"params": state["params"], "opt": state["opt"]})
        print(f"[train] restored step {last} after failure", flush=True)
        out = {"params": tree["params"], "opt": tree["opt"],
               "last_loss": None}
        if p_shd is not None:
            out["params"] = jax.device_put(out["params"], p_shd)
            out["opt"] = jax.device_put(out["opt"], o_shd)
        return out

    executor = StepExecutor(one_step, restore, injector=injector,
                            monitor=monitor, metrics=metrics)
    t0 = time.time()
    final_state, end_step = executor.run(state, start, args.steps)
    dt = time.time() - t0
    totals = energy.totals()
    print(f"[train] done: {args.steps} steps in {dt:.1f}s "
          f"({dt / max(args.steps, 1) * 1e3:.0f} ms/step), "
          f"final loss {final_state['last_loss']:.4f}, "
          f"retries {len(executor.retries)}, "
          f"straggler events {len(monitor.events)}")
    n_steps = max(args.steps, 1)
    print(f"[train] energy ({power.name}, objective="
          f"{args.objective or 'time'}, f_scale proj {f_scales['proj']:g}"
          f" / attn {f_scales['attn']:g} / mlp {f_scales['mlp']:g} / "
          f"vocab {f_scales['vocab']:g}): "
          f"{totals['joules']:.1f} J total, "
          f"{totals['joules'] / n_steps:.2f} J/step, "
          f"{totals['joules'] * totals['seconds'] / n_steps ** 2:.3e} "
          f"Js EDP/step, "
          f"{totals['joules'] / max(totals['seconds'], 1e-9):.1f} W avg")
    print(f"[train] fused epilogues (DESIGN.md §9): "
          f"~{ep_saved / 1e6:.1f} MB/fwd HBM traffic eliminated "
          f"(~{ep_saved * hw.e_hbm:.3f} J/fwd at modeled e_hbm)")
    if args.energy_report:
        energy.write(args.energy_report)
        print(f"[train] wrote energy report to {args.energy_report}")
    if args.metrics_report:
        metrics.write(args.metrics_report)
        print(f"[train] wrote metrics snapshot to {args.metrics_report}")
    if args.trace and tracer is not None:
        tracer.write_jsonl(args.trace)
        print(f"[train] wrote {len(tracer.events)} trace events to "
              f"{args.trace}")
    loader.close()
    if ckpt:
        ckpt.close()
    return final_state


if __name__ == "__main__":
    main()
