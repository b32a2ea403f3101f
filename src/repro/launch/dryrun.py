import os
# compile-only over 512 placeholder host devices: pinned to the CPU
# backend (and so are the --sweep children, which inherit this env), so
# no process of the sweep ever opens a TPU that the host may have
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

# (imports only below the device-count flag -- jax locks it on first init)
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

"""Multi-pod dry-run (assignment §MULTI-POD DRY-RUN).

For every (arch x runnable shape x mesh) cell: lower + compile the real
train/serve step under the production mesh, print memory/cost analysis,
and dump everything the roofline needs to artifacts/dryrun/<cell>.json.
No arrays are allocated: inputs are ShapeDtypeStructs.

Usage:
  python -m repro.launch.dryrun --arch qwen3_1_7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --sweep --jobs 6     # everything, parallel
"""


def _json_safe(d):
    out = {}
    for k, v in (d or {}).items():
        try:
            out[str(k)] = float(v)
        except (TypeError, ValueError):
            out[str(k)] = str(v)
    return out


def run_cell(arch: str, shape: str, mesh_kind: str, outdir: str,
             grad_accum: int | None = None, device_order: str = "rowmajor",
             extra_tag: str = "", audit: bool = False) -> dict:
    from repro.configs import get_config
    from repro.launch.hlo import analyze_hlo, collective_bytes, op_census
    from repro.launch.mesh import make_production_mesh, mesh_chips
    from repro.launch.steps import build_prefill_step, build_serve_step, \
        build_train_step
    from repro.models import SHAPES

    cfg = get_config(arch)
    if shape not in cfg.runnable_shapes():
        return {"arch": arch, "shape": shape, "mesh": mesh_kind,
                "status": "skipped",
                "reason": f"not runnable for {cfg.family} (DESIGN.md §4)"}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"),
                                device_order=device_order)
    spec = SHAPES[shape]
    t0 = time.time()
    if spec.kind == "decode":
        fn, _, (params_abs, state_abs, tokens_abs, pos_abs) = \
            build_serve_step(cfg, mesh, shape)
        lowered = fn.lower(params_abs, state_abs, tokens_abs, pos_abs)
    elif spec.kind == "prefill":
        fn, _, (params_abs, batch_abs) = build_prefill_step(cfg, mesh, shape)
        lowered = fn.lower(params_abs, batch_abs)
    else:
        ga = grad_accum
        if ga is None:
            # per-arch microbatching (§Perf iteration C2/C3): the smallest
            # accumulation that bounds the per-chip saved-activation stack
            # (full-remat stack = L*S*B_loc*d*2B/ga; ga also multiplies
            # per-microbatch weight re-reads, so smaller is faster)
            ga = {"llava_next_34b": 8, "deepseek_coder_33b": 8,
                  "glm4_9b": 4}.get(arch, 4)
        fn, _, (params_abs, opt_abs, batch_abs) = build_train_step(
            cfg, mesh, shape, grad_accum=ga,
            pod_compress=(mesh_kind == "multi"))
        lowered = fn.lower(params_abs, opt_abs, batch_abs)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # newer jax: one dict per module
        cost = cost[0] if cost else {}
    print(f"[{arch} x {shape} x {mesh_kind}] memory_analysis:", mem)
    print(f"[{arch} x {shape} x {mesh_kind}] cost_analysis: flops="
          f"{(cost or {}).get('flops', float('nan')):.3e} "
          f"bytes={(cost or {}).get('bytes accessed', float('nan')):.3e}")
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)            # entry-level (unweighted)
    census = op_census(hlo)
    weighted = analyze_hlo(hlo)             # trip-count-weighted (roofline)

    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_kind,
        "status": "ok",
        "chips": mesh_chips(mesh),
        "mesh_shape": dict(zip(mesh.axis_names,
                               mesh.devices.shape)),
        "kind": spec.kind,
        "seq_len": spec.seq_len, "global_batch": spec.global_batch,
        "grad_accum": ga if spec.kind == "train" else None,
        "family": cfg.family,
        "params": cfg.params_count(),
        "active_params": cfg.active_params_count(),
        "cost_analysis": _json_safe(cost),
        "memory_analysis": {
            a: getattr(mem, a)
            for a in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, a)
        } if mem is not None else {},
        "collectives": coll,
        "op_census": census,
        "weighted": {
            "flops_per_chip": weighted["flops"],
            "traffic_bytes_per_chip": weighted["traffic_bytes"],
            "traffic_bytes_upper_per_chip": weighted["traffic_bytes_upper"],
            "collectives": weighted["collectives"],
            "whiles": weighted["whiles"],
        },
        "t_lower_s": t_lower, "t_compile_s": t_compile,
        "device_order": device_order,
    }
    if audit:
        # static lint pass over the compiled module (DESIGN.md §13.3).
        # Decode steps run the generation hot loop, so a host transfer
        # there is an error; train/prefill steps on CPU backends are
        # legitimately unfused, so epilogue round trips stay warnings.
        from repro.analysis.hlo_audit import audit_hlo
        rep = audit_hlo(
            hlo, subject=f"{arch}/{shape}/{mesh_kind}",
            forbid_host_transfers=(spec.kind == "decode"))
        rec["audit"] = rep.to_dict()
        for f in rep.findings:
            print(f"[audit] {f.severity}: {f.code} -- {f.message}")
        if not rep.ok:
            rec["status"] = "audit-failed"
    os.makedirs(outdir, exist_ok=True)
    tag = f"{arch}__{shape}__{mesh_kind}" + (
        f"__{extra_tag}" if extra_tag else "")
    with open(os.path.join(outdir, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def _sweep(args):
    """Fan the full (arch x shape x mesh) grid out over subprocesses."""
    import itertools
    import subprocess
    import sys

    from repro.configs import ARCHS
    from repro.models import SHAPES

    cells = [(a, s, m) for a, s, m in itertools.product(
        ARCHS, SHAPES, ("single", "multi"))]
    if args.mesh != "both":
        cells = [c for c in cells if c[2] == args.mesh]
    procs: list = []
    results = []

    def reap(block=False):
        for p, cell, fh in procs[:]:
            if p.poll() is not None or block:
                p.wait()
                fh.close()
                procs.remove((p, cell, fh))
                results.append((cell, p.returncode))
                status = "ok" if p.returncode == 0 else "FAIL"
                print(f"[sweep] {cell} -> {status}", flush=True)

    logs = os.path.join(args.out, "logs")
    os.makedirs(logs, exist_ok=True)
    for arch, shape, mesh in cells:
        while len(procs) >= args.jobs:
            reap()
            time.sleep(0.5)
        tag = f"{arch}__{shape}__{mesh}"
        # held open across the child's lifetime; closed in reap()
        fh = open(os.path.join(logs, tag + ".log"), "w")  # noqa: SIM115
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape, "--mesh", mesh,
               "--out", args.out]
        p = subprocess.Popen(cmd, stdout=fh, stderr=subprocess.STDOUT,
                             env=os.environ)
        procs.append((p, (arch, shape, mesh), fh))
    while procs:
        reap()
        time.sleep(0.5)
    fails = [c for c, rc in results if rc != 0]
    print(f"[sweep] done: {len(results) - len(fails)} ok, "
          f"{len(fails)} failed {fails}")
    return 1 if fails else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--grad-accum", type=int, default=None)
    ap.add_argument("--device-order", default="rowmajor",
                    choices=("rowmajor", "hilbert"))
    ap.add_argument("--tag", default="")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--audit", action="store_true",
                    help="run the HLO traffic auditor over each "
                         "compiled step; exit 1 on error findings")
    args = ap.parse_args()

    if args.sweep:
        raise SystemExit(_sweep(args))

    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    for mk in meshes:
        try:
            rec = run_cell(args.arch, args.shape, mk, args.out,
                           grad_accum=args.grad_accum,
                           device_order=args.device_order,
                           extra_tag=args.tag, audit=args.audit)
            print(f"[dryrun] {args.arch} x {args.shape} x {mk}: "
                  f"{rec['status']}")
            if rec["status"] == "audit-failed":
                raise SystemExit(1)
        except Exception:
            traceback.print_exc()
            raise SystemExit(1) from None


if __name__ == "__main__":
    main()
