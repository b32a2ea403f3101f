"""Record a small trace of a cell on the chip together with the program
scope of each of its operations, as a fixture for the CPU tests of the
scope readers (``bench/tests/test_scopes.py``), and print where the
device's time went over the whole traced window, part by part.

    python bench/record_scoped_fixture.py --workload qwen3-1.7b.score_2k \\
        --seed 41 --steps 3 \\
        --out bench/tests/fixtures/qwen3-1.7b.score_2k.scoped.json

As ``bench/record_fixture.py``, with two more keys: ``scopes`` (``{op
name: scope path}`` of the ops in the fixture's trace) and
``scoped_metrics`` (the readers that need the scopes, as they read the
fixture).  ``metrics`` keeps the readers that do not.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
# the readers that read the program's scopes (harness/scopes.py)
SCOPED = ("sfc_attn_proj_roofline", "sfc_mlp_roofline", "sfc_head_roofline",
          "attention_core_pct")


def summary(tr, scopes_map: dict, roles, lo: float, hi: float) -> dict:
    """What the acceptance of the scope readers asks of a traced window:
    device seconds by part, the SFC events without exactly one of the
    family's GEMM ``roles``, and the roles' kernel seconds against the
    kernel's."""
    from harness import profile, scopes

    kernel = r"^sfc_matmul_pallas\b"
    parts = scopes.part_seconds(tr, scopes_map, roles, lo, hi)
    busy = profile.busy_seconds(tr, lo, hi)
    sfc = {n for ops in tr.device_ops.values() for _, _, n in ops
           if n.startswith("sfc_matmul_pallas")}
    by_role = {r: scopes.role_seconds(tr, scopes_map, kernel, (r,), roles,
                                      lo, hi)
               for r in roles}
    return {"busy_s": busy, "parts_s": parts,
            "outside_pct": 100.0 * parts.get("outside", 0.0) / busy,
            "sfc_ops_without_role": sorted(
                n for n in sfc
                if scopes.role(scopes_map.get(n, ""), roles) is None),
            "sfc_role_paths": {n: scopes_map.get(n, "") for n in sorted(sfc)},
            "kernel_s_by_role": by_role,
            "kernel_s": profile.kernel_seconds(tr, kernel, lo, hi),
            "outside_ops": profile.top_ops(
                profile.Trace({p: [e for e in ops if scopes.part(
                    scopes_map.get(e[2], ""), roles) == "outside"]
                    for p, ops in tr.device_ops.items()}, []), lo, hi)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]
    import record_fixture
    from harness import cell, profile, runner, scopes

    c = cell.load(args.workload)
    roles = scopes.roles_of(c.family().shapes(c.model))
    with tempfile.TemporaryDirectory() as tmp:
        kept = Path(tmp) / "kept.json"
        result = runner.run(c, args.seed,
                            args.seconds, True, t_start=time.perf_counter(),
                            keep_trace=kept)
        whole = json.loads(kept.read_text())
    tr = profile.Trace.from_json(whole["trace"])
    lo, hi = profile.window(tr)
    scopes_map = scopes.loaded(lo)
    if not scopes_map:
        raise SystemExit("the run's readers found no scope map")
    print(json.dumps({"metrics": result["metrics"], "diag": result["diag"],
                      "window": summary(tr, scopes_map, roles, lo, hi)}))

    fx = record_fixture.trim(whole, args.workload, args.steps)
    ops = {e[2] for evs in fx["trace"]["device_ops"].values() for e in evs}
    fx["scopes"] = {n: p for n, p in scopes_map.items() if n in ops}
    fx["scoped_metrics"] = {k: fx["metrics"].pop(k) for k in list(
        fx["metrics"]) if k.split(".")[0] in SCOPED}
    args.out.write_text(json.dumps(fx))
    print(f"{args.out}: {args.out.stat().st_size} bytes, {fx['metrics']}, "
          f"{fx['scoped_metrics']}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
