"""Record a small trace of a cell on the chip, as a fixture for the CPU
tests of the trace reduction (``bench/tests/test_reduce.py``).

    python bench/record_fixture.py --workload qwen3-1.7b.score_2k \\
        --seed 41 --steps 3 --out bench/tests/fixtures/qwen3.json

A traced run of the cell; the trace and the run record are cut to the
window's first ``--steps`` steps and stored with the per-layer metrics
the readers compute from them there, which the test computes again
without a chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def trim(kept: dict, workload: str, steps: int) -> dict:
    """The first ``steps`` steps of a kept trace and record, with the
    per-layer metrics read from them."""
    from harness import cell, profile, runner
    from harness.record import Record, Step

    c = cell.load(workload)
    tr = profile.Trace.from_json(kept["trace"])
    rec = Record(kept["record"]["t_open"], kept["record"]["t_close"],
                 [Step(**st) for st in kept["record"]["steps"]])
    its = rec.window()[:steps]
    rec.steps, rec.t_close = its, its[-1].t1
    lo, _ = profile.window(tr)
    # the host's waits end as it sees each call's result, in order
    spans = sorted((s, e) for n, s, e in tr.host
                   if n == "bench.wait" and s >= lo)
    hi = spans[steps - 1][1]
    small = profile.Trace(
        {p: [e for e in ops if e[1] > lo and e[0] < hi]
         for p, ops in tr.device_ops.items()},
        [(n, s, min(e, hi)) for n, s, e in tr.host
         if s < hi and e > lo and n != "bench.window"]
        + [("bench.window", lo, hi)])
    reading = runner.Reading(rec, small, lo, hi, c.family().shapes(c.model),
                             runner.peaks_for("TPU v5 lite"), c, 0.0)
    metrics = {}
    for m in c.per_layer:
        v = cell.metric_reader(m["name"])(reading)
        if v is not None:
            metrics[m["name"]] = v
    config = next(w["config"] for w in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["workloads"]
        if w["name"] == workload)
    return {"workload": workload, "config": config, "metrics": metrics,
            "record": dataclasses.asdict(rec), "trace": small.to_json()}


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
    from harness import cell, runner

    with tempfile.TemporaryDirectory() as tmp:
        kept = Path(tmp) / "kept.json"
        runner.run(cell.load(args.workload), args.seed, args.seconds, True,
                   t_start=time.perf_counter(), keep_trace=kept)
        fx = trim(json.loads(kept.read_text()), args.workload, args.steps)
    args.out.write_text(json.dumps(fx))
    print(f"{args.out}: {args.out.stat().st_size} bytes, {fx['metrics']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
