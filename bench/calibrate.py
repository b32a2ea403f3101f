"""Readings for a cell's output-check limit, in one process on the chip:
the program's widest gap over many seeds (the lower reading) and the
float8 control's over a few (the upper reading).

    python bench/calibrate.py --workload qwen3-1.7b.score_2k \\
        --seconds 10 --seeds 101-112 --control 101-103

Each seed is a whole run of the cell (weights, traffic, window, check)
through ``harness.runner``; one line of JSON per run, then a summary.
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b or a) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control", type=_seeds, default=[])
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]
    from harness import cell, runner

    c = cell.load(args.workload)
    gap_name = cell.loop_module(c.mix["loop"]).GAP
    program, control = [], []
    for seed in args.seeds:
        res = runner.run(c, seed, args.seconds, False,
                         t_start=time.perf_counter(),
                         control=seed in args.control)
        gap = res["check"][gap_name]["value"]
        program.append(gap)
        if seed in args.control:
            control.append(res["diag"]["control_" + gap_name])
        print(json.dumps({"seed": seed, "program_gap": gap,
                          "control_gap": res["diag"].get(
                              "control_" + gap_name),
                          "correct": res["correct"],
                          "metrics": res["metrics"],
                          "diag": res["diag"]}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(program),
                      "lower": max(program), "program": program,
                      "upper": min(control) if control else None,
                      "control": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
