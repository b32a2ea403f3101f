"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload qwen3-1.7b.score_2k --seed 7 \\
        --seconds 10 --trace 0

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic mix, loop kind, metrics and output check live in files of their
own under ``bench/`` (see ``harness/cell.py``).  The run makes its
weights and traffic from ``--seed``, warms up the cell's program, drives
the mix through the program for ``--seconds``, checks a sample of what
the window produced against the plain reference, and prints one JSON
object as the last line of stdout: the end-to-end metrics with
``--trace 0``, the per-layer metrics (from a profiler trace of the
window) with ``--trace 1``.  It exits non-zero, printing no result, where it finds no
TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path


def _process_start() -> float:
    """This process's start on the ``time.perf_counter`` clock."""
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        start = float(Path("/proc/self/stat").read_text()
                      .rsplit(")", 1)[1].split()[19]) / ticks
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return time.perf_counter() - (uptime - start)
    except (OSError, ValueError, IndexError):
        return time.perf_counter()


T_START = _process_start()
ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the TPU runtime's own logs, which would go to a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]
    import repro
    if Path(repro.__file__).resolve().parents[2] != ROOT:
        raise SystemExit(f"the program under test must come from this "
                         f"checkout, not {repro.__file__}")
    from harness import cell, runner

    c = cell.load(args.workload)
    result = runner.run(c, args.seed, args.seconds, bool(args.trace),
                        t_start=T_START)
    runner.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
