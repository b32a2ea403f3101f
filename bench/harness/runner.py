"""One run of one cell: set-up, the measured window, the output check,
and the result line.  What the window drives comes from the module of
the mix's loop kind (``bench/loops/<loop>.py``, its ``Job``)."""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from pathlib import Path

from harness import profile
from harness.cell import BENCH, ROOT, Cell, loop_module, metric_reader
from harness.model import check_layout
from harness.record import Record

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACING = "/jax/core/compile/jaxpr_trace_duration"


class NoChip(SystemExit):
    """The run cannot stand for the cell on this machine."""


@dataclasses.dataclass
class Reading:
    """What a metric's reader gets: the window's record, and with
    ``--trace 1`` the profiler's trace of it."""
    rec: Record
    trace: profile.Trace | None
    lo: float | None          # the window in the trace, ns
    hi: float | None
    shapes: object            # the family's shapes (harness/cell.py)
    peaks: dict
    cell: Cell
    setup_s: float


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json "
                     f"({sorted(table)}): no peaks, no run")
    return table[kind]


def devices(chips: int, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r}; the "
                     f"benchmark never falls back to another platform")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def set_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` if set,
    else ``<checkout>/.jax_cache``, a fixed path; every program is kept,
    however quickly it compiled."""
    import jax

    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Programs traced and compiled while ``armed``."""

    def __init__(self):
        import jax

        self.armed = False
        self.compiles = self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed:
            if event == BACKEND_COMPILE:
                self.compiles += 1
            elif event == TRACING:
                self.traces += 1


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True, hooks=None,
        control: bool = False, out_dir: Path | None = None,
        peaks: dict | None = None, keep_trace: Path | None = None) -> dict:
    import jax

    devs = devices(cell.chips, require_chip)
    kind = devs[0].device_kind
    peaks = peaks or peaks_for(kind)
    set_compile_cache()
    counter = CompileCounter()

    marks = [("start", t_start),
             ("imports and device init", time.perf_counter())]
    family = cell.family()
    cfg = family.program_config(cell.model)
    s = family.shapes(cell.model)
    params = family.make_params(s, seed, cell.model)
    check_layout(params, cfg)
    jax.block_until_ready(params)
    marks.append(("weights", time.perf_counter()))
    loop = loop_module(cell.mix["loop"])
    job = loop.Job(cell, cfg, params, s, seed, family.reference)
    if hooks is not None:
        hooks(job)
    marks.append(("traffic and program", time.perf_counter()))
    job.warm()
    marks.append(("warm-up", time.perf_counter()))

    out_dir = Path(out_dir or ROOT / "bench_out")
    prof_dir = out_dir / f"trace-{cell.name}-{seed}"
    window_ann = []

    def on_open():
        if trace:
            shutil.rmtree(prof_dir, ignore_errors=True)
            jax.profiler.start_trace(str(prof_dir))
            # made after the start: an annotation made before it is lost
            window_ann.append(jax.profiler.TraceAnnotation("bench.window"))
            window_ann[0].__enter__()
        counter.armed = True

    def on_close():
        counter.armed = False
        if trace:
            window_ann[0].__exit__(None, None, None)
            jax.profiler.stop_trace()

    rec = job.window(seconds, on_open, on_close)
    setup_s = rec.t_open - t_start
    print("set-up: " + ", ".join(f"{n} {b - a:.2f} s" for (_, a), (n, b)
                                  in zip(marks, marks[1:])), file=sys.stderr)
    dts = sorted(st.t1 - st.t0 for st in rec.window()) or [0.0]
    print(f"window: {len(dts)} steps in {rec.seconds:.2f} s, step median "
          f"{dts[len(dts) // 2] * 1e3:.1f} ms, max {dts[-1] * 1e3:.1f} ms; "
          f"{rec.tokens()} tokens", file=sys.stderr)
    stats = devs[0].memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    attempted = job.attempted()
    job.free()
    gc.collect()

    gap = loop.GAP     # the number compared, as the check file names it
    limit = cell.check.get(gap)
    ck = job.check(int(cell.check["sample"]), seed, limit, control)

    metrics: dict = {}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result: dict = {}
    tr = lo = hi = None
    if trace:
        tr = profile.load(str(prof_dir))
        lo, hi = profile.window(tr)
        device.update(busy_s=profile.busy_seconds(tr, lo, hi),
                      window_s=(hi - lo) / 1e9)
    reading = Reading(rec, tr, lo, hi, s, peaks, cell, setup_s)
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = metric_reader(m["name"])(reading)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if trace:
        result["breakdown"] = {
            "device_ops": profile.top_ops(tr, lo, hi),
            "idle_gaps": profile.longest_gaps(tr, lo, hi)}
        if keep_trace is not None:
            # the reduced trace with its record (bench/record_fixture.py)
            Path(keep_trace).write_text(json.dumps(
                {"trace": tr.to_json(), "record": dataclasses.asdict(rec)}))
        shutil.rmtree(prof_dir, ignore_errors=True)
    result = {"correct": bool(ck["correct"]), "attempted": attempted,
              "failed": 0, "metrics": metrics, "device": device,
              **result,
              "diag": {"seed": seed, "window_s": rec.seconds,
                       "steps": len(rec.window()),
                       "compiles_in_window": counter.compiles,
                       "traces_in_window": counter.traces,
                       "checked_rows": ck["rows"],
                       "checked_tokens": ck["tokens"],
                       **({"control_" + gap: ck["control_" + gap]}
                          if control else {})},
              "check": {gap: {"value": ck[gap], "limit": limit}}}
    return result


def report(result: dict) -> None:
    """The compared numbers as the last lines of stderr, then the result
    as the last line of stdout."""
    for name, v in result["check"].items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
