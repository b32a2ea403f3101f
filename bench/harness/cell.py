"""A cell, resolved from ``BENCHMARK.json`` by its name.

Everything that belongs to one configuration, one traffic mix, one
per-layer metric or one cell's output check lives in a file of its own,
found by name:

    bench/configs/<config>.json     sizes, engine, source, departures,
                                    and its ``family``
    bench/families/<family>.py      one model family: its sizes, the
                                    program's config, the weights, the
                                    GEMMs of a forward, the reference
    bench/traffic/<traffic>.json    the mix: data for its loop kind
    bench/loops/<loop>.py           one loop kind: what the window
                                    drives, and its output check
    bench/metrics/<metric>.py       one metric's reader (a metric
                                    ``a.b`` falls back to ``a.py``)
    bench/checks/<workload>.json    the sample and limit of the check

A family module provides ``shapes(model)`` (the file's sizes; the
object has ``vocab``, ``token_flops(context, head)`` and ``gemms()``,
the forward's GEMMs as ``harness.model.Gemm`` records),
``program_config(model)``, ``make_params(s, seed, model)`` and
``reference(params, tokens, s, mode)`` (next-token log-probabilities in
float32, or the float8 control with ``mode="fp8"``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    model: dict          # bench/configs/<config>.json
    mix: dict            # bench/traffic/<traffic>.json
    check: dict          # bench/checks/<workload>.json
    end_to_end: list     # BENCHMARK.json metric entries this cell reports
    per_layer: list

    def family(self):
        """The module of the configuration's model family."""
        return family_module(self.model.get("family"))


def _reported_in(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    w = cells[workload]
    base = root / "bench"
    return Cell(
        name=workload, chips=int(w["chips"]),
        model=json.loads((base / "configs" / f"{w['config']}.json")
                         .read_text()),
        mix=json.loads((base / "traffic" / f"{w['traffic']}.json")
                       .read_text()),
        check=json.loads((base / "checks" / f"{workload}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"]
                    if _reported_in(m, workload)],
        per_layer=[m for m in bench["per_layer"]
                   if _reported_in(m, workload)])


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    # registered before it runs, as an import would: a dataclass looks
    # its module up while it is made
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def family_module(name: str | None, root: Path = ROOT):
    """``bench/families/<name>.py``, the model family a configuration
    file names under ``family``."""
    if not name:
        raise SystemExit("the configuration file names no 'family'")
    path = root / "bench" / "families" / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"the configuration's family {name!r} has no "
                         f"file {path}")
    return _module(path, f"bench_family_{name}")


def loop_module(kind: str, root: Path = ROOT):
    """``bench/loops/<kind>.py``, whose ``Job`` drives a window."""
    path = root / "bench" / "loops" / f"{kind}.py"
    if not path.is_file():
        raise SystemExit(f"no loop kind {kind!r} under {path.parent}")
    return _module(path, f"bench_loop_{kind}")


def metric_reader(name: str, root: Path = ROOT):
    """The ``read`` function of ``bench/metrics/<name>.py``, or of the
    file named by the part before the first dot (``mfu_pct.decode`` ->
    ``mfu_pct.py``), so one reader serves a quantity split by cell
    family."""
    base = root / "bench" / "metrics"
    for stem in (name, name.split(".")[0]):
        path = base / f"{stem}.py"
        if path.is_file():
            return _module(path, f"bench_metric_{stem.replace('.', '_')}"
                           ).read
    raise SystemExit(f"no reader for per-layer metric {name!r} under "
                     f"{base}")
