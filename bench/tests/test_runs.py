"""Whole runs at the smoke size on the CPU, with the chip check
skipped: a sound run comes out correct, and each fault a scoring cell
can have, planted in the timed path, makes ``correct`` false.  (A
scoring call keeps no state between calls, and one chip exchanges
nothing between chips, so those two faults cannot happen here.)"""
import json
import os
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

from harness import runner
from harness.cell import BENCH, ROOT

PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]
WORKLOAD = json.loads((ROOT / "BENCHMARK.json").read_text()
                      )["workloads"][0]["name"]


@pytest.fixture(autouse=True)
def _compile_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def _run(cell, tmp_path, hooks=None, trace=False, seed=2**31 + 3, **kw):
    return runner.run(cell, seed, 1.0, trace, t_start=time.perf_counter(),
                      require_chip=False, hooks=hooks, out_dir=tmp_path,
                      peaks=PEAKS, **kw)


def _answer_altered(job):
    """One log-probability of each row moved by 0.01 where it is
    produced."""
    fn = job.fn
    job.fn = lambda p, t: fn(p, t).at[:, 7].add(0.01)


def _half_the_batch(job):
    """Only the first half of each call's rows forwarded; the rest
    copied from it."""
    fn = job.fn

    def broken(p, t):
        half = fn(p, t[: t.shape[0] // 2])
        return half[jnp.arange(t.shape[0]) % half.shape[0]]
    job.fn = broken


def _wrong_window(job):
    """Each call scores the corpus window before the one it was given."""
    fn, corpus = job.fn, job.corpus

    def broken(p, t):
        i = next(k for k in range(len(corpus)) if (corpus[k] == t).all())
        return fn(p, corpus[i - 1])
    job.fn = broken


def test_a_sound_run_is_correct(smoke_cell, tmp_path):
    res = _run(smoke_cell, tmp_path)
    assert res["correct"], res
    assert res["check"]["max_logprob_gap"]["value"] <= 1e-4
    assert res["diag"]["checked_tokens"] == 4 * 255
    assert res["diag"]["compiles_in_window"] == 0
    assert list(res)[-1] == "check"
    assert set(res["metrics"]) == {"setup_s", "scored_tok_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] == 2 * res["diag"]["steps"]


def test_a_traced_run_reports_per_layer_metrics(smoke_cell, tmp_path):
    kept = tmp_path / "kept.json"
    res = _run(smoke_cell, tmp_path, trace=True, keep_trace=kept)
    assert res["correct"], res
    # the CPU runs no Pallas kernel and its trace has no device plane:
    # those readers find nothing and their metrics are left out
    assert set(res["metrics"]) == {"mfu_pct.score"}
    assert 0 < res["metrics"]["mfu_pct.score"]["value"] <= 100
    assert res["device"]["window_s"] > 0
    assert not any(tmp_path.glob("trace-*"))
    # the kept trace cuts down to a fixture of the form test_reduce reads
    import record_fixture
    fx = record_fixture.trim(json.loads(kept.read_text()), WORKLOAD, 2)
    assert len(fx["record"]["steps"]) == 2
    assert fx["trace"]["host"][-1][0] == "bench.window"


@pytest.mark.parametrize("fault", [_answer_altered, _half_the_batch,
                                   _wrong_window])
def test_a_broken_timed_path_is_not_correct(smoke_cell, tmp_path, fault):
    res = _run(smoke_cell, tmp_path, hooks=fault)
    assert not res["correct"], res
    assert res["check"]["max_logprob_gap"]["value"] > 1e-4


def test_no_limit_is_never_correct(smoke_cell, tmp_path):
    smoke_cell.check = dict(smoke_cell.check, max_logprob_gap=None)
    res = _run(smoke_cell, tmp_path)
    assert not res["correct"]
    assert res["check"]["max_logprob_gap"]["limit"] is None


def test_the_control_reads_far_above_the_program(smoke_cell, tmp_path):
    res = _run(smoke_cell, tmp_path, control=True)
    assert res["diag"]["control_max_logprob_gap"] > \
        100 * res["check"]["max_logprob_gap"]["value"]


def _bench_run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOAD, "--seed",
         "1", "--seconds", "1", "--trace", "0", *extra], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return proc.returncode != 0 and not (lines and lines[-1].startswith("{"))


def test_no_tpu_no_result():
    proc = _bench_run(ROOT)
    assert _no_result(proc), proc.stdout
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    assert _no_result(_bench_run(tmp_path))
