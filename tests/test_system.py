"""End-to-end behaviour tests for the full system."""
import subprocess
import sys
import tempfile


import pytest

import jax

from repro.launch.serve import ServeLoop
from repro.launch.train import main as train_main


def test_tiny_lm_trains_and_loss_drops():
    """The quickstart path: 40 steps on a tiny qwen3, loss must fall."""
    state = train_main([
        "--arch", "qwen3_1_7b", "--smoke", "--steps", "40",
        "--batch", "8", "--seq", "64", "--lr", "3e-3",
        "--log-every", "20"])
    assert state["last_loss"] is not None
    assert state["last_loss"] < 4.5  # ln(128) = 4.85 at init


@pytest.mark.slow
def test_train_resume_from_checkpoint():
    with tempfile.TemporaryDirectory() as d:
        train_main(["--arch", "hymba_1_5b", "--smoke", "--steps", "12",
                    "--batch", "4", "--seq", "32", "--ckpt-dir", d,
                    "--ckpt-every", "6", "--log-every", "6"])
        # second invocation resumes from step 12
        state = train_main(["--arch", "hymba_1_5b", "--smoke", "--steps",
                            "6", "--batch", "4", "--seq", "32",
                            "--ckpt-dir", d, "--ckpt-every", "6",
                            "--log-every", "6"])
        assert state["last_loss"] is not None


def test_serve_loop_emits_tokens():
    from repro.configs import get_smoke_config
    from repro.models import init_model

    cfg = get_smoke_config("qwen3_1_7b")
    params = init_model(cfg, jax.random.PRNGKey(0))
    loop = ServeLoop(cfg, params, slots=2, cache_len=64, temperature=0.0)
    for r in range(3):
        loop.submit(r, [5, 6, 7, 8])
    out = loop.run(max_new=6)
    assert set(out) == {0, 1, 2}
    for toks in out.values():
        assert len(toks) > 4           # emitted beyond the prompt
        assert all(0 <= t < cfg.padded_vocab for t in toks)
    # greedy decode is deterministic across same-admission requests with
    # the same prompt (req 2 is admitted later: its RoPE positions differ
    # under lockstep decode -- see ServeLoop docstring note)
    assert out[0] == out[1]


@pytest.fixture
def isolated_tune_cache(tmp_path, monkeypatch):
    """Objective-driven runs resolve through the autotuner: keep their
    winner cache out of the user's real one."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune.json"))


def test_engine_for_objective_threading(isolated_tune_cache):
    """The launch layer's engine resolution: objective=None keeps the
    historical XLA default; an objective builds (or re-stamps) the
    tuner-routed engine."""
    from repro.launch.steps import _engine_for
    from repro.models import DotEngine

    assert _engine_for(None, None).schedule == "xla"
    eng = DotEngine(schedule="morton")
    assert _engine_for(eng, None) is eng
    auto = _engine_for(None, "energy")
    assert auto.schedule == "auto" and auto.objective == "energy"
    restamped = _engine_for(eng, "edp")
    assert restamped.schedule == "morton" and restamped.objective == "edp"
    assert eng.objective == "time"  # frozen original untouched
    with pytest.raises(ValueError):
        _engine_for(None, "joules")


def test_train_with_edp_objective_smoke(isolated_tune_cache, capsys):
    """Acceptance: train --objective edp --smoke runs end-to-end and the
    summary carries per-step J and EDP."""
    state = train_main([
        "--arch", "qwen3_1_7b", "--smoke", "--steps", "4",
        "--batch", "4", "--seq", "32", "--objective", "edp",
        "--log-every", "2"])
    assert state["last_loss"] is not None
    out = capsys.readouterr().out
    assert "objective=edp" in out
    assert "J/step" in out and "EDP/step" in out


def test_serve_with_energy_objective(isolated_tune_cache):
    """Acceptance: the serve loop under an energy objective decodes
    correctly and accounts per-request joules at the tuned f_scale."""
    from repro.configs import get_smoke_config
    from repro.models import init_model

    cfg = get_smoke_config("qwen3_1_7b")
    params = init_model(cfg, jax.random.PRNGKey(0))
    loop = ServeLoop(cfg, params, slots=2, cache_len=64,
                     objective="energy")
    assert loop.engine.schedule == "auto"
    assert loop.engine.objective == "energy"
    assert 0 < loop.f_scale <= 1.25
    for r in range(2):
        loop.submit(r, [5, 6, 7, 8])
    out = loop.run(max_new=4)
    assert set(out) == {0, 1}
    assert all(loop.request_joules[r] > 0 for r in out)
    assert loop.energy.meta["objective"] == "energy"


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache and nothing
    else is set; otherwise every call yields the same path inside the
    checkout."""
    from pathlib import Path

    from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, \
        enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax")
        assert enable_compile_cache() == "/elsewhere/jax"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = enable_compile_cache()
        assert first == enable_compile_cache() == CHECKOUT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == first
        checkout = Path(__file__).resolve().parents[1]
        assert Path(first) == checkout / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_serve_cli_fails_on_uninjected_degradation(monkeypatch):
    """The serve CLI exits non-zero when its loop degraded to the
    reference kernel without an injected kernel fault; an injected one
    is the chaos path and serves to the end."""
    from repro.kernels import paged_attention as pa
    from repro.launch import serve

    argv = ["--arch", "qwen3_1_7b", "--smoke", "--layout", "paged",
            "--mode", "continuous", "--max-new", "3"]
    prompts = [[5, 6, 7, 8], [9, 10, 11]]
    try:
        loop = serve.main(argv + ["--chaos", "kernel@step=1"],
                          prompts=prompts)
        assert loop._kernel_degraded and not loop.errors
        assert all(len(loop.out[r]) == len(p) + 3
                   for r, p in enumerate(prompts))
        pa.reset_fallback()

        run = serve.ServeLoop.run

        def degrading_run(self, max_new=32):
            self._engage_kernel_fallback("planted")
            return run(self, max_new)

        monkeypatch.setattr(serve.ServeLoop, "run", degrading_run)
        with pytest.raises(SystemExit, match="without an injected"):
            serve.main(argv, prompts=prompts)
    finally:
        pa.reset_fallback()


def test_benchmark_driver_runs():
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "bench_locality"],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
        cwd=".")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "cachegrind/morton" in r.stdout


@pytest.mark.slow
def test_examples_quickstart():
    r = subprocess.run(
        [sys.executable, "examples/quickstart.py"],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "max |err|" in r.stdout
