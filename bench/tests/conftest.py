"""CPU tests of the benchmark (``pytest bench/tests``): the harness's
pieces, and whole runs at a smoke size with the chip check skipped."""
import functools
import os
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

SMOKE_MODEL = {
    "source": "repro.configs.qwen3_1_7b SMOKE",
    "family": "dense",
    "program": {"arch": "qwen3_1_7b", "preset": "smoke"},
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 128, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": True, "torch_dtype": "float32", "qk_norm": True,
    "engine": {"schedule": "morton"},
}


@functools.cache
def dense():
    """The dense family (``bench/families/dense.py``), loaded as the
    harness loads it."""
    from harness.cell import family_module

    return family_module("dense")


SMOKE_MIX = {"loop": "score", "rows": 2, "seq": 256, "windows": 4096,
             "ahead_s": 0.2}


@pytest.fixture
def smoke_cell():
    """A scoring cell at the smoke size of qwen3-1.7b, reporting every
    metric of BENCHMARK.json."""
    import json

    from harness.cell import Cell

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return Cell(name="smoke.score", chips=1, model=dict(SMOKE_MODEL),
                mix=dict(SMOKE_MIX),
                check={"sample": 4, "max_logprob_gap": 1e-4},
                end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])
