"""A model family is files only: in a copy of ``BENCHMARK.json`` and
``bench/``, a mixture-of-experts family (``family_moe.py``, the
program's ``granite_moe_1b_a400m`` SMOKE preset) with its
configuration, mix, check and cell, added beside what is there, runs
correct on the CPU and reports the whole step's share of the peak, and
no file that was there changes."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from harness.cell import BENCH, ROOT

HERE = Path(__file__).resolve().parent
CELL = "granite-moe-smoke.score_256"
CONFIG = {
    "source": "repro.configs.granite_moe_1b_a400m SMOKE",
    "family": "moe",
    "program": {"arch": "granite_moe_1b_a400m", "preset": "smoke"},
    "hidden_size": 64, "intermediate_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_local_experts": 8, "num_experts_per_tok": 2, "vocab_size": 128,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "reduced": [], "departures": {}, "engine": {"schedule": "morton"},
}
# 256 tokens a call: the program's MoE takes its exact all-experts path
# (moe_dense), not capacity dispatch, which drops tokens
MIX = {"loop": "score", "rows": 1, "seq": 256, "windows": 4096,
       "ahead_s": 0.2}
CHECK = {"sample": 4, "max_logprob_gap": 1e-4}
RUN = """
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import harness
from harness import cell, runner
c = cell.load(sys.argv[3])
peaks = json.loads((cell.BENCH / "peaks.json").read_text())["devices"]
res = runner.run(c, 2**31 + 11, 1.0, True, t_start=time.perf_counter(),
                 require_chip=False, out_dir=sys.argv[4],
                 peaks=peaks["TPU v5 lite"])
res["harness_file"] = harness.__file__
print(json.dumps(res))
"""


def _digests(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()}


def _add(bench: dict) -> dict:
    """BENCHMARK.json with the cell added: a configuration, a cell, and
    the cell among those that report the step programs' share."""
    out = json.loads(json.dumps(bench))
    out["configs"].append({
        "name": "granite-moe-smoke", "source": CONFIG["source"],
        "file": "bench/configs/granite-moe-smoke.json", "reduced": [],
        "why": "token-choice top-2 of 8 experts, the harness's test family"})
    out["workloads"].append({
        "name": CELL, "config": "granite-moe-smoke", "traffic": "score_256",
        "chips": 1, "why": "256-token windows through the MoE family"})
    for m in out["per_layer"]:
        if m["name"] == "mfu_pct.score":
            m["workloads"].append(CELL)
    return out


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    (root / "BENCHMARK.json").write_text(json.dumps(_add(bench), indent=1))
    b = root / "bench"
    shutil.copy(HERE / "family_moe.py", b / "families" / "moe.py")
    (b / "configs" / "granite-moe-smoke.json").write_text(json.dumps(CONFIG))
    (b / "traffic" / "score_256.json").write_text(json.dumps(MIX))
    (b / "checks" / f"{CELL}.json").write_text(json.dumps(CHECK))
    return root, before, bench


def test_the_family_is_files_only(copy, tmp_path):
    root, before, bench = copy
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"))
    proc = subprocess.run(
        [sys.executable, "-c", RUN, str(root / "bench"), str(ROOT / "src"),
         CELL, str(tmp_path / "out")], cwd=root, env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    # the copy's harness ran, and found the family by the file's name
    assert Path(res["harness_file"]).is_relative_to(root)
    assert res["correct"], res
    assert res["check"]["max_logprob_gap"]["value"] <= 1e-4
    assert 0 < res["metrics"]["mfu_pct.score"]["value"] <= 100

    # every file that was there is as it was; BENCHMARK.json only gained
    after = _digests(root)
    changed = sorted(p for p in before if after.get(p) != before[p])
    assert changed == ["BENCHMARK.json"]
    assert json.loads((root / "BENCHMARK.json").read_text()) == _add(bench)

