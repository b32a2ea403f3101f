"""BENCHMARK.json and the files it names: every cell's configuration,
mix, check and per-layer readers are found by name, the configurations
state what they changed from their source, and the peaks table is keyed
by device kind."""
import json
import re

import pytest

from harness import cell as cell_mod
from harness import runner
from harness.cell import ROOT

B = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_and_keys():
    names = [c["name"] for c in B["configs"]] + \
        [w["name"] for w in B["workloads"]] + \
        [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert {"setup_s"} <= {m["name"] for m in B["end_to_end"]}
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["name"] == "setup_s" or callable(
            cell_mod.metric_reader(m["name"]))
    ends = {m["name"] for m in B["end_to_end"]}
    for m in B["per_layer"]:
        assert m["moves"] in ends
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("w", [w["name"] for w in B["workloads"]])
def test_each_cell_resolves(w):
    c = cell_mod.load(w)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(cell_mod.metric_reader(m["name"]))
    gap = cell_mod.loop_module(c.mix["loop"]).GAP
    # a limit not yet read on the chip is null, and every run is then
    # not correct (test_runs.py)
    assert gap in c.check and c.check["sample"] > 0
    assert c.check[gap] is None or c.check[gap] > 0
    family = c.family()
    family.program_config(c.model)
    s = family.shapes(c.model)
    assert s.vocab > 0 and s.token_flops(1, True) > 0
    assert all(g.role and g.count > 0 and 0 < g.row_share
               for g in s.gemms())


@pytest.mark.parametrize("entry", B["configs"], ids=lambda e: e["name"])
def test_config_files(entry):
    f = json.loads((ROOT / entry["file"]).read_text())
    assert f["source"] == entry["source"]
    assert sorted(f["reduced"]) == sorted(entry["reduced"])
    assert entry["file"].startswith("bench/configs/")
    # a departure from the source is stated with both values, never as
    # a cut of scale
    for key, dep in f["departures"].items():
        assert key in f and f[key] == dep["source"] != dep["run"]
        assert key not in f["reduced"]


def test_peaks_keyed_by_kind():
    assert runner.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(runner.NoChip):
        runner.peaks_for("TPU v9 imaginary")


@pytest.mark.parametrize("entry", B["configs"], ids=lambda e: e["name"])
def test_an_unstated_departure_is_refused(entry):
    """Each departure a file states is one the program needs: without
    it the harness refuses to run the file as if it were the source."""
    f = json.loads((ROOT / entry["file"]).read_text())
    for key in f["departures"]:
        g = dict(f, departures={k: v for k, v in f["departures"].items()
                                if k != key})
        with pytest.raises(SystemExit, match="departure"):
            cell_mod.family_module(f["family"]).program_config(g)


@pytest.mark.parametrize("family", [None, "", "no_such_family"])
def test_a_config_without_a_family_file_is_refused(family):
    """The family is found by the name the configuration file gives; a
    missing name, or one with no file, stops the run and says which."""
    with pytest.raises(SystemExit, match="no_such_family.py" if family
                       else "names no 'family'"):
        cell_mod.family_module(family)
