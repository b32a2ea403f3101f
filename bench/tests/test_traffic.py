"""The seeded corpus of a scoring mix: same seed, same tokens; another
seed, other tokens of the same sizes; ids inside the vocabulary."""
import json

import numpy as np
import pytest

from harness.cell import BENCH, loop_module

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
VOCAB = 151936


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_corpus(name):
    mix = _mix(name)
    score = loop_module(mix["loop"])
    a = score.corpus(mix, 2**33 + 5, VOCAB)
    b = score.corpus(mix, 2**33 + 5, VOCAB)
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_other_corpus_same_sizes(name):
    mix = _mix(name)
    score = loop_module(mix["loop"])
    a = score.corpus(mix, 1, VOCAB)
    b = score.corpus(mix, 2**31 + 7, VOCAB)
    assert a.shape == b.shape == (mix["windows"], mix["rows"], mix["seq"])
    assert (a != b).mean() > 0.99


@pytest.mark.parametrize("name", MIXES)
def test_ids_inside_the_vocabulary(name):
    mix = _mix(name)
    a = loop_module(mix["loop"]).corpus(mix, 3, VOCAB)
    assert a.dtype == np.int32
    assert a.min() >= 2 and a.max() < VOCAB
    # every id but pad and eos is drawn about equally often
    counts = np.bincount(a.ravel(), minlength=VOCAB)[2:]
    assert counts.std() < 0.1 * counts.mean() + 3 * counts.mean() ** 0.5
