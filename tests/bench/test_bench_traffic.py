"""The training traffic is deterministic by seed, and every seed gets the
same sizes."""
import json
import pathlib

import numpy as np
import pytest

from bench.lib.markov import markov_batch

ROOT = pathlib.Path(__file__).resolve().parents[2]
BIG = 2 ** 31 + 12345


def test_markov_batches_repeat_by_seed_and_step():
    a = markov_batch(1000, 64, 4, BIG, 3)
    b = markov_batch(1000, 64, 4, BIG, 3)
    c = markov_batch(1000, 64, 4, BIG + 1, 3)
    assert np.array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    # rows differ, and most steps follow the chain
    assert len({tuple(r) for r in a["tokens"]}) == 4
    t = a["tokens"]
    follows = np.mean((t[:, :-1] * 31 + 17) % 1000 == t[:, 1:])
    assert 0.8 < follows < 0.97


@pytest.mark.parametrize("mix", ["train_16x1k", "train_1x4k"])
def test_train_mixes_fix_the_sizes(mix):
    t = json.loads((ROOT / f"bench/traffic/{mix}.json").read_text())
    m = {"vocab_size": 32000}
    a = markov_batch(m["vocab_size"], t["seq"], t["batch"], BIG, 0, t["noise"])
    b = markov_batch(m["vocab_size"], t["seq"], t["batch"], 5, 0, t["noise"])
    assert a["tokens"].shape == b["tokens"].shape == (t["batch"], t["seq"])
    assert not np.array_equal(a["tokens"], b["tokens"])
    assert t["check_steps"] <= t["distinct_batches"]
