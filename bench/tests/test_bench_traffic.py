"""The one traffic generator: deterministic per seed, the same sizes for
every seed in another order, and the clips and medians each mix states."""
import json
from collections import Counter
from itertools import islice
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

MIXES = sorted((Path(__file__).resolve().parents[1] / "traffic").glob("*.json"))
SEEDS = (3, 2**31 + 12345)


def _mix(path):
    return json.loads(path.read_text())


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_requests(path):
    mix = _mix(path)
    a = list(islice(traffic.specs(mix, SEEDS[1], 1000), 40))
    b = list(islice(traffic.specs(mix, SEEDS[1], 1000), 40))
    assert [s.max_new for s in a] == [s.max_new for s in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_every_seed_gets_the_same_sizes_in_another_order(path):
    mix = _mix(path)
    runs = [list(islice(traffic.specs(mix, s, 1000), mix["pool"])) for s in SEEDS]
    for key in (lambda s: len(s.prompt), lambda s: s.max_new):
        seqs = [[key(s) for s in r] for r in runs]
        assert Counter(seqs[0]) == Counter(seqs[1])
        assert seqs[0] != seqs[1]


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
@pytest.mark.parametrize("which", ["prompt_len", "output_len"])
def test_sizes_keep_clips_and_median(path, which):
    d = _mix(path)[which]
    sizes = traffic.quantile_sizes(d, _mix(path)["pool"])
    assert sizes.min() >= d["min"] and sizes.max() <= d["max"]
    assert abs(np.median(sizes) - d["median"]) <= 1


def test_token_ids_cover_the_vocabulary_uniformly():
    mix = _mix(MIXES[0])
    toks = np.concatenate([s.prompt for s in islice(traffic.specs(mix, 5, 50), 200)])
    assert toks.min() >= 0 and toks.max() < 50
    counts = np.bincount(toks, minlength=50)
    assert counts.min() > 0.5 * counts.mean()


def test_arrivals_keep_the_rate_and_differ_only_in_order():
    mix = {"name": "m", "pool": 512, "rate_per_s": 4.0}
    a = traffic.due_times(mix, SEEDS[0], 512)
    b = traffic.due_times(mix, SEEDS[1], 512)
    assert abs(a[-1] / 512 - 0.25) < 0.01
    assert np.allclose(np.sort(np.diff(a, prepend=0)), np.sort(np.diff(b, prepend=0)))
    assert not np.allclose(a, b)


def test_a_mix_without_a_rate_is_refused():
    with pytest.raises(ValueError):
        traffic.due_times({"name": "m", "pool": 8, "rate_per_s": None}, 1, 4)
