"""The generator: every seed asks for the same sizes, in another order."""
import json
from collections import Counter

import numpy as np
import pytest

import traffic
from run import HERE

MIX = json.loads((HERE / "mixes" / "reasoning.json").read_text())


def sizes(reqs):
    return Counter((len(r.prompt), r.max_new) for r in reqs), \
        Counter(len(r.prompt) for r in reqs), Counter(r.max_new for r in reqs)


@pytest.mark.parametrize("seed", [0, 2**31 + 11, 2**33 + 5])
def test_same_sizes_for_every_seed(seed):
    first0, queue0 = traffic.closed_loop(MIX, 1000, 1)
    first, queue = traffic.closed_loop(MIX, 1000, seed)
    assert sizes(first)[1:] == sizes(first0)[1:]
    assert sizes(queue)[1:] == sizes(queue0)[1:]
    assert len(first) == MIX["streams"] and len(queue) == MIX["queue"]


def test_seed_fixes_the_requests():
    a = traffic.closed_loop(MIX, 1000, 42)
    b = traffic.closed_loop(MIX, 1000, 42)
    for ra, rb in zip(a[0] + a[1], b[0] + b[1]):
        assert np.array_equal(ra.prompt, rb.prompt)
        assert ra.max_new == rb.max_new


def test_first_fill_covers_every_prompt_length():
    first, _ = traffic.closed_loop(MIX, 1000, 5)
    assert sorted(len(r.prompt) for r in first) == [4, 4, 8, 16]
    assert all(256 <= r.max_new <= 1024 for r in first)


def test_percentile_nearest_rank():
    assert traffic.percentile(list(range(1, 101)), 95) == 95
    assert traffic.percentile([3.0], 95) == 3.0
