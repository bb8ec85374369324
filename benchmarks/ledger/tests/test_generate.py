"""The traffic generator: every seed offers the same work in another
order."""
import collections
import json
import os

import numpy as np
import pytest

import generate
from run import HERE


def traffic(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def multiset(schedule):
    return collections.Counter((len(ids), new) for _d, ids, new in schedule)


def gaps(schedule):
    due = np.array([d for d, _i, _n in schedule])
    return np.diff(np.concatenate([[0.0], due]))


def test_chat_steady_same_multisets_other_order():
    t = traffic("chat-steady")
    a = generate.schedule(t, 45, t["slots"], 1, 50304)
    b = generate.schedule(t, 45, t["slots"], 4_000_000_007 % (2**31 - 1), 50304)
    assert len(a) == len(b) == round(t["arrivals"]["rate_per_s"]
                                     * (45 - t["arrivals"]["drain_s"]))
    assert multiset(a) == multiset(b)
    assert [(len(i), n) for _d, i, n in a] != [(len(i), n) for _d, i, n in b]
    assert np.allclose(np.sort(gaps(a)), np.sort(gaps(b)))
    assert not np.allclose(gaps(a), gaps(b))
    for s in (a, b):      # the gaps sum to the counted part of the window
        assert s[-1][0] == pytest.approx(45 - t["arrivals"]["drain_s"])
        assert all(x[0] <= y[0] for x, y in zip(s, s[1:]))
    assert not np.array_equal(a[0][1], b[0][1])       # other token ids


def test_chat_steady_lengths_are_as_the_file_says():
    t = traffic("chat-steady")
    s = generate.schedule(t, 45, t["slots"], 3, 50304)
    prompts = np.array([len(i) for _d, i, _n in s])
    new = np.array([n for _d, _i, n in s])
    assert prompts.min() >= 32 and prompts.max() <= 512
    assert new.min() >= 32 and new.max() <= 256
    assert 100 <= np.median(prompts) <= 160 and 80 <= np.median(new) <= 115
    assert (prompts + new - 1).max() <= t["capacity"]


def test_backlog_is_due_at_once_and_larger_than_a_window():
    t = traffic("longctx-backlog")
    s = generate.schedule(t, 45, t["slots"], 5, 50304)
    assert len(s) == 6 * 45 + 12 and all(d == 0.0 for d, _i, _n in s)
    prompts = np.array([len(i) for _d, i, _n in s])
    assert prompts.min() > 1024 and prompts.max() <= 1792   # one bucket
    assert max(len(i) + n - 1 for _d, i, n in s) <= t["capacity"]
    other = generate.schedule(t, 45, t["slots"], 6, 50304)
    assert multiset(s) == multiset(other)
