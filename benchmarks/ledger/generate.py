"""The one generator of request traffic; a mix is its parameters
(``traffic/<mix>.json``).

The offered work is the same in every run: the multiset of (prompt tokens,
new tokens) pairs and the multiset of inter-arrival gaps are drawn from
``multiset_seed``, written in the file, never from ``--seed``. ``--seed``
only permutes the two orders and draws the token ids. Two runs with
different seeds then offer exactly the same tokens at exactly the same mean
rate, in another order.

``prompt_tokens`` / ``new_tokens``: {"dist": "uniform", "min", "max"} or
{"dist": "lognormal", "median", "sigma", "min", "max"} (clipped: a heavy
right tail inside the limits). ``arrivals``: {"process": "backlog",
"per_window_second": r} (everything due at once; r x seconds + slots
requests, more than the window can finish) or {"process": "poisson",
"rate_per_s": r, "ramp_s", "drain_s"} (exponential gaps rescaled so that
the arrivals end ``drain_s`` before the window does).
"""
import math

import numpy as np


def _lengths(spec, n, rng):
    if spec["dist"] == "uniform":
        return rng.randint(spec["min"], spec["max"] + 1, n)
    if spec["dist"] == "lognormal":
        draw = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
        return np.clip(np.rint(draw), spec["min"], spec["max"]).astype(int)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def count(traffic, seconds, slots):
    arr = traffic["arrivals"]
    if arr["process"] == "backlog":
        return int(math.ceil(arr["per_window_second"] * seconds)) + slots
    if arr["process"] == "poisson":
        return max(1, int(round(arr["rate_per_s"]
                                * (seconds - arr["drain_s"]))))
    raise ValueError(f"unknown arrival process {arr['process']!r}")


def schedule(traffic, seconds, slots, run_seed, vocab):
    """[(due_s, prompt ids, new tokens)] in order of arrival; ``due_s`` is
    relative to the start of the window."""
    n = count(traffic, seconds, slots)
    fixed = np.random.RandomState(traffic["multiset_seed"])
    prompts = _lengths(traffic["prompt_tokens"], n, fixed)
    budgets = _lengths(traffic["new_tokens"], n, fixed)
    arr = traffic["arrivals"]
    if arr["process"] == "poisson":
        gaps = fixed.exponential(1.0, n)
        gaps *= (seconds - arr["drain_s"]) / gaps.sum()
    else:
        gaps = np.zeros(n)
    rng = np.random.RandomState(run_seed)
    pairs = rng.permutation(n)
    due = np.cumsum(gaps[rng.permutation(n)])
    return [(float(due[j]),
             rng.randint(0, vocab, int(prompts[i])).astype(np.int32),
             int(budgets[i])) for j, i in enumerate(pairs)]
