"""The traffic generator: exact per seed, the same schedule for every seed
with other token ids, and prompt lengths on the cell's buckets."""
import numpy as np
import pytest

from bench.lib import common, traffic

BIG = 2**31 + 977          # seeds past 32 signed bits
OPEN = "sc2-code-open"


def _open(seed, seconds=30.0):
    return traffic.open_loop(common.workload(OPEN), seed, seconds, 49152)


def test_repeats_exactly_per_seed():
    a, b = _open(BIG), _open(BIG)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x["max_new"] == y["max_new"] and x.get("due") == y.get("due")
        np.testing.assert_array_equal(x["prompt"], y["prompt"])


@pytest.mark.parametrize("seconds", [10.0, 30.0])
def test_seeds_share_the_schedule_and_differ_in_tokens(seconds):
    a, b = _open(BIG, seconds), _open(BIG + 1, seconds)
    assert [(len(r["prompt"]), r["max_new"], r["due"]) for r in a] == \
        [(len(r["prompt"]), r["max_new"], r["due"]) for r in b]
    assert all(not np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))


def test_schedule_follows_the_file():
    work = dict(common.workload(OPEN), schedule_seed=1)
    a = _open(3)
    b = traffic.open_loop(work, 3, 30.0, 49152)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]


@pytest.mark.parametrize("seed", [5, BIG])
def test_prompts_round_to_the_buckets(seed):
    work = common.workload(OPEN)
    reqs = _open(seed)
    lens = {len(r["prompt"]) for r in reqs}
    assert lens <= set(work["prompt"]["buckets"])
    assert lens == set(traffic.lengths_used(work))
    outs = [r["max_new"] for r in reqs]
    assert work["output"]["min"] <= min(outs) and max(outs) <= work["output"]["max"]
    assert all(0 <= r["prompt"].min() and r["prompt"].max() < 49152 for r in reqs)


def test_open_loop_fills_the_window_at_the_rate():
    work = common.workload(OPEN)
    for seed in (0, BIG):
        reqs = _open(seed, 30.0)
        due = np.asarray([r["due"] for r in reqs])
        assert len(reqs) == round(work["rate_per_s"] * 30.0)
        assert due[0] == 0.0 and np.all(np.diff(due) >= 0) and due[-1] < 30.0
    # bursty: gamma gaps with CV 2 spread wider than a Poisson process's
    gaps = np.diff([r["due"] for r in _open(BIG, 600.0)])
    assert gaps.std() / gaps.mean() > 1.5


def test_sizes_follow_the_distribution():
    spec = {"kind": "lognormal", "median": 1500, "sigma": 0.6, "min": 256, "max": 3072}
    x = traffic.sizes(spec, 20000, np.random.default_rng(0))
    assert abs(np.median(x) - 1500) < 40
    assert x.min() >= 256 and x.max() <= 3072
    spec = {"kind": "loguniform", "min": 128, "max": 512}
    x = traffic.sizes(spec, 20000, np.random.default_rng(0))
    assert abs(np.median(x) - 256) < 8
