"""The traffic generator: same seed same traffic, the stated
distributions, and the same multiset of sizes for every seed."""
import collections

import numpy as np
import pytest

from bench import traffic

SEED = 2**31 + 5


@pytest.mark.parametrize("mix", ["decode-backlog", "chat-prefix"])
def test_lm_requests_deterministic_per_seed(mix):
    m = traffic.load(mix)
    a = traffic.lm_requests(m, SEED, 50304, 64)
    b = traffic.lm_requests(m, SEED, 50304, 64)
    c = traffic.lm_requests(m, SEED + 1, 50304, 64)
    for x, y in zip(a, b):
        assert x["due"] == y["due"] and x["max_new"] == y["max_new"]
        np.testing.assert_array_equal(x["prompt"], y["prompt"])
    assert any(not np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, c))


def test_sizes_are_the_same_multiset_for_every_seed():
    m = traffic.load("decode-backlog")
    n = 4 * traffic.STRATUM

    def sizes(seed):
        reqs = traffic.lm_requests(m, seed, 50304, n)
        return (sorted(len(r["prompt"]) for r in reqs),
                sorted(r["max_new"] for r in reqs))
    assert sizes(SEED) == sizes(SEED + 7) == sizes(3)
    order = [r["max_new"] for r in traffic.lm_requests(m, SEED, 50304, n)]
    other = [r["max_new"] for r in traffic.lm_requests(m, 3, 50304, n)]
    assert order != other


def test_decode_backlog_ranges():
    m = traffic.load("decode-backlog")
    reqs = traffic.lm_requests(m, SEED, 50304, 1024)
    lens = np.array([len(r["prompt"]) for r in reqs])
    outs = np.array([r["max_new"] for r in reqs])
    first = m["arrival"]["min_pending"]
    assert first == 16
    assert lens.min() >= 16 and lens.max() <= 64
    assert outs[:first].min() >= 1 and outs[:first].max() <= 448
    assert outs[first:].min() >= 256 and outs[first:].max() <= 448
    assert abs(outs[first:].mean() - 352) < 2 and abs(lens.mean() - 40) < 1
    assert all(r["due"] is None and r["system"] == -1 for r in reqs)
    assert max(int(r["prompt"].max()) for r in reqs) < 50304


def test_chat_prefix_shares_system_prompts_by_zipf():
    m = traffic.load("chat-prefix")
    n = 1600
    reqs = traffic.lm_requests(m, SEED, 50304, n)
    systems = traffic.system_prompts(m, SEED, 50304)
    assert len(systems) == 8 and all(len(s) == 256 for s in systems)
    for r in reqs[:50]:
        np.testing.assert_array_equal(r["prompt"][:256],
                                      systems[r["system"]])
    counts = collections.Counter(r["system"] for r in reqs)
    # served as its 16 quantiles: every rank's share is a whole number of
    # sixteenths, within a sixteenth of the Zipf law's
    p = np.arange(1, 9) ** -1.1
    p /= p.sum()
    share = np.array([counts[k] / n for k in range(8)])
    served = traffic.ppf(m["system"]["pick"], traffic.QUANTILES)
    np.testing.assert_array_equal(share * 16, np.bincount(served, None, 8))
    assert np.all(np.abs(share - p) <= 1 / 16) and share[0] == share.max()
    user = np.array([len(r["prompt"]) - 256 for r in reqs])
    assert user.min() >= 16 and user.max() <= 384
    assert abs(np.median(user) - 64) <= 3
    due = np.array([r["due"] for r in reqs])
    assert np.all(np.diff(due) >= 0)
    rate = (n - 1) / (due[-1] - due[0])
    assert abs(rate / m["arrival"]["rate_hz"] - 1) < 0.05
    assert due[0] > -m["ramp_s"]


def test_remaining_lengths_of_a_steady_backlog():
    """A lane of a steady backlog has r tokens left with a chance that
    falls as fewer lengths reach r: flat up to the shortest output, then
    down to the longest."""
    spec = {"dist": "uniform_int", "lo": 256, "hi": 448}
    a = traffic.remaining(spec, 4096, traffic.rng_for(SEED, 10))
    b = traffic.remaining(spec, 4096, traffic.rng_for(3, 10))
    np.testing.assert_array_equal(np.sort(a), np.sort(b))
    assert a.min() >= 1 and a.max() <= 448
    lengths = traffic.ppf(spec, traffic.QUANTILES)
    mean = (lengths ** 2).sum() / (2 * lengths.sum())
    assert abs(a.mean() - mean) < 8
    assert abs(np.mean(a <= 128) - 128 / lengths.mean()) < 0.02
    assert np.mean(a > 256) < np.mean(a <= 128)


def test_uniform_and_exponential_quantiles():
    u = (np.arange(8) + 0.5) / 8
    np.testing.assert_array_equal(
        traffic.ppf({"dist": "uniform_int", "lo": 0, "hi": 7}, u),
        np.arange(8))
    e = traffic.ppf({"dist": "exponential", "mean": 2.0}, np.array([0.5]))
    assert abs(e[0] - 2.0 * np.log(2)) < 1e-12
    with pytest.raises(ValueError):
        traffic.ppf({"dist": "pareto"}, u)


def test_prompt_lengths_cover_every_length():
    assert traffic.lm_prompt_lengths(traffic.load("decode-backlog")) == \
        list(range(16, 65))
    assert traffic.lm_prompt_lengths(traffic.load("chat-prefix")) == \
        list(range(256 + 16, 256 + 385))


def test_frame_indices_deterministic():
    m = traffic.load("frames-backlog")
    a = traffic.frame_indices(m, SEED, 1000)
    np.testing.assert_array_equal(a, traffic.frame_indices(m, SEED, 1000))
    assert a.min() >= 0 and a.max() < m["pool"]
    assert not np.array_equal(a, traffic.frame_indices(m, SEED + 1, 1000))


def test_unknown_mix_fails():
    with pytest.raises(FileNotFoundError):
        traffic.load("no-such-mix")
