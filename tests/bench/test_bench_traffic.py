"""The traffic generator: one multiset of work per mix and window, its size
following the rate, ordered by the seed (gaps freely, lengths stratified),
prompts in the mix's buckets."""
import json
from collections import Counter

import numpy as np
import pytest

from bench_tiny import CHAT, CODE, REPO

from bench.traffic.generator import make_trace, quantiles, request_count, stratified_order

# the cells' mixes, and the toy mixes of the CPU runs (a gamma process and
# a choice of prompt lengths among them)
MIXES = sorted(p.stem for p in (REPO / "bench" / "traffic").glob("*.json")) + ["toy-chat", "toy-code"]


def _mix(name):
    if name.startswith("toy-"):
        return {"toy-chat": CHAT, "toy-code": CODE}[name]
    return json.loads((REPO / "bench" / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_trace(name):
    a = make_trace(_mix(name), 45.0, 2**31 + 17, 1000)
    b = make_trace(_mix(name), 45.0, 2**31 + 17, 1000)
    assert [(r.arrival_s, r.max_new_tokens) for r in a] == [(r.arrival_s, r.max_new_tokens) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_reorder_one_multiset(name):
    mix = _mix(name)
    a = make_trace(mix, 45.0, 3, 1000)
    b = make_trace(mix, 45.0, 4, 1000)
    assert len(a) == len(b) == request_count(mix, 45.0)
    assert Counter(len(r.prompt) for r in a) == Counter(len(r.prompt) for r in b)
    assert Counter(r.max_new_tokens for r in a) == Counter(r.max_new_tokens for r in b)
    gaps = lambda t: sorted(np.round(np.diff([r.arrival_s for r in t] + [45.0]), 9))  # noqa: E731
    np.testing.assert_allclose(gaps(a), gaps(b), atol=1e-9)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert a[0].arrival_s == 0.0 and a[-1].arrival_s < 45.0


@pytest.mark.parametrize("name", MIXES)
def test_lengths_respect_buckets_and_clips(name):
    mix = _mix(name)
    trace = make_trace(mix, 45.0, 9, 500)
    p = mix["prompt"]
    allowed = set(p.get("buckets", p.get("values", [])))
    assert {len(r.prompt) for r in trace} <= allowed
    o = mix["output"]
    assert all(o["min"] <= r.max_new_tokens <= o["max"] for r in trace)
    assert all(0 <= int(r.prompt.min()) and int(r.prompt.max()) < 500 for r in trace)


def test_stratified_blocks_hold_one_of_each_band():
    for n, strata in ((40, 8), (113, 10), (7, 10)):
        order = stratified_order(n, strata, np.random.default_rng(n))
        assert sorted(order) == list(range(n))
        band = np.arange(n) * strata // n
        pos = np.empty(n, np.int64)
        pos[order] = np.arange(n)
        for b in range(strata):
            p = np.sort(pos[band == b])
            if len(p) > 1:
                # a band's members are spread evenly: about n/m apart
                assert np.diff(p).max() <= n / len(p) + strata
        if n >= strata:
            for i in range(0, n - strata + 1, strata):
                assert np.bincount(band[order[i:i + strata]]).max() <= 2


def test_count_follows_the_rate():
    mix = _mix("chat-steady")
    counts = [request_count(dict(mix, arrival=dict(mix["arrival"], rate_rps=r)), 45.0)
              for r in (0.6, 0.75, 2.0, 2.1)]
    assert counts == [27, 34, 90, 94]
    assert len(make_trace(dict(mix, arrival=dict(mix["arrival"], rate_rps=0.75)), 45.0, 1, 100)) == 34


def test_quantiles_follow_their_distribution():
    q = quantiles({"dist": "lognormal", "median": 100, "sigma": 0.5}, 1001)
    assert q[500] == pytest.approx(100)
    g = quantiles({"dist": "gamma", "cv": 3.0}, 2000)
    assert np.std(g) / np.mean(g) == pytest.approx(3.0, rel=0.25)
    e = quantiles({"dist": "exponential"}, 2000)
    assert np.mean(e) == pytest.approx(1.0, rel=0.02)
