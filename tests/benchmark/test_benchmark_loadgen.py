"""The benchmark's traffic generator: determinism, the same work for every
seed, and the parameters a traffic file can give."""

import json
import os

import numpy as np
import pytest

from benchmarks.lib import loadgen, stats

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAFFIC = os.path.join(ROOT, "benchmarks", "traffic")
SERVED = ["offline_chat_lengths", "chat_steady"]


def traffic(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


def sizes(reqs):
    return sorted((len(r.prompt), r.max_new_tokens) for r in reqs)


@pytest.mark.parametrize("name", SERVED)
@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11, 2 ** 32 + 5])
def test_same_seed_same_stream(name, seed):
    a = loadgen.generate(traffic(name), seed, 30, 32768)
    b = loadgen.generate(traffic(name), seed, 30, 32768)
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert sizes(a) == sizes(b)


@pytest.mark.parametrize("name", SERVED)
def test_every_seed_gets_the_same_work_in_another_order(name):
    t = traffic(name)
    a = loadgen.generate(t, 1, 30, 32768)
    b = loadgen.generate(t, 2 ** 31 + 3, 30, 32768)
    assert sizes(a) == sizes(b)
    same_order = [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert same_order == (t.get("order") == "fixed")
    gaps = lambda rs: sorted(np.round(np.diff(  # noqa: E731
        [0.0] + [r.arrival_s for r in rs]), 9))
    assert gaps(a) == gaps(b)
    assert not np.array_equal(a[0].prompt, b[0].prompt) or \
        len(a[0].prompt) != len(b[0].prompt)


def test_lengths_follow_the_weights():
    t = traffic("chat_steady")
    reqs = loadgen.generate(t, 5, 100, 32768)
    n = len(reqs)
    for length, w in zip(t["prompt_lens"], t["prompt_weights"]):
        share = sum(len(r.prompt) == length for r in reqs) / n
        assert abs(share - w) < 0.02
    for length, w in zip(t["output_lens"], t["output_weights"]):
        share = sum(r.max_new_tokens == length for r in reqs) / n
        assert abs(share - w) < 0.02


def test_poisson_arrivals_hold_the_rate_and_at_zero_holds_zero():
    t = traffic("chat_steady")
    reqs = loadgen.generate(t, 9, 50, 32768)
    assert len(reqs) == round(50 * t["requests_per_second_of_window"])
    d = loadgen.describe(reqs)
    assert abs(d["offered_rps"] - t["rate_rps"]) / t["rate_rps"] < 0.02
    assert all(r.arrival_s == 0.0 for r in loadgen.generate(
        traffic("offline_chat_lengths"), 9, 10, 32768))


@pytest.mark.parametrize("weights,n,want", [
    ([0.5, 0.3, 0.2], 10, [5, 3, 2]), ([1, 1, 1], 10, [4, 3, 3]),
    ([0.4, 0.3, 0.2, 0.1], 7, [3, 2, 1, 1]), ([1.0], 3, [3])])
def test_apportion(weights, n, want):
    assert loadgen.apportion(weights, n) == want


def test_prefix_share_and_sessions_are_data():
    t = dict(traffic("chat_steady"), prefix_share=0.75, num_prefixes=2,
             prefix_lens=[64], session_share=0.25, session_turns=2,
             num_requests=40)
    reqs = loadgen.generate(t, 3, 30, 1000)
    base = set(t["prompt_lens"])
    shared = [r for r in reqs if len(r.prompt) - 64 in base]
    assert len(shared) >= 15
    heads = {tuple(r.prompt[:64]) for r in shared}
    assert len(heads) <= 2
    assert any(r.session_id is not None for r in reqs)


@pytest.mark.parametrize("bad", [
    {"arrival": "closed"}, {"rate_rps": 0}, {"prompt_lens": []},
    {"prompt_weights": [1.0]}, {"prefix_share": 1.5},
    {"requests_per_second_of_window": 0}])
def test_bad_parameters_raise(bad):
    with pytest.raises(ValueError):
        loadgen.generate(dict(traffic("chat_steady"), **bad), 1, 10, 100)


def test_negative_seed_raises():
    with pytest.raises(ValueError):
        loadgen.seed_rng(-1)


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5),
    ([10], 95, 10.0), (list(range(101)), 95, 95.0), ([1, 3], 25, 1.5)])
def test_percentile_is_numpys(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)
    assert stats.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


@pytest.mark.parametrize("fn", [stats.median, stats.mean,
                                lambda v: stats.percentile(v, 95)])
def test_an_empty_sample_raises(fn):
    with pytest.raises(ValueError):
        fn([])


def test_a_sample_with_nan_raises():
    with pytest.raises(ValueError):
        stats.percentile([1.0, float("nan")], 50)
