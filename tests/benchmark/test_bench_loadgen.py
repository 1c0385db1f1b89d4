"""The benchmark's traffic: stratified lengths, seeded order, due times."""

import json
import os

import numpy as np
import pytest

from benchmark import loadgen

TRAFFIC = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark", "traffic")


def mix(name, **over):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return dict(json.load(f), **over)


def lengths(reqs):
    return sorted(r.prompt.size for r in reqs), sorted(r.max_new_tokens for r in reqs)


def test_two_seeds_offer_the_same_multiset_of_work():
    m = mix("chat_steady", rate_rps=2.0)
    a = loadgen.open_loop_trace(m, 50.0, 32768, seed=1)
    b = loadgen.open_loop_trace(m, 50.0, 32768, seed=2)
    assert len(a) == len(b) == 100
    assert lengths(a) == lengths(b)
    # the measured requests (all but the first tenth) are the same multiset too
    assert [r.measured for r in a] == [False] * 10 + [True] * 90
    assert lengths([r for r in a if r.measured]) == lengths([r for r in b if r.measured])
    assert [r.prompt.size for r in a] != [r.prompt.size for r in b]  # the order is the seed's
    assert min(r.prompt.size for r in a) >= 32 and max(r.prompt.size for r in a) <= 2048
    assert min(r.max_new_tokens for r in a) >= 16 and max(r.max_new_tokens for r in a) <= 384
    # the stated medians
    assert np.median([r.prompt.size for r in a]) == pytest.approx(256, rel=0.05)
    assert np.median([r.max_new_tokens for r in a]) == pytest.approx(96, rel=0.05)


def test_a_traced_tail_leaves_the_window_as_it_is():
    m = mix("chat_steady", rate_rps=2.0)
    plain = loadgen.open_loop_trace(m, 50.0, 32768, seed=1)
    tailed = loadgen.open_loop_trace(m, 50.0, 32768, seed=1, tail_s=5.0)
    inside, after = tailed[: len(plain)], tailed[len(plain) :]
    # the window is the untraced run's, request for request
    assert [(r.due_s, r.measured, r.max_new_tokens, r.prompt.tobytes()) for r in inside] == [(r.due_s, r.measured, r.max_new_tokens, r.prompt.tobytes()) for r in plain]
    # the tail: the same rate for 5 s more, inside the tail, never measured
    assert len(after) == 10 and all(50.0 < r.due_s < 55.0 and not r.measured for r in after)
    assert [r.index for r in tailed] == list(range(len(tailed)))
    # a tail too short for one arrival at this rate adds nothing
    assert len(loadgen.open_loop_trace(m, 50.0, 32768, seed=1, tail_s=0.2)) == len(plain)


def test_one_seed_gives_a_byte_identical_trace():
    m = mix("chat_steady", rate_rps=2.0)
    a = loadgen.open_loop_trace(m, 20.0, 32768, seed=7)
    b = loadgen.open_loop_trace(m, 20.0, 32768, seed=7)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(x.prompt.tobytes() == y.prompt.tobytes() and x.max_new_tokens == y.max_new_tokens for x, y in zip(a, b))


@pytest.mark.parametrize("arrival", [{"process": "poisson"}, {"process": "pareto", "alpha": 1.5}])
def test_arrivals_are_a_fixed_count_inside_the_horizon(arrival):
    due = loadgen.arrival_offsets(arrival, rate_rps=3.0, horizon_s=40.0, rng=np.random.default_rng(5))
    assert len(due) == 120
    assert np.all(np.diff(due) > 0) and due[0] > 0 and due[-1] < 40.0


def test_closed_loop_rounds_repeat_the_multiset():
    m = mix("decode_heavy")
    stream = loadgen.request_stream(m, 16, 32768, seed=3)
    first, second = [next(stream) for _ in range(16)], [next(stream) for _ in range(16)]
    assert lengths(first) == lengths(second)
    assert [r.index for r in second] == list(range(16, 32))
    assert 128 <= min(r.prompt.size for r in first) and max(r.prompt.size for r in first) <= 512
    assert 512 <= min(r.max_new_tokens for r in first) and max(r.max_new_tokens for r in first) <= 1024


@pytest.mark.parametrize("traffic", ["decode_heavy", "long_decode"])
def test_closed_loop_order_is_the_same_for_every_seed(traffic):
    """In a closed loop the order of the lengths decides what falls inside
    the window, so it is a rule of the round and not the seed's: two seeds
    offer the same lengths request for request, and other token ids."""
    m = mix(traffic)
    a, b = (loadgen.request_stream(m, 64, 32768, seed=s) for s in (2244070101, 7))
    a, b = [next(a) for _ in range(192)], [next(b) for _ in range(192)]
    sizes = lambda reqs: [(r.prompt.size, r.max_new_tokens) for r in reqs]  # noqa: E731
    assert sizes(a) == sizes(b)
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # a round's order is its own, and the pairing of prompt with output length too
    assert sizes(a[:64]) != sizes(a[64:128]) != sizes(a[128:])
    assert np.argsort([r.prompt.size for r in a[:64]]).tolist() != np.argsort([r.max_new_tokens for r in a[:64]]).tolist()
    # the same seed, the same requests byte for byte
    again = loadgen.request_stream(m, 64, 32768, seed=7)
    assert all(next(again).prompt.tobytes() == y.prompt.tobytes() for y in b)


def test_an_open_loop_keeps_its_seeded_order():
    """``make_requests`` as the open loop calls it draws order and pairing
    from the seed, as before PR 44's change to the closed loop's supply."""
    m = mix("chat_steady")
    a, b = loadgen.make_requests(m, 40, 32768, seed=1), loadgen.make_requests(m, 40, 32768, seed=2)
    assert lengths(a) == lengths(b)
    assert [r.prompt.size for r in a] != [r.prompt.size for r in b]
    fixed_a, fixed_b = (loadgen.make_requests(m, 40, 32768, seed=s, seeded_order=False) for s in (1, 2))
    assert [r.prompt.size for r in fixed_a] == [r.prompt.size for r in fixed_b] != [r.prompt.size for r in a]


def test_stratified_lengths_are_the_quantiles_clipped_to_the_stated_range():
    # lognormal: the middle request takes the median, the tails are clipped, the order rises
    dist = {"dist": "lognormal", "median": 256, "sigma": 0.9, "min": 32, "max": 2048}
    lens = loadgen.stratified_lengths(dist, 101)
    assert lens[50] == 256 and lens[0] == 32 and lens[-1] == 2048 and np.all(np.diff(lens) >= 0)
    assert list(loadgen.stratified_lengths({"dist": "uniform", "min": 100, "max": 200}, 4)) == [112, 138, 162, 188]
    with pytest.raises(ValueError, match="unknown length distribution"):
        loadgen.quantile({"dist": "zipf"}, 0.5)


def test_training_batches_first_batch_repeats_two_sequences():
    toks = loadgen.train_batches({"pool_batches": 3, "seq_len": 16}, rows=8, vocab_size=100, seed=1)
    assert toks.shape == (3, 8, 17) and toks.dtype == np.int32
    assert np.array_equal(toks[0][0], toks[0][2]) and np.array_equal(toks[0][1], toks[0][7])
    assert not np.array_equal(toks[0][0], toks[0][1])
    assert not np.array_equal(toks[1][0], toks[1][2])


def test_percentile_interpolates_and_keeps_inf():
    assert loadgen.percentile([1, 2, 3, 4, 5], 50) == 3.0
    assert loadgen.percentile([0, 10], 90) == pytest.approx(9.0)
    assert loadgen.percentile([1.0, 2.0, float("inf")], 50) == 2.0
    assert loadgen.percentile([1.0, float("inf")], 90) == float("inf")
    assert loadgen.percentile([], 90) is None
