"""Open-loop due times and lengths are a pure function of the seed, and
every seed gets the same amount of work."""
import collections

import numpy as np
import pytest

from bench.harness import traffic

MIX = {"process": "poisson", "rate_per_s": 2.5,
       "prompt_lens": [256, 512, 1024], "prompt_weights": [0.4, 0.35, 0.25],
       "output_lens": [16, 32], "output_weights": [0.5, 0.5]}


def key(arrivals):
    return [(a.idx, a.due_s, a.tokens.tobytes(), a.max_new)
            for a in arrivals]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 3])
def test_same_seed_same_schedule(seed):
    a = traffic.arrivals(MIX, seed, 30.0, 1000)
    b = traffic.arrivals(MIX, seed, 30.0, 1000)
    assert key(a) == key(b)
    assert all(0 <= x.due_s < 30.0 for x in a)
    assert [x.due_s for x in a] == sorted(x.due_s for x in a)


def test_seeds_differ_in_order_not_in_work():
    a = traffic.arrivals(MIX, 1, 40.0, 1000)
    b = traffic.arrivals(MIX, 2, 40.0, 1000)
    assert key(a) != key(b)
    for arr in (a, b):
        lens = collections.Counter(x.tokens.shape[1] for x in arr)
        outs = collections.Counter(x.max_new for x in arr)
        assert sum(lens.values()) == 100           # 2.5/s x 40 s
        assert lens == {256: 40, 512: 35, 1024: 25}
        assert outs == {16: 50, 32: 50}
    gaps = [np.sort(np.diff([x.due_s for x in arr])) for arr in (a, b)]
    # the same multiset of gaps, give or take the one left at the end
    assert abs(gaps[0].sum() - gaps[1].sum()) < 2 / MIX["rate_per_s"] * 4


def test_warmup():
    mix = dict(MIX, output_lens=[256, 512], warmup_max_new=8,
               prompt_lens=[128, 256], prompt_weights=[0.5, 0.5])
    a = traffic.arrivals(mix, 3, 20.0, 32000)
    w = traffic.warmup(mix, 3, 32000, at_least=3)
    assert sorted((x.tokens.shape[1], x.max_new) for x in w) == [
        (128, 8), (128, 8), (256, 8), (256, 8)]
    assert all(x.due_s == 0.0 for x in w)
    assert not set(x.tokens.tobytes() for x in w) & set(
        x.tokens.tobytes() for x in a)


@pytest.mark.parametrize("weights,block", [
    ([0.4, 0.35, 0.25], 20), ([0.5, 0.5], 2), ([1, 2], 3), ([1, 1000], 0)])
def test_length_block(weights, block):
    assert traffic.length_block(weights) == block


def test_every_stretch_of_the_schedule_has_the_mix():
    """Any block of 20 requests from the start holds the prompt lengths'
    shares exactly and the answers half and half, and each block of 8
    arrivals spans about as long as 8 mean gaps: what a window gets
    through above capacity is the same work for every seed."""
    for seed in (1, 2, 2**33 + 9):
        a = sorted(traffic.arrivals(MIX, seed, 40.0, 1000),
                   key=lambda x: x.idx)
        for k in range(0, 100, 20):
            blk = a[k:k + 20]
            assert collections.Counter(x.tokens.shape[1] for x in blk) == {
                256: 8, 512: 7, 1024: 5}
        for k in range(0, 100, 2):
            assert sorted(x.max_new for x in a[k:k + 2]) == [16, 32]
        due = sorted(x.due_s for x in traffic.arrivals(MIX, seed, 32.0, 10))
        assert len(due) == 80                       # 10 blocks of 8 gaps
        spans = [due[k + 8] - due[k] for k in range(0, 72, 8)]
        mean = 8 / MIX["rate_per_s"]
        assert all(0.7 * mean < s < 1.45 * mean for s in spans)
