"""The benchmark's traffic generator: a seed fixes the requests, and the work
a decode mix puts into the measured window does not depend on the seed."""

import collections
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from perfbench.traffic import generator  # noqa: E402
from repro.serving import workload  # noqa: E402

SEEDS = [0, 7, 2**31 + 11, 4_294_967_301]


def _lengths(reqs):
    return [(len(r.prompt), r.max_new_tokens) for r in reqs]


@pytest.mark.parametrize("seed", SEEDS)
def test_seed_fixes_the_requests(seed):
    mix = generator.load("decode-long-out")
    a = generator.requests(mix, seed, 49155)
    b = generator.requests(mix, seed, 49155)
    assert _lengths(a) == _lengths(b)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = generator.requests(mix, seed + 1, 49155)
    assert _lengths(a) != _lengths(c)          # another order
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    for r in a:
        assert r.prompt.dtype == np.int32
        assert 1 <= r.prompt.min() and r.prompt.max() < 49155


def test_every_block_holds_the_same_lengths_on_every_seed():
    mix = generator.load("decode-long-out")
    n = generator.slots(mix)
    blocks = []
    for seed in SEEDS:
        reqs = generator.requests(mix, seed, 32000)
        assert len(reqs) == n * mix["blocks"]
        # the sessions in flight: the same (prompt, output) pairs
        assert collections.Counter(_lengths(reqs[:n])) == collections.Counter(
            (p + a, o - a) for p, a, o in generator.in_flight(mix))
        for b in range(1, mix["blocks"]):
            blk = reqs[b * n:(b + 1) * n]
            blocks.append((collections.Counter(len(r.prompt) for r in blk),
                           collections.Counter(r.max_new_tokens
                                               for r in blk)))
    assert all(blk == blocks[0] for blk in blocks)
    later = reqs[n:2 * n]
    assert collections.Counter(len(r.prompt) for r in later) == \
        {128: 16, 256: 16, 512: 16}
    assert min(r.max_new_tokens for r in later) >= mix["output"]["lo"]
    assert max(r.max_new_tokens for r in later) <= mix["output"]["hi"]


def test_decode_window_work_does_not_depend_on_the_seed():
    """The first block fills every slot. Each of its sessions has at least
    ``reserve`` tokens left, and prompt plus output fit the cache, so no slot
    frees in set-up (at most 4 tokens: the prefill's, one fill tick's, two
    warm ticks') plus a window of up to ``reserve - 5`` ticks: every window
    tick decodes one token in each of the 48 slots, on every seed. Every
    prompt prefills in whole ``step``s, so set-up uses a few chunk shapes."""
    mix = generator.load("decode-long-out")
    n = generator.slots(mix)
    step = mix["in_flight"]["step"]
    assert mix["prefill_chunk"] % step == 0
    assert -(-sum(-(-len(r.prompt) // mix["prefill_chunk"])
                  for r in generator.requests(mix, 0, 32000)[:n])
             // mix["prefill_chunks_per_tick"]) <= 2
    for seed in SEEDS:
        first = generator.requests(mix, seed, 32000)[:n]
        assert min(r.max_new_tokens for r in first) >= \
            mix["in_flight"]["reserve"]
        assert max(len(r.prompt) + r.max_new_tokens
                   for r in first) <= mix["max_len"]
        assert all(len(r.prompt) % step == 0 for r in first)


def test_in_flight_sessions_follow_the_steady_state():
    """The sessions in flight match a simulated closed loop at random times,
    kept where ``reserve`` tokens or more are left."""
    mix = generator.load("decode-long-out")
    lo, hi = mix["output"]["lo"], mix["output"]["hi"]
    reserve = mix["in_flight"]["reserve"]
    rng = np.random.default_rng(0)
    outs = rng.integers(lo, hi + 1, 400_000)
    # a client found at a random time is in a request with weight ~ length
    found = rng.choice(outs, 400_000, p=outs / outs.sum())
    served = (rng.random(400_000) * found).astype(int)
    keep = found - served >= reserve
    sessions = generator.in_flight(mix)
    a = np.array([s[1] for s in sessions])
    o = np.array([s[2] for s in sessions])
    assert np.mean(a) == pytest.approx(np.mean(served[keep]), rel=0.05)
    assert np.mean(o) == pytest.approx(np.mean(found[keep]), rel=0.03)
    assert np.all(o - a >= reserve) and np.all(a % mix["in_flight"]["step"]
                                               == 0)


def test_length_sampler_matches_the_programs():
    """The spread lengths are the program's uniform sampler's, at evenly
    spaced quantiles: the same range and, to within a length, the same
    mean."""
    lens = generator.spread_lengths(3, 9, 700)
    rng = np.random.RandomState(5)
    theirs = [workload.LengthDist(3, 9).sample(rng) for _ in range(20_000)]
    assert (min(lens), max(lens)) == (min(theirs), max(theirs))
    assert collections.Counter(lens) == {k: 100 for k in range(3, 10)}
    assert abs(np.mean(lens) - np.mean(theirs)) < 0.05


def test_spread_lengths_follow_the_distribution():
    lens = generator.spread_lengths(256, 1536, 48)
    assert min(lens) >= 256 and max(lens) <= 1536
    assert abs(np.mean(lens) - (256 + 1536) / 2) < 20
    assert lens == sorted(lens) and len(set(lens)) == 48
