"""The one traffic generator: reads a mix's parameters from
``traffic/<name>.json`` and draws its requests from the seed.

A mix is closed-loop: ``n_bo * mb_slots`` clients, each sending its next
request when the last one finishes. Requests come in blocks of one request
per client. Every block holds the same lengths whatever the seed; the seed
only orders each block and draws the token ids.

The first block is the sessions in flight when the window opens, drawn from
the closed loop's steady state (``in_flight``): each session has already
served part of its output, and the engine gets its prompt followed by those
tokens as one prompt, prefilled in set-up, and asks for the rest. So the
window decodes at the contexts the mix holds in steady state, and every
seed puts the same work in it. Later blocks are fresh requests:

* ``prompt.buckets``: the block's prompts cycle through the buckets, so each
  bucket takes an equal share (the bucket draw);
* ``output``: ``lo``..``hi`` spread evenly over the block, as the uniform
  length sampler (``LengthDist``) of ``repro.serving.workload`` draws them,
  at evenly spaced quantiles instead of at random.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN = (5 ** 0.5 - 1) / 2


def load(name: str) -> Dict:
    with open(os.path.join(HERE, f"{name}.json")) as fh:
        mix = json.load(fh)
    mix["name"] = name
    return mix


def bucket_lengths(buckets: List[int], n: int) -> List[int]:
    """``n`` prompt lengths cycling through ``buckets``."""
    return [int(buckets[i % len(buckets)]) for i in range(n)]


def spread_lengths(lo: int, hi: int, n: int) -> List[int]:
    """``n`` lengths uniform on [lo, hi] (inclusive), at the evenly spaced
    quantiles (i + 0.5) / n."""
    return [int(lo + (i + 0.5) / n * (hi - lo + 1)) for i in range(n)]


def slots(mix: Dict) -> int:
    return int(mix["n_bo"]) * int(mix["mb_slots"])


def in_flight(mix: Dict) -> List[Tuple[int, int, int]]:
    """(prompt, served, output) lengths of the sessions in flight when the
    window opens, one per client.

    In the closed loop's steady state a client is found in a request of
    output ``o`` that has served ``a`` tokens with weight ``1[0 <= a < o]``
    per output length (longer requests are found more often). Conditioned
    on ``in_flight.reserve`` tokens or more being left, so that no session
    finishes in set-up or in the window, the served count has the weight
    ``#{o in [max(lo, a + reserve), hi]}``, and the output given it is
    uniform on that range. Served counts are taken at evenly spaced
    quantiles and rounded to the nearest ``in_flight.step``, so that prompt
    plus served tokens prefill in a few chunk shapes; outputs at a fixed
    golden-ratio sequence of quantiles of their range; prompts cycle
    through the buckets."""
    n = slots(mix)
    lo, hi = int(mix["output"]["lo"]), int(mix["output"]["hi"])
    reserve = int(mix["in_flight"]["reserve"])
    step = int(mix["in_flight"]["step"])
    if not 1 <= lo <= hi or not 1 <= reserve <= hi:
        raise ValueError(f"bad output [{lo}, {hi}] or reserve {reserve}")
    served = np.arange(0, hi - reserve + 1)
    weight = hi - np.maximum(lo, served + reserve) + 1
    cdf = np.cumsum(weight) / weight.sum()
    top = (hi - reserve) - (hi - reserve) % step
    prompts = bucket_lengths(mix["prompt"]["buckets"], n)
    out = []
    for j in range(n):
        a = int(served[np.searchsorted(cdf, (j + 0.5) / n)])
        a = min(step * int(a / step + 0.5), top)
        first = max(lo, a + reserve)
        u = ((j + 0.5) * GOLDEN) % 1.0
        out.append((prompts[j], a, first + int(u * (hi - first + 1))))
    return out


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32 token ids in [1, vocab)
    max_new_tokens: int


def requests(mix: Dict, seed: int, vocab: int) -> List[Request]:
    """Every request of the run, in the order the clients send them."""
    n = slots(mix)
    rng = np.random.default_rng(int(seed))
    reqs: List[Request] = []

    def add(prompt_len: int, max_new: int) -> None:
        reqs.append(Request(
            rid=len(reqs),
            prompt=rng.integers(1, vocab, size=prompt_len).astype(np.int32),
            max_new_tokens=max_new))

    sessions = in_flight(mix)
    for i in rng.permutation(n):
        p, a, o = sessions[i]
        add(p + a, o - a)
    prompt_lens = bucket_lengths(mix["prompt"]["buckets"], n)
    output_lens = spread_lengths(mix["output"]["lo"], mix["output"]["hi"], n)
    for _ in range(int(mix["blocks"]) - 1):
        order_p = rng.permutation(n)
        order_o = rng.permutation(n)
        for i in range(n):
            add(prompt_lens[order_p[i]], output_lens[order_o[i]])
    return reqs
