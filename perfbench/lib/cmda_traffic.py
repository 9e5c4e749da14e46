"""ONE queue of several kinds of request: `lib.traffic.open_loop` with the
prompt lengths drawn from the kinds of the traffic file's `mix` (each a
`share` of the window's requests and a clipped log-normal of its own, by
`lib.traffic`'s own quantiles) in the one log-normal's place. Everything
else is that generator's: every seed gets the SAME multiset of gaps, of
prompt lengths (each kind's quantiles at its count) and of answer lengths,
in an order of its own; the window is one period of periodic traffic, whose
seam is put in the period's longest gap. Without `mix` it is
`lib.traffic.open_loop`."""

from __future__ import annotations

import random
from typing import Dict, List

from perfbench.lib import traffic as one_kind


def counts(traffic: dict, n: int) -> List[int]:
    """How many of a window's n requests each kind of `mix` gets: its share
    rounded, the first kind taking what the rounding leaves."""
    got = [int(round(k["share"] * n)) for k in traffic["mix"][1:]]
    return [n - sum(got)] + got


def prompt_lengths(traffic: dict, n: int) -> List[int]:
    return [length for kind, count in zip(traffic["mix"], counts(traffic, n))
            for length in one_kind._lognormal_quantiles(count, kind["prompt_tokens"])]


def open_loop(traffic: dict, seed: int, seconds: float, vocab: int) -> List[Dict]:
    """`lib.traffic.open_loop`'s schedule (`due_s`, `prompt`,
    `max_new_tokens`, sorted by due time) over the kinds of `mix`."""
    if "mix" not in traffic:
        return one_kind.open_loop(traffic, seed, seconds, vocab)
    if "order_seed" in traffic:
        raise ValueError("a mixed queue is ordered by the seed")
    warm, rate = float(traffic["warm_s"]), float(traffic["rate_per_s"])
    n = int(round(rate * seconds))
    gaps = one_kind._gap_quantiles(n, n / seconds, float(traffic.get("arrival_cv", 1.0)))
    prompts = prompt_lengths(traffic, n)
    answers = one_kind._lognormal_quantiles(n, traffic["answer_tokens"])
    rnd, ids = random.Random(int(seed)), one_kind._rng(seed, 3)
    for seq in (gaps, prompts, answers):
        rnd.shuffle(seq)
    # the period's SEAM lies in its longest gap: the order of the gaps is the
    # seed's, turned so that the longest comes last. A busy slot yields 200
    # tokens a second, so an answer in flight across an edge of the window
    # moves the window's count by its timing (0.3 s = 60 tokens = 0.7% of a
    # window; six seeds spread 0.81% before this, PERF.md section 6, PR 60);
    # behind ln(2 n) / rate seconds of silence little is in flight
    last = gaps.index(max(gaps))
    gaps = gaps[last + 1:] + gaps[:last + 1]
    due, t = [], 0.0
    for g in gaps:
        due.append(t)
        t += g
    order = [k for k in range(n) if due[k] >= seconds - warm] + list(range(n))
    n_before = len(order) - n
    return [{"i": i, "due_s": due[k] - (seconds if i < n_before else 0.0),
             "prompt": ids.integers(1, vocab, prompts[k]).tolist(),
             "max_new_tokens": answers[k]} for i, k in enumerate(order)]

