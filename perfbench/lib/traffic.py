"""The one traffic generator: a pure function of (traffic file, seed).

Two kinds of traffic, told apart by the file's `kind`:

`token_batches`   training: `distinct_batches` batches of `batch` x (`seq`+1)
                  uniform token ids, cycled step after step.
`open_loop`       serving: requests due on a schedule, whoever is slow.
                  Every seed gets the SAME multiset of inter-arrival gaps
                  (the quantiles of an exponential at `rate_per_s`, or of a
                  gamma with `arrival_cv`) and the SAME multiset of prompt
                  and answer lengths (quantiles of a clipped log-normal), in
                  an order drawn from the seed: the work offered is equal
                  from seed to seed, only when each piece arrives differs.
                  A file that states `order_seed` draws that order from IT,
                  once for every seed, and the seed chooses only at which
                  request of the period the window starts: then the same
                  answers meet the same prompt passes under every seed, and
                  a tail over requests reads the program, not the draw
                  (PERF.md section 6, PR 52). Prompt token ids are drawn
                  from the seed.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def _rng(seed: int, salt: int) -> np.random.Generator:
    # seeds run past 2**31; SeedSequence takes any non-negative integer
    return np.random.default_rng(np.random.SeedSequence([int(seed), salt]))


def token_batches(traffic: dict, seed: int, vocab: int) -> np.ndarray:
    """[distinct_batches, batch, seq + 1] int32."""
    n, b, s = traffic["distinct_batches"], traffic["batch"], traffic["seq"]
    return _rng(seed, 1).integers(0, vocab, (n, b, s + 1), dtype=np.int32)


def sample_tokens(seed: int, vocab: int, batch: int, seq: int) -> np.ndarray:
    """The seeded sample the reference is compared on: [batch, seq + 1]."""
    return _rng(seed, 2).integers(0, vocab, (batch, seq + 1), dtype=np.int32)


def _lognormal_quantiles(n: int, spec: dict) -> List[int]:
    nd = NormalDist(spec["log_mean"], spec["log_sd"])
    out = []
    for i in range(n):
        v = math.exp(nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(v), spec["min"]), spec["max"])))
    return out


def _gap_quantiles(n: int, rate: float, cv: float) -> List[float]:
    """n inter-arrival gaps with mean 1/rate: exponential for cv 1, else a
    gamma with shape 1/cv^2 (quantiles by bisection on its CDF)."""
    if abs(cv - 1.0) < 1e-9:
        gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    else:
        k = 1.0 / (cv * cv)

        def cdf(x):  # regularised lower incomplete gamma, series
            if x <= 0:
                return 0.0
            term = total = 1.0 / k
            for j in range(1, 2000):
                term *= x / (k + j)
                total += term
                if term < 1e-14 * total:
                    break
            return total * math.exp(-x + k * math.log(x) - math.lgamma(k))

        gaps = []
        for i in range(n):
            p, lo, hi = (i + 0.5) / n, 0.0, 50.0 * max(1.0, k)
            for _ in range(80):
                mid = 0.5 * (lo + hi)
                lo, hi = (mid, hi) if cdf(mid) < p else (lo, mid)
            gaps.append(0.5 * (lo + hi) / k)
    mean = sum(gaps) / n
    return [g / mean / rate for g in gaps]


def open_loop(traffic: dict, seed: int, seconds: float, vocab: int) -> List[Dict]:
    """Requests due in [-warm_s, seconds): `due_s` (0 = the window opens),
    `prompt` token ids, `max_new_tokens`. Sorted by due time.

    The window is ONE PERIOD of periodic traffic: its n = rate x seconds
    requests take the quantile multisets of gaps (scaled to sum to `seconds`)
    and of lengths, each in an order drawn from the seed; the requests before
    it are the end of the period before, i.e. the window's last `warm_s`
    seconds shifted back by one period. What streams into the window from
    before it is then what streams out of its end, and a system that keeps up
    delivers the period's tokens inside the window under every seed.

    With `order_seed` in the file the three orders are drawn from it, and
    the seed turns the period: the window starts at request r of n, r drawn
    from the seed, and runs once round. Who follows whom at what distance,
    and so which answers wait behind which prompt passes, is then the same
    under every seed; only where the window's edges fall differs."""
    warm, rate = float(traffic["warm_s"]), float(traffic["rate_per_s"])
    n = int(round(rate * seconds))
    gaps = _gap_quantiles(n, n / seconds, float(traffic.get("arrival_cv", 1.0)))
    prompts = _lognormal_quantiles(n, traffic["prompt_tokens"])
    answers = _lognormal_quantiles(n, traffic["answer_tokens"])
    rnd, ids = random.Random(int(seed)), _rng(seed, 3)
    fixed = traffic.get("order_seed")
    order = rnd if fixed is None else random.Random(int(fixed))
    for seq in (gaps, prompts, answers):
        order.shuffle(seq)
    if fixed is not None:
        r = rnd.randrange(n)
        gaps, prompts, answers = (seq[r:] + seq[:r]
                                  for seq in (gaps, prompts, answers))
    due, t = [], 0.0
    for g in gaps:
        due.append(t)
        t += g
    order = [k for k in range(n) if due[k] >= seconds - warm] + list(range(n))
    n_before = len(order) - n
    return [{"i": i, "due_s": due[k] - (seconds if i < n_before else 0.0),
             "prompt": ids.integers(1, vocab, prompts[k]).tolist(),
             "max_new_tokens": answers[k]} for i, k in enumerate(order)]


def mean_lengths(traffic: dict, n: int = 1000):
    p = _lognormal_quantiles(n, traffic["prompt_tokens"])
    a = _lognormal_quantiles(n, traffic["answer_tokens"])
    return sum(p) / n, sum(a) / n


def slot_rule(traffic: dict, slots: int) -> Dict[str, float]:
    """The rate rule the serving traffic states: with a mean service time of
    (mean answer tokens x token gap + time to first token), the mean number
    of busy slots m = rate x service time must satisfy m + 3 sqrt(m) <= slots,
    so that a full house is three standard deviations of Poisson occupancy
    away and no request waits for a slot."""
    r = traffic["slot_rule"]
    _, mean_answer = mean_lengths(traffic)
    service_s = (mean_answer * r["token_gap_ms"] + r["ttft_ms"]) / 1e3
    m = float(traffic["rate_per_s"]) * service_s
    m_max = ((-3 + math.sqrt(9 + 4 * slots)) / 2) ** 2
    return {"service_s": service_s, "mean_busy_slots": m,
            "needs_slots": m + 3 * math.sqrt(m), "max_mean_busy": m_max,
            "max_rate_per_s": m_max / service_s, "ok": m + 3 * math.sqrt(m) <= slots}
