"""The traffic generator is a pure function of (traffic file, seed); the
serving traffic obeys the slot rule it states."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import traffic  # noqa: E402
from perfbench.lib.manifest import Manifest  # noqa: E402

BIG = 2**31 + 11  # seeds pass 32 signed bits


@pytest.fixture(scope="module")
def chat():
    return Manifest(ROOT).load_traffic("chat-open-loop")


def test_open_loop_is_a_pure_function_of_the_seed(chat):
    a = traffic.open_loop(chat, BIG, 51, 92544)
    b = traffic.open_loop(chat, BIG, 51, 92544)
    c = traffic.open_loop(chat, BIG + 1, 51, 92544)
    assert a == b and a != c
    assert all(x["due_s"] <= y["due_s"] for x, y in zip(a, a[1:]))
    assert -chat["warm_s"] <= a[0]["due_s"] < 0 and a[-1]["due_s"] < 51
    assert [r["i"] for r in a] == list(range(len(a)))


def test_every_seed_offers_the_same_work_in_another_order(chat):
    a = traffic.open_loop(chat, 1, 51, 92544)
    c = traffic.open_loop(chat, BIG, 51, 92544)
    in_window = lambda rows: [r for r in rows if 0 <= r["due_s"] < 51]
    assert len(in_window(a)) == len(in_window(c)) == round(chat["rate_per_s"] * 51)
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new_tokens"]):
        assert sorted(map(key, in_window(a))) == sorted(map(key, in_window(c)))
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in c]
    gaps = lambda rows: {round(y["due_s"] - x["due_s"], 9)
                         for x, y in zip(rows, rows[1:])}
    assert len(gaps(in_window(a)) ^ gaps(in_window(c))) <= 2  # but for the last
    lens = [len(r["prompt"]) for r in a]
    assert min(lens) >= 56 and max(lens) <= 640
    assert 21 <= min(r["max_new_tokens"] for r in a)
    assert max(r["max_new_tokens"] for r in a) <= 256
    assert all(1 <= t < 92544 for r in a[:5] for t in r["prompt"])


def test_the_window_is_one_period_of_periodic_traffic(chat):
    """What is due before the window is the window's own end, one period
    earlier: the same lengths at the same offsets."""
    rows = traffic.open_loop(chat, BIG, 51, 92544)
    before = [r for r in rows if r["due_s"] < 0]
    tail = [r for r in rows if r["due_s"] >= 51 - chat["warm_s"]]
    assert len(before) == len(tail) > 20
    for b, t in zip(before, tail):
        assert b["due_s"] == pytest.approx(t["due_s"] - 51)
        assert (len(b["prompt"]), b["max_new_tokens"]) == (len(t["prompt"]),
                                                           t["max_new_tokens"])
    assert before[0]["due_s"] >= -chat["warm_s"]


def _window(rows, seconds=51):
    return [r for r in rows if 0 <= r["due_s"] < seconds]


@pytest.mark.parametrize("seeds", [(1, BIG), (BIG + 1, 7)])
def test_an_order_of_the_files_own_is_turned_by_the_seed(chat, seeds):
    """With `order_seed` every seed offers ONE cyclic sequence of (gap,
    prompt length, answer length), started at a request of its own: who
    follows whom at what distance is the same, so the same answers meet the
    same prompt passes; the prompts' ids are still the seed's."""
    tr = dict(chat, order_seed=9)
    a, c = (_window(traffic.open_loop(tr, s, 51, 92544)) for s in seeds)
    n = round(chat["rate_per_s"] * 51)
    assert len(a) == len(c) == n

    def cycle(rows):  # (prompt, answer, gap to the next, round the period's end)
        due = [r["due_s"] for r in rows] + [rows[0]["due_s"] + 51]
        return [(len(r["prompt"]), r["max_new_tokens"], round(due[k + 1] - due[k], 9))
                for k, r in enumerate(rows)]

    ca, cc = cycle(a), cycle(c)
    turns = [k for k in range(n) if ca == cc[k:] + cc[:k]]
    assert len(turns) == 1 and turns[0] != 0
    assert a[0]["prompt"] != c[turns[0]]["prompt"]  # same length, other ids
    # without the key the order is the seed's: no turn of one sequence
    own = {k: v for k, v in chat.items() if k != "order_seed"}
    pa, pc = (cycle(_window(traffic.open_loop(own, s, 51, 92544))) for s in seeds)
    assert not any(pa == pc[k:] + pc[:k] for k in range(n))


def test_the_chat_cell_states_one_order(chat):
    """`serve.tpot_p95_ms` is a tail over 275 answers: the chat file fixes who
    meets whom (PERF.md section 6, PR 52)."""
    assert isinstance(chat.get("order_seed"), int) and chat["order_why"]


def test_a_turned_period_is_still_one_period(chat):
    """The requests before the window are its own end one period earlier,
    with `order_seed` as without."""
    tr = dict(chat, order_seed=9)
    rows = traffic.open_loop(tr, BIG, 51, 92544)
    assert rows == traffic.open_loop(tr, BIG, 51, 92544)
    before = [r for r in rows if r["due_s"] < 0]
    tail = [r for r in rows if r["due_s"] >= 51 - chat["warm_s"]]
    assert len(before) == len(tail) > 20
    for b, t in zip(before, tail):
        assert b["due_s"] == pytest.approx(t["due_s"] - 51)
        assert (len(b["prompt"]), b["max_new_tokens"]) == (len(t["prompt"]),
                                                           t["max_new_tokens"])


def test_chat_rate_obeys_the_slot_rule(chat):
    slots = Manifest(ROOT).load_config("internlm2-1.8b")["run"]["num_slots"]
    rule = traffic.slot_rule(chat, slots)
    assert rule["ok"] and rule["needs_slots"] <= slots
    assert chat["rate_per_s"] <= rule["max_rate_per_s"]
    # PR 24's 6.5 requests/s, where the tail had broken, does not
    assert not traffic.slot_rule(dict(chat, rate_per_s=6.5), slots)["ok"]
    p, a = traffic.mean_lengths(chat)
    assert 220 < p < 240 and 98 < a < 106


def test_bursty_arrivals_keep_the_mean_rate(chat):
    rows = traffic.open_loop(dict(chat, arrival_cv=3.0), 5, 51, 92544)
    gaps = np.diff([r["due_s"] for r in rows])
    assert np.mean(gaps) == pytest.approx(1 / chat["rate_per_s"], rel=0.15)  # one of the n gaps is not between two rows
    assert np.std(gaps) / np.mean(gaps) > 2.0


def test_token_batches_from_the_seed():
    tr = Manifest(ROOT).load_traffic("pretrain-2x2048")
    a = traffic.token_batches(tr, BIG, 32768)
    assert a.shape == (tr["distinct_batches"], 2, 2049) and a.dtype == np.int32
    assert (a == traffic.token_batches(tr, BIG, 32768)).all()
    assert (a != traffic.token_batches(tr, 3, 32768)).any()
    assert 0 <= a.min() and a.max() < 32768
