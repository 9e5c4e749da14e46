"""The yardstick's arithmetic on fixed inputs: percentiles and spreads,
operation and byte counts against hand-worked values, the peaks table."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import counts, stats  # noqa: E402
from perfbench.lib.manifest import Manifest  # noqa: E402
from perfbench.lib.peaks import peaks  # noqa: E402


def test_percentile_interpolates():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_spreads():
    runs = [100.0, 101.0, 102.0, 103.0, 104.0, 150.0]
    # statistics.quantiles(n=4) of these: q1 100.75, q3 115.5, median 102.5
    assert stats.quartile_spread(runs) == pytest.approx((115.5 - 100.75) / 102.5)
    # the far-off run is left out: range of the other five over the median
    assert stats.trimmed_range(runs) == pytest.approx(4.0 / 102.5)


@pytest.mark.parametrize("name,layer,total,matmul", [
    # one block: q 4096x4096, k and v 4096x1024, o 4096x4096, three 4096x14336
    # = 16,777,216 x 2 + 4,194,304 x 2 + 176,160,768 = 218,103,808
    ("mistral-7b-v0.3.1chip", 218_103_808,
     5 * (218_103_808 + 8192) + 4096 + 2 * 32768 * 4096,
     5 * 218_103_808 + 32768 * 4096),
    # q 2048x2048, k and v 2048x1024, o 2048x2048, three 2048x8192 = 62,914,560
    ("internlm2-1.8b", 62_914_560,
     24 * (62_914_560 + 4096) + 2048 + 2 * 92544 * 2048,
     24 * 62_914_560 + 92544 * 2048),
])
def test_parameter_counts_by_hand(name, layer, total, matmul):
    c = Manifest(ROOT).load_config(name)
    assert counts.layer_matmul_params(c) == layer
    assert counts.param_count(c) == total
    assert counts.matmul_params(c) == matmul


def test_train_flops_per_token_by_hand():
    c = Manifest(ROOT).load_config("mistral-7b-v0.3.1chip")
    # 6 x 1,224,736,768 matmul weights + 6 x 5 layers x 2048 x 32 x 128
    want = 6 * 1_224_736_768 + 6 * 5 * 2048 * 4096
    assert counts.train_matmul_flops_per_token(c, 2048) == want
    # at the chip's 197e12 peak that is a ceiling of 25,9xx tokens/s
    assert 25_800 < peaks("TPU v5 lite")["bf16_flops_per_s"] / want < 26_000


def test_decode_bytes_by_hand():
    c = Manifest(ROOT).load_config("internlm2-1.8b")
    weights = (24 * 62_914_560 + 92544 * 2048 + 24 * 2 * 2048 + 2048) * 2
    assert counts.decode_weight_bytes(c) == weights == 3_399_159_808
    # one position: 24 layers x (k + v) x 8 heads x 128 x 2 bytes
    assert counts.cache_row_bytes(c) == 98_304
    assert counts.decode_step_bytes(c, 7000) == weights + 7000 * 98_304
    # read once at 819e9 bytes/s that is ~5 ms: a 30 ms step is ~17% of it
    t = counts.decode_step_bytes(c, 7000) / peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert 0.0045 < t < 0.0055


def test_unknown_device_is_an_error():
    assert peaks("TPU v5e") == peaks("TPU v5 lite")
    with pytest.raises(KeyError):
        peaks("cpu")
    with pytest.raises(KeyError):
        peaks("_source")


@pytest.mark.parametrize("stalled", [0, 3])
def test_a_stall_moves_the_tail_and_leaves_the_middle(stalled):
    """`tpot_p50_ms` beside `serve.tpot_p95_ms`: twenty answers of 11 tokens
    5 ms apart; a stall of 90 ms inside `stalled` of them (what the chat
    cell's replica does once a minute, PERF.md section 6, PR 52) lifts the
    95th percentile by whole milliseconds and the median not at all; a run
    without rows reads nothing."""
    man = Manifest(ROOT)
    p50, p95 = (man.load_module("metrics", n).read
                for n in ("tpot_p50_ms", "serve.tpot_p95_ms"))
    rows = [{"ok": True, "max_new_tokens": 11, "n_tokens": 11,
             "arrivals_s": [i + 0.005 * k + (0.09 if i < stalled and k > 5 else 0.0)
                            for k in range(11)]} for i in range(20)]
    run = {"window_rows": rows, "seconds": 51}
    assert p50(run) == pytest.approx(5.0)
    assert p95(run) == pytest.approx(14.0 if stalled else 5.0)
    assert p50({"window_rows": []}) is None
