"""The trace -> metrics reduction, on fixed events and on the small trace
recorded on a TPU v5e that is kept beside it."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import xplane  # noqa: E402

RECORDED = os.path.join(ROOT, "perfbench", "lib", "testdata", "toy_tpu_1.xplane.pb")


def _planes(second_device=False):
    ops = [("%while.1 = (s32[]) while(...)", 0, 100),
           ("%fusion.1 = bf16[8,128]{1,0:T(8,128)} fusion(x)", 0, 40),
           ("%all-gather.2 = bf16[16,128]{1,0} all-gather(y)", 40, 20),
           ("%fusion.2 = bf16[8,128]{1,0} fusion(x)", 60, 30),
           ("%copy.3 = f32[4]{0} copy(z)", 150, 50)]
    planes = {
        "/device:TPU:0": {"XLA Ops": ops, "XLA Modules": [
            ("jit_step(123)", 0, 100), ("jit_step(123)", 150, 50)]},
        "/host:CPU": {"python": [("bench.train_step", 90, 80),
                                 ("bench.wait_input", 100, 30)]}}
    if second_device:  # the same stream, its all-gather hidden under compute
        planes["/device:TPU:1"] = {"XLA Ops": ops[:2] + ops[3:] + [
            ("%fusion.9 = bf16[8,128]{1,0} fusion(x)", 40, 20)]}
    return planes


def test_busy_idle_top_ops_and_gaps_on_fixed_events():
    r = xplane.reduce(_planes())
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["busy_s"] == pytest.approx(150e-9)       # [0,100) and [150,200)
    ops = dict(r["device_ops"])
    assert ops["copy.3_f32_4_"] == pytest.approx(50e-9)
    assert ops["fusion.1_bf16_8_128_"] == pytest.approx(40e-9)
    assert ops["while.1__s32__"] == pytest.approx(10e-9)  # self time only
    # the one gap, [100,150): its middle lies in both spans, innermost wins
    assert r["idle_gaps"] == [["bench.wait_input", pytest.approx(50e-9)]]
    assert r["module_ms_p50"]["jit_step"] == pytest.approx(75e-6)
    assert r["module_calls"]["jit_step"] == 2


def test_exposed_collective_time_is_what_no_compute_covers():
    assert xplane.reduce(_planes())["exposed_collective_s"] == pytest.approx(20e-9)
    both = xplane.reduce(_planes(second_device=True))
    assert both["devices"] == 2
    # exposed on chip 0 (20 ns), none on chip 1: the mean over chips
    assert both["exposed_collective_s"] == pytest.approx(10e-9)
    assert both["busy_s"] == pytest.approx(150e-9)


def test_no_device_plane_is_an_error_not_a_zero():
    with pytest.raises(ValueError, match="no device plane"):
        xplane.reduce({"/host:CPU": {"python": [("bench.train_step", 0, 10)]}})


def test_op_label():
    assert xplane.op_label(
        "%copy.65 = bf16[24,32,8,1024,128]{4,3,2,1,0:T(8,128)(2,1)} copy(%p)"
    ) == "copy.65_bf16_24_32_8_1024_128_"
    assert xplane.op_label("plain-name") == "plain-name"


def test_recorded_v5e_trace_reduces():
    """Three steps of a toy program on one TPU v5 lite, each under
    `bench.train_step`, with a 2 ms `bench.wait_input` pause between."""
    planes = xplane.load(RECORDED)
    assert "/device:TPU:0" in planes
    assert {"XLA Ops", "XLA Modules"} <= set(planes["/device:TPU:0"])
    spans = [n for v in planes["/host:CPU"].values() for n, _, _ in v]
    assert spans.count("bench.train_step") == 3
    r = xplane.reduce(planes)
    assert r["devices"] == 1 and r["module_calls"] == {"jit_step": 3}
    assert 0 < r["busy_s"] < r["window_s"] < 0.05
    assert r["exposed_collective_s"] == 0.0
    assert r["device_ops"][0][0].startswith("fusion")
    # busy time is the three module executions, to within their edges
    assert r["busy_s"] == pytest.approx(3 * r["module_ms_p50"]["jit_step"] / 1e3,
                                        rel=0.1)
    # the two long gaps are the pauses between steps
    assert [g[1] > 1e-3 for g in r["idle_gaps"][:3]] == [True, True, False]


def test_recorded_four_chip_trace_has_collectives_on_every_chip():
    """The same toy on the four chips of one v5e host, its rows sharded: the
    column sum is an all-reduce, and every chip has a plane of its own."""
    planes = xplane.load(os.path.join(os.path.dirname(RECORDED),
                                      "toy_tpu_4.xplane.pb"))
    assert sorted(p for p in planes if p.startswith("/device:")) == [
        f"/device:TPU:{i}" for i in range(4)]
    names = {n for p in planes if p.startswith("/device:")
             for n, _, _ in planes[p]["XLA Ops"]}
    assert any("all-reduce" in n for n in names)
    r = xplane.reduce(planes)
    assert r["devices"] == 4 and r["module_calls"]["jit_step"] == 12
    assert 0 < r["exposed_collective_s"] < r["busy_s"] < r["window_s"]
