"""The reduction from a device trace to numbers, pinned on a trace
recorded on one TPU v5e chip (``tools/record_trace.py``: four steps of a
toy OLMo-2-shaped train step, 2 ms of sleep between them)."""
import os

import pytest

from benchmarks.lib import reduce_trace as rt

TRACE = os.path.join(os.path.dirname(__file__), "data", "train1.xplane.pb.gz")


@pytest.fixture(scope="module")
def reduced():
    return rt.reduce_trace(TRACE)


def test_devices_window_and_busy(reduced):
    assert reduced["n_devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.018139843, rel=1e-6)
    assert reduced["busy_s"] == pytest.approx(0.004246937, rel=1e-6)
    assert reduced["idle_share"] == pytest.approx(0.76587796, rel=1e-6)


def test_programs_by_name(reduced):
    step = reduced["modules"]["jit_pure_step(17609479216135773298)"]
    assert step["count"] == 4
    assert step["median_s"] == pytest.approx(0.001069851, rel=1e-6)
    # the device is busy only while a program runs, and a little less
    assert reduced["busy_s"] <= sum(m["total_s"]
                                    for m in reduced["modules"].values())


def test_pallas_kernels_are_found_by_their_target(reduced):
    kernels = reduced["kernels"]
    assert kernels["splash_mha_fwd_residuals"]["count"] == 8     # 2 layers x 4
    assert kernels["splash_mha_dkv_no_residuals"]["total_s"] == \
        pytest.approx(0.000353871, rel=1e-6)
    # XLA's own custom-calls (ConcatBitcast) are not kernels
    assert not any("custom-call" in k for k in kernels)
    assert reduced["pallas_s"] == pytest.approx(0.001032207, rel=1e-6)


def test_self_time_does_not_count_a_loop_body_twice(reduced):
    ops = reduced["ops"]
    assert sum(o["self_s"] for o in ops.values()) == pytest.approx(
        reduced["busy_s"], rel=0.02)
    assert ops["while (tuple) [while]"]["self_s"] < 1e-4


def test_no_collective_on_one_chip(reduced):
    assert reduced["collective_s"] == 0.0
    assert reduced["collective_exposed_s"] == 0.0


def test_breakdown_names_gaps_by_the_benchmarks_own_spans(reduced):
    b = reduced["breakdown"]
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "fusion (tuple) [fusion]"
    assert ["splash_mha_dkv_no_residuals [pallas]", 0.000353871] in [
        [n, round(s, 9)] for n, s in b["device_ops"]]
    assert any(n == "copy bf16[2,1024,4,128] [copy]"
               for n, _ in b["device_ops"])
    name, seconds = b["idle_gaps"][0]
    assert name.startswith("bench/trace_slice | after jit_")
    assert seconds == pytest.approx(0.008354783, rel=1e-6)
    gaps = sum(s for _, s in reduced["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx(reduced["window_s"] - reduced["busy_s"],
                                 rel=0.01)


def test_parse_op():
    text = ('%all-gather-start.3 = (bf16[8,128]{1,0}, bf16[16,128]{1,0}) '
            'all-gather-start(bf16[8,128]{1,0} %p), dimensions={0}')
    name, opcode, pallas = rt.parse_op(text)
    assert (name, opcode, pallas) == ("all-gather-start", "all-gather-start",
                                      False)
    assert rt.COLLECTIVE.match(opcode)
    assert rt.parse_op('%k.1 = f32[8]{0} custom-call(f32[8]{0} %x), '
                       'custom_call_target="tpu_custom_call"')[2]
    assert rt.result_shape(text) == "(tuple)"
    assert rt.result_shape("%copy.3 = bf16[8,2048,16,128]{3,2,1,0:T(8,128)"
                           "(2,1)} copy(bf16[8,2048,16,128]{3,2,1,0} %p)") \
        == "bf16[8,2048,16,128]"


def test_interval_arithmetic():
    a = rt.union([(0, 10), (5, 12), (20, 30)])
    assert a == [(0, 12), (20, 30)]
    assert rt.subtract(a, [(2, 4), (11, 25)]) == [(0, 2), (4, 11), (25, 30)]
    # a collective from 0 to 10 with compute from 3 to 6: 7 exposed
    assert rt.length(rt.subtract([(0, 10)], [(3, 6)])) == 7
    events = [(0, 100, "while"), (10, 40, "a"), (50, 90, "b"), (60, 70, "c")]
    assert rt.self_times(events) == [30, 30, 30, 10]
