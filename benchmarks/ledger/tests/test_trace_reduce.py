"""``trace_reduce`` on ``recorded.xplane.pb`` (made on the chip by
``record_trace.py``), against values counted by hand from its event list.

The recorded window (host ``bench.window``) runs from 47,839,989 ns for
17,019,469 ns. Device events, in ns (start, duration):
  jit_alpha #1  46,474,426  373,716   -> before the window (the device clock
                                         leads the host's by ~1.5 ms): dropped
  jit_beta  #1  51,682,713   12,685   one operation 51,682,716 + 12,681
  jit_alpha #2  52,383,536  373,942   operations 13+12,743+89,928+89,954+
                                      90,176+90,963 = 373,777
  jit_beta  #2  56,973,521   12,446   one operation, 12,443
  jit_alpha #3  57,866,842  374,033   operations 13+12,831+89,927+89,955+
                                      90,176+90,966 = 373,868
  jit_beta  #3  62,490,177   12,470   one operation, 12,467
"""
import os

import pytest

import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(__file__), "recorded.xplane.pb")
NS = 1e-9


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_file(RECORDED)


def test_window_and_busy_union(reduced):
    assert reduced["window_s"] == pytest.approx(17_019_469 * NS, rel=1e-9)
    busy = 12_681 + 373_777 + 12_443 + 373_868 + 12_467
    dev = reduced["devices"][0]
    # operation starts are rounded to whole ns in the file: 1 ns a piece
    assert dev["busy_s"] == pytest.approx(busy * NS, abs=30 * NS)
    assert reduced["busy_s"] == dev["busy_s"]
    assert dev["idle_share"] == pytest.approx(1 - busy / 17_019_469, abs=1e-5)


def test_time_by_module(reduced):
    dev = reduced["devices"][0]
    assert dev["module_runs"] == {"jit_alpha": 2, "jit_beta": 3}
    assert dev["module_s"]["jit_alpha"] == pytest.approx(
        (373_942 + 374_033) * NS, abs=2 * NS)
    assert dev["module_s"]["jit_beta"] == pytest.approx(
        (12_685 + 12_446 + 12_470) * NS, abs=3 * NS)


def test_time_by_operation(reduced):
    ops = reduced["devices"][0]["op_s"]
    seconds, count = ops["convolution_tanh_fusion bf16[2048,2048]"]
    assert count == 8
    assert seconds == pytest.approx(
        (89_928 + 89_954 + 90_176 + 90_963
         + 89_927 + 89_955 + 90_176 + 90_966) * NS, abs=8 * NS)
    assert ops["multiply_reduce_fusion f32[2048]"][1] == 3
    top = tr.breakdown(reduced)["device_ops"][0]
    assert top[0] == "convolution_tanh_fusion bf16[2048,2048] x8"


def test_gaps_go_to_the_host_span_open_at_their_middle(reduced):
    gaps = reduced["devices"][0]["gaps"]
    # every gap's middle lies in a bench.feed (the sleep) or a bench.step
    assert set(gaps) == {"bench.feed", "bench.step"}
    busy = reduced["devices"][0]["busy_s"]
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - busy, rel=1e-9)
    # gaps (ns) and the host span at their middle, by hand:
    #  window start 47,839,989 .. beta#1 51,682,716    3,842,727  feed #1
    #  beta#1 end 51,695,397 .. alpha#2 52,383,542       688,145  feed #1
    #  between alpha#2's operations                            6  feed #1
    #  alpha#2 end 52,757,325 .. beta#2 56,973,523     4,216,198  step #2
    #  beta#2 end 56,985,966 .. alpha#3 57,866,848       880,882  feed #2
    #  between alpha#3's operations                            7  feed #2
    #  alpha#3 end 58,240,723 .. beta#3 62,490,180     4,249,457  step #3
    #  beta#3 end 62,502,647 .. window end 64,859,458  2,356,811  feed #3
    # (feed #1 is 49,253,469..53,033,178, step #2 53,811,068..54,945,708,
    #  feed #2 54,953,838..58,473,448, step #3 59,266,058..60,375,948,
    #  feed #3 60,379,518..63,919,718)
    feed = 3_842_727 + 688_145 + 6 + 880_882 + 7 + 2_356_811
    step = 4_216_198 + 4_249_457
    assert gaps["bench.feed"] == pytest.approx(feed * NS, abs=20 * NS)
    assert gaps["bench.step"] == pytest.approx(step * NS, abs=20 * NS)
    assert tr.breakdown(reduced)["idle_gaps"][0][0] == "bench.step"


def test_union_and_names():
    assert tr._union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    text = ("%all-gather-start.12 = (bf16[8,128]{1,0}, bf16[16,128]{1,0}) "
            "all-gather-start(bf16[8,128]{1,0} %p), dimensions={0}")
    assert tr.op_kind(text) == "all-gather-start"
    assert tr.op_key(text) == "all-gather-start bf16[8,128]"
    assert tr.reduce_planes({}, []) is None
