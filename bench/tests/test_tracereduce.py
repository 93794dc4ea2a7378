"""The trace reduction, on a hand-made trace and on a recorded chip trace
(fixtures/short8_trace.json.gz: 0.25 s of wifi_bcc34.short8-shaped decode()
calls on one TPU v5e, as tracereduce.load_xplane read it; the numbers
pinned below are what the reduction read from it when it was recorded)."""
from pathlib import Path

import pytest

from bench import tracereduce

FIXTURE = Path(__file__).parent / "fixtures" / "short8_trace.json.gz"


def test_hand_made_trace():
    raw = {
        "host": [["bench.window", 1000, 1000], ["bench.decode", 1000, 300],
                 ["bench.wait", 1300, 700]],
        "devices": {
            "0": [["kernel", 1100, 200, True], ["fusion", 1250, 100, False],
                  ["copy", 1800, 400, False]],  # ends past the window
            "1": [["kernel", 1000, 500, True]],
        },
    }
    s = tracereduce.reduce(raw)
    assert s.window_s == pytest.approx(1000e-9)
    # chip 0 busy [1100, 1350) and [1800, 2000); chip 1 [1000, 1500)
    assert s.busy_s == pytest.approx((450e-9 + 500e-9) / 2)
    assert s.kernel_s == pytest.approx(700e-9)
    assert s.glue_s == pytest.approx(300e-9)
    assert s.idle_share == pytest.approx(1 - 475 / 1000)
    # chip 0's gaps: [1000,1100) under decode, [1350,1800) under wait
    assert s.gaps == [("bench.wait", pytest.approx(450e-9)),
                      ("bench.decode", pytest.approx(100e-9))]
    spans = tracereduce.program_spans_on_trace_clock([("commit", 50, 10)], 40, raw)
    assert spans == [("tick.commit", 1010, 10)]


def test_recorded_chip_trace():
    raw = tracereduce.load_raw(str(FIXTURE))
    s = tracereduce.reduce(raw)
    assert s.window_s == pytest.approx(0.257523953)
    assert s.busy_s == pytest.approx(0.009477847)
    assert s.kernel_s == pytest.approx(0.00908611)
    assert s.glue_s == pytest.approx(0.000391737)
    # every operation inside the window is either kernel or glue time
    assert sum(s.op_s.values()) == pytest.approx(s.kernel_s + s.glue_s)
    top = tracereduce.breakdown(s)["device_ops"]
    assert [name for name, _ in top[:2]] == [
        "jit_viterbi_scan_packed:%viterbi_scan_packed.1",
        "jit_traceback_packed:%traceback_packed.1",
    ]
    assert s.gaps[0] == ("bench.decode", pytest.approx(0.001983537))
