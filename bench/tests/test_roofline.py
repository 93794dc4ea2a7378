"""The essential work of the wifi_bcc34.imix shapes, pinned: B=512 frames
of T = 630, 4918 and 12310 trellis steps of the K=7 rate-1/2 mother code."""
import pytest

from bench import roofline
from bench.peaks import PEAKS, peaks


@pytest.mark.parametrize("steps, ops, nbytes", [
    (630, 512 * 630 * (4 * 64 + 2 * 2 * 4), 512 * 630 * (2 * 4 + 1 / 8)),
    (4918, 512 * 4918 * (4 * 64 + 2 * 2 * 4), 512 * 4918 * (2 * 4 + 1 / 8)),
    (12310, 512 * 12310 * 272, 512 * 12310 * 8.125),
])
def test_imix_essential_work(steps, ops, nbytes):
    assert roofline.viterbi_ops(512, steps, 7, 2) == ops
    assert roofline.viterbi_bytes(512, steps, 2) == nbytes
    t, bound = roofline.roofline_s(ops, nbytes, PEAKS["TPU v5 lite"])
    assert bound == "memory"
    assert t == pytest.approx(nbytes / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(ValueError):
        peaks("TPU v9000")
