"""The essential work of the lte_turbo.cb6144 shape, pinned: B=256 code
blocks of K=6144, two max-log-MAP passes of K + 3 = 6147 steps of the
8-state constituent per iteration."""
import pytest

from bench import roofline_turbo
from bench.peaks import PEAKS

# per step: alpha and beta ACS 2 * 4 * 8, the LLR 2 * (2 * 8 + 7) + 1,
# branch metrics 2 * 3 * 4; per information bit: the extrinsic, 3
STEP = 64 + 47 + 24
PASS_OPS = 6147 * STEP + 6144 * 3
# float32 channel LLRs (2 a step), a-priori in and extrinsic out (1 a bit each)
PASS_BYTES = 4 * (6147 * 2 + 2 * 6144)


@pytest.mark.parametrize("iterations", [1, 2, 8])
def test_cb6144_essential_work(iterations):
    assert roofline_turbo.STEP_OPS == 135
    ops = roofline_turbo.turbo_ops(256, 6144, iterations)
    nbytes = roofline_turbo.turbo_bytes(256, 6144, iterations)
    assert ops == 256 * iterations * 2 * PASS_OPS
    assert nbytes == 256 * iterations * 2 * PASS_BYTES
    t, bound = roofline_turbo.roofline_s(ops, nbytes, PEAKS["TPU v5 lite"])
    assert bound == "memory"
    assert t == pytest.approx(nbytes / 819e9)


def test_eight_iterations_of_a_call():
    assert roofline_turbo.turbo_bytes(256, 6144, 8) == 402_751_488
    assert roofline_turbo.turbo_ops(256, 6144, 8) == pytest.approx(3.474e9, rel=1e-3)
