"""Essential work of an LTE turbo decode, independent of how it is computed.

Per iteration, two max-log-MAP passes, one per constituent, each over
T = K + 3 trellis steps (the K information steps and the constituent's three
tail steps) of S = 8 states.  Operations per step of a pass:

* alpha and beta: one add-compare-select per state each (two adds, one
  compare, one select = 4 operations);
* the LLR: for each input hypothesis, alpha + branch + beta over the S
  transitions (2 adds each) and a minimum over them (S - 1 compares), then
  one subtraction;
* the branch metrics: each of the 2^2 (systematic, parity) labels is a
  multiply-add over the 3 features (systematic, parity, a-priori LLRs).

and, per information bit of a pass, the extrinsic value (two subtractions
and the scaling).  Bytes per pass: the float32 channel LLRs read (2 per
step), the a-priori LLRs read and the extrinsic values written (1 each per
information bit).  The alpha round trip, one-hot matrix products, the
interleaver's gathers and padding are the implementation's choices and do
not count.  Every iteration the loop runs counts whole: frozen streams are
recomputed.
"""
from __future__ import annotations

from bench.roofline import ACS_OPS, roofline_s  # noqa: F401  (re-exported)

STATES = 8
TAIL_STEPS = 3
CODED = 2  # channel LLRs per step of a pass: systematic, parity
FEATURES = CODED + 1  # and the a-priori LLR
LLR_BYTES = 4  # float32
EXTRINSIC_OPS = 3

STEP_OPS = (2 * ACS_OPS * STATES  # alpha and beta
            + 2 * (2 * STATES + STATES - 1) + 1  # the LLR's two minimisations
            + 2 * FEATURES * (1 << CODED))  # branch metrics


def turbo_ops(batch: int, k: int, iterations: int) -> float:
    per_pass = (k + TAIL_STEPS) * STEP_OPS + k * EXTRINSIC_OPS
    return float(batch) * iterations * 2 * per_pass


def turbo_bytes(batch: int, k: int, iterations: int) -> float:
    per_pass = LLR_BYTES * ((k + TAIL_STEPS) * CODED + 2 * k)
    return float(batch) * iterations * 2 * per_pass
