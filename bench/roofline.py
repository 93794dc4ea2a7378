"""Essential work of a Viterbi decode, independent of how it is computed.

Operations: per trellis step and state, one add-compare-select (two adds,
one compare, one select = 4 operations), plus the branch metrics (one
multiply-add per code bit for each of the 2**n code symbols).  Bytes: the
received float32 symbols read once (every code position of the call's
input, erasures included, as the entry point takes them) and one decoded
bit written per trellis step.  One-hot matrix products, survivor round
trips and padding are the implementation's choices and do not count.
"""
from __future__ import annotations

from bench.peaks import Peaks

ACS_OPS = 4
SYMBOL_BYTES = 4  # float32 received symbols


def viterbi_ops(batch: int, steps: int, constraint: int, n_out: int) -> float:
    states = 1 << (constraint - 1)
    return float(batch) * steps * (ACS_OPS * states + 2 * n_out * (1 << n_out))


def viterbi_bytes(batch: int, steps: int, n_out: int) -> float:
    return float(batch) * steps * (n_out * SYMBOL_BYTES + 1 / 8)


def roofline_s(ops: float, nbytes: float, peaks: Peaks):
    """(least time on the chip, which bound binds).

    The compute roof is the chip's published peak, ``peaks.ops_per_s``: the
    matrix unit's bf16 rate.  Add-compare-select is vector-unit work, which
    cannot reach that rate and has no published peak of its own, so
    ``t_ops`` is a lower bound: a share read against this roofline never
    overstates, and "memory" here means only that memory binds at that
    peak.  Which bound really binds is unresolved: compute does wherever
    the vector unit's rate lies below the work's operations per byte times
    the HBM bandwidth."""
    t_ops, t_mem = ops / peaks.ops_per_s, nbytes / peaks.hbm_bytes_per_s
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
