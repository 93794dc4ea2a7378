"""In-kernel branch metrics: fold the metric computation into the scan kernel.

Every branch metric this repo uses is affine in the received symbols:

  hard (Hamming)        bm(c) = Σ_j |r_j - x_cj|          (r ∈ {0,1})
                              = Σ_j (1 - 2 x_cj) r_j + Σ_j x_cj
  hard + puncture mask  bm(c) = Σ_j m_j |r_j - x_cj|
                              = Σ_j (1 - 2 x_cj)(m_j r_j) + Σ_j x_cj m_j
  soft (correlation)    bm(c) = Σ_j (2 x_cj - 1) y_j      (y real, mask
                                                           pre-applied)

i.e. ``bm = W @ feat + bias`` with a static (M, F) weight, a static (M,)
bias, and F = n (or 2n punctured-hard) per-step *features* — versus the
M = 2^n entries of a precomputed table.  Folding W through the branch
one-hots (one-hot matmuls are exact row selections) turns the scan kernel's
per-parity metric lookup into ``b_j @ feat + rb_j`` directly, so the kernel
streams raw received symbols and never touches a bm table: per-step HBM
reads drop from M·B to F·B floats and the metric add rides the same MXU
matmul that did the table lookup.

A FusedMetricPlan bundles (W, bias, feature builder) for one
(code, metric kind, puncture) combination; ``folded()`` yields the kernel
operands.  Integer-valued plans (hard metrics) are bit-exact against the
table path; soft plans agree to float32 rounding.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.puncture import host_pattern_mask
from repro.core.trellis import ConvCode


@functools.lru_cache(maxsize=None)
def _phase_mask(
    code: ConvCode, T: int, pattern: Tuple[Tuple[int, ...], ...], phase: int
) -> jnp.ndarray:
    """(T, n) 0/1 puncture mask for trellis steps starting at ``phase``
    within the pattern period (callers reduce an absolute t0 mod period, so
    the key space — and the cache — is bounded by the period).  Build is
    O(T) however deep into a stream the chunk starts; a steady-state
    received session (fixed chunk, cycling phases) pays the host tile +
    device transfer once per phase, not once per push."""
    return jnp.asarray(_host_phase_mask(code, T, pattern, phase))


@functools.lru_cache(maxsize=None)
def _host_phase_mask(
    code: ConvCode, T: int, pattern: Tuple[Tuple[int, ...], ...], phase: int
) -> np.ndarray:
    """:func:`_phase_mask` on the host, read-only: the cache hands the same
    array to every caller."""
    # puncture pattern is a python tuple-of-tuples — host data, not a sync
    pat = np.asarray(pattern)  # repr-lint: allow[RPR003]
    mask = host_pattern_mask(code, phase + T, pat)[phase:]
    mask.setflags(write=False)
    return mask


@dataclasses.dataclass(frozen=True)
class FusedMetricPlan:
    """Static affine form of one branch metric + its feature builder."""

    code: ConvCode
    metric: str  # "hard" | "soft"
    puncture: Optional[Tuple[Tuple[int, ...], ...]]
    weight: np.ndarray  # (M, F)
    bias: np.ndarray  # (M,)

    @property
    def n_features(self) -> int:
        return self.weight.shape[1]

    def features(self, received: jnp.ndarray, t0: int = 0) -> jnp.ndarray:
        """(..., T, n_out) raw channel output -> (..., T, F) kernel features.

        ``t0`` is the absolute trellis step of the first row — it phases the
        puncture mask for mid-stream chunks.
        """
        r = received.astype(jnp.float32)
        if self.puncture is None:
            return r
        period = len(self.puncture[0])
        mask = _phase_mask(self.code, r.shape[-2], self.puncture, t0 % period)
        if self.metric == "soft":
            return r * mask  # erased positions correlate to 0
        return jnp.concatenate([r * mask, jnp.broadcast_to(mask, r.shape)], axis=-1)

    def host_features(self, received: np.ndarray, t0: int = 0) -> np.ndarray:
        """:meth:`features` in NumPy, for rows still on the host: the same
        float32 values bit for bit (a multiply by 0/1 and a concatenation),
        in a fresh array even where no puncture applies."""
        r = received.astype(np.float32)
        if self.puncture is None:
            return r
        period = len(self.puncture[0])
        mask = _host_phase_mask(self.code, r.shape[-2], self.puncture, t0 % period)
        if self.metric == "soft":
            return r * mask
        return np.concatenate([r * mask, np.broadcast_to(mask, r.shape)], axis=-1)

    def bm_from_features(self, feats: jnp.ndarray) -> jnp.ndarray:
        """(..., T, F) features -> (..., T, M) bm tables: the affine form
        evaluated outside the kernel (streaming tail chunks that take the
        lax.scan reference path).  Bit-exact vs the table builders for
        integer-valued (hard) metrics."""
        W = jnp.asarray(self.weight)
        return jnp.einsum("...tf,mf->...tm", feats, W) + jnp.asarray(self.bias)

    def bm_tables(self, received: jnp.ndarray, t0: int = 0) -> jnp.ndarray:
        """(..., T, n_out) raw symbols -> (..., T, M) bm tables."""
        return self.bm_from_features(self.features(received, t0))

    def folded(self) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Kernel operands: (b0 (S, F), b1 (S, F), rb (S, 2)).

        The branch one-hots are 0/1 row selectors, so ``OH_j @ W`` just
        re-indexes W per successor state — exact, no precision cost.
        """
        OH0, OH1 = self.code.branch_onehot_pair
        b0 = OH0 @ self.weight
        b1 = OH1 @ self.weight
        rb = np.stack([OH0 @ self.bias, OH1 @ self.bias], axis=1)
        return (
            jnp.asarray(b0, jnp.float32),
            jnp.asarray(b1, jnp.float32),
            jnp.asarray(rb, jnp.float32),
        )


def fused_metric_plan(
    code: ConvCode,
    metric: str = "hard",
    puncture: Optional[np.ndarray] = None,
) -> FusedMetricPlan:
    """Build the affine in-kernel form of a branch metric (see module doc)."""
    # plan construction: symbol table / puncture rows are host numpy inputs
    X = np.asarray(code.symbol_bits, np.float64)  # repr-lint: allow[RPR003]
    punct = (
        None
        if puncture is None
        else tuple(
            tuple(int(v) for v in row)
            for row in np.asarray(puncture)  # repr-lint: allow[RPR003]
        )
    )
    if metric == "soft":
        W = 2.0 * X - 1.0
        bias = np.zeros((X.shape[0],))
    elif punct is None:
        W = 1.0 - 2.0 * X
        bias = X.sum(axis=1)
    else:
        # features are [masked bits | mask]: Σ m|r-x| = (1-2X)@(mr) + X@m
        W = np.concatenate([1.0 - 2.0 * X, X], axis=1)
        bias = np.zeros((X.shape[0],))
    if metric not in ("hard", "soft"):
        raise ValueError(f"metric must be 'hard' or 'soft', got {metric!r}")
    return FusedMetricPlan(
        code=code,
        metric=metric,
        puncture=punct,
        weight=W.astype(np.float32),
        bias=bias.astype(np.float32),
    )
