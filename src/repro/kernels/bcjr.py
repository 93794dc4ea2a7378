"""Max-log-MAP BCJR forward/backward scans as Pallas kernels.

Same structure as the Viterbi ACS scan (kernels/viterbi_scan.py) — the state
metrics live in VMEM scratch across all T grid steps, branch costs are an
``(S, F)`` weight matrix times the per-step feature column, and the state
gathers are (S, S) one-hot matmuls — run twice:

  alpha (forward)   exactly the Viterbi recursion over the RSC butterfly
                    (``A_{t+1}(s') = min_j [P_j @ A + b_j @ feat]``), but
                    every pre-update metric column ``A_t`` is streamed to
                    HBM because the backward pass needs it.
  beta + LLR        a time-REVERSED grid (the traceback-kernel idiom from
  (backward)        kernels/survivors.py): scratch carries ``B_{t+1}``, each
                    step emits the max-log LLR
                    ``L_t = min_s[A_t + gamma_t(s,1) + B_{t+1}(s'_1)]
                          - min_s[A_t + gamma_t(s,0) + B_{t+1}(s'_0)]``
                    and then retires ``B_t = min_a [N_a @ B + c_a @ feat]``.

All metrics are min-domain costs with the convention
``lambda = log P(0)/P(1)`` (cost of bit b = b * lambda), so a *negative* LLR
means "decide 1".  Max-log == Viterbi algebra, which is why the subtract-min
renormalization per step (the kernels' numerical guard for unbounded T)
cancels exactly in the emitted LLRs.

Both kernels are generic over the operand arrays (built by
``siso/rsc.RSCCode``'s cached properties) — like viterbi_scan they never
import the code object.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.trellis import NEG_UNREACHABLE
from repro.kernels.common import resolve_interpret

_HI = jax.lax.Precision.HIGHEST


def _state0_column(shape) -> jnp.ndarray:
    """(S, bB) init metrics: state 0 costs 0, everything else unreachable."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return jnp.where(row == 0, 0.0, NEG_UNREACHABLE)


def _alpha_kernel(p0_ref, p1_ref, b0_ref, b1_ref, data_ref,
                  out_a_ref, out_pm_ref, scratch, shift_acc):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        # the encoder starts in state 0 (same convention as Viterbi)
        scratch[...] = _state0_column(scratch.shape)
        shift_acc[...] = jnp.zeros_like(shift_acc)

    alpha = scratch[...]
    out_a_ref[0] = alpha  # pre-update A_t, consumed by the backward pass
    data = data_ref[0].astype(jnp.float32)
    cand0 = (jax.lax.dot(p0_ref[...], alpha, precision=_HI)
             + jax.lax.dot(b0_ref[...], data, precision=_HI))
    cand1 = (jax.lax.dot(p1_ref[...], alpha, precision=_HI)
             + jax.lax.dot(b1_ref[...], data, precision=_HI))
    new = jnp.minimum(cand0, cand1)
    # subtract-min renorm: keeps metrics bounded for any T; a per-(t, stream)
    # constant, so it cancels in the LLR extraction.  The shifts accumulate
    # so the terminal metrics can be reported in absolute cost units.
    shift = jnp.min(new, axis=0, keepdims=True)
    new = jnp.minimum(new - shift, NEG_UNREACHABLE)
    scratch[...] = new
    shift_acc[...] = shift_acc[...] + shift
    out_pm_ref[...] = new + shift_acc[...]


def _make_beta_kernel(terminated: bool):
    def kernel(n0_ref, n1_ref, u0_ref, u1_ref, c0_ref, c1_ref, w0_ref, w1_ref,
               a_ref, data_ref, out_llr_ref, scratch):
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _init():
            if terminated:
                scratch[...] = _state0_column(scratch.shape)
            else:
                scratch[...] = jnp.zeros_like(scratch)

        beta = scratch[...]  # B_{t+1} (grid step i handles t = T-1-i)
        alpha = a_ref[0]
        data = data_ref[0].astype(jnp.float32)
        # per-input-hypothesis total costs: A_t(s) + gamma_t(s, u) + B_{t+1}(s')
        cost0 = (alpha
                 + jax.lax.dot(w0_ref[...], data, precision=_HI)
                 + jax.lax.dot(u0_ref[...], beta, precision=_HI))
        cost1 = (alpha
                 + jax.lax.dot(w1_ref[...], data, precision=_HI)
                 + jax.lax.dot(u1_ref[...], beta, precision=_HI))
        out_llr_ref[0] = (jnp.min(cost1, axis=0, keepdims=True)
                          - jnp.min(cost0, axis=0, keepdims=True))
        # retire to B_t over the new-register-bit branches
        cand0 = (jax.lax.dot(n0_ref[...], beta, precision=_HI)
                 + jax.lax.dot(c0_ref[...], data, precision=_HI))
        cand1 = (jax.lax.dot(n1_ref[...], beta, precision=_HI)
                 + jax.lax.dot(c1_ref[...], data, precision=_HI))
        new = jnp.minimum(cand0, cand1)
        new = new - jnp.min(new, axis=0, keepdims=True)
        new = jnp.minimum(new, NEG_UNREACHABLE)
        scratch[...] = new

    return kernel


@functools.partial(jax.jit, static_argnums=(2, 3))
def bcjr_alpha_scan(
    mats: Tuple[jnp.ndarray, ...],
    feat: jnp.ndarray,
    block_b: int = 128,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Forward (alpha) scan.

    Args:
      mats: (P0, P1, b0, b1) — select matrices (S, S) + branch weights (S, F).
      feat: (T, F, B) per-step feature columns (channel LLRs + a-priori LLR).
        B must be a multiple of ``block_b``.
    Returns:
      alphas: (T, S, B) float32 — the PRE-update metrics A_t (A_0 is the
        state-0 init), renormalized per step.
      final_pm: (S, B) float32 — A_T in ABSOLUTE cost units (the per-step
        renorm shifts are accumulated and added back), so its min over
        states is the Viterbi best-path metric of the same trellis.
    """
    p0, p1, b0, b1 = mats
    T, F, B = feat.shape
    S = p0.shape[0]
    grid = (B // block_b, T)
    tbl = lambda r, c: pl.BlockSpec((r, c), lambda b, t: (0, 0))  # noqa: E731
    return pl.pallas_call(
        _alpha_kernel,
        grid=grid,
        in_specs=[
            tbl(S, S), tbl(S, S), tbl(S, F), tbl(S, F),
            pl.BlockSpec((1, F, block_b), lambda b, t: (t, 0, b)),
        ],
        out_specs=[
            pl.BlockSpec((1, S, block_b), lambda b, t: (t, 0, b)),
            pl.BlockSpec((S, block_b), lambda b, t: (0, b)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, S, B), jnp.float32),
            jax.ShapeDtypeStruct((S, B), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((S, block_b), jnp.float32),
            pltpu.VMEM((1, block_b), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(p0, p1, b0, b1, feat)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def bcjr_beta_llr_scan(
    mats: Tuple[jnp.ndarray, ...],
    alphas: jnp.ndarray,
    feat: jnp.ndarray,
    terminated: bool = False,
    block_b: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Backward (beta) scan fused with max-log LLR extraction.

    Args:
      mats: (N0, N1, U0, U1, c0, c1, w0, w1) from RSCCode's cached tables.
      alphas: (T, S, B) pre-update forward metrics from bcjr_alpha_scan.
      feat: (T, F, B) the same feature columns the forward pass consumed.
      terminated: trellis ends in state 0 (beta init [0, inf, ...]) vs open
        (uniform beta init).
    Returns:
      llr: (T, 1, B) float32 — ``log P(u_t=0) - log P(u_t=1)`` in max-log
        approximation; decide bit 1 where negative.  The singleton middle
        axis makes each step's (1, block_b) row a full-extent block, the
        only row-block layout Mosaic accepts (a (1, block_b) block of a
        (T, B) array is refused: its sublane dim is neither 8-aligned nor
        the whole axis).
    """
    n0, n1, u0, u1, c0, c1, w0, w1 = mats
    T, S, B = alphas.shape
    F = feat.shape[1]
    grid = (B // block_b, T)
    tbl = lambda r, c: pl.BlockSpec((r, c), lambda b, t: (0, 0))  # noqa: E731
    rev3 = lambda b, t: (T - 1 - t, 0, b)  # noqa: E731
    (llr,) = pl.pallas_call(
        _make_beta_kernel(bool(terminated)),
        grid=grid,
        in_specs=[
            tbl(S, S), tbl(S, S), tbl(S, S), tbl(S, S),
            tbl(S, F), tbl(S, F), tbl(S, F), tbl(S, F),
            pl.BlockSpec((1, S, block_b), rev3),
            pl.BlockSpec((1, F, block_b), rev3),
        ],
        out_specs=[pl.BlockSpec((1, 1, block_b), rev3)],
        out_shape=[jax.ShapeDtypeStruct((T, 1, B), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((S, block_b), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(n0, n1, u0, u1, c0, c1, w0, w1, alphas, feat)
    return llr
