"""Full-sequence Viterbi forward pass as a single Pallas kernel.

This is the strongest TPU analogue of the paper's custom instruction: the
path metrics stay **resident in VMEM scratch across all T trellis steps** —
they never round-trip to HBM, exactly like the microcoded Texpand keeps its
operands out of the fetch/decode path.  The grid iterates (batch-tile, time);
TPU grid execution is sequential, so scratch carries state across time steps.

One entry (:func:`viterbi_scan`) and one parameterized kernel body
(`_make_scan_kernel`) serve every caller — block, streaming and tiled:

  init path      ``pm0=None`` seeds pm = [0, +inf, ...] in-kernel (paper
                 §IV-B, paths start in state 0); a ``pm0`` input seeds the
                 carried metrics (the streaming chunk scan, the tiled launch).
  branch metrics the per-step input is a generic ``(F, bB)`` tile multiplied
                 by an ``(S, F)`` weight pair plus an ``(S, 2)`` bias.  With
                 weights = the branch one-hots and F = n_symbols this is the
                 classic precomputed bm-table path; with weights = the folded
                 metric matrices of kernels/metrics.py and F = n features the
                 kernel computes hard/soft/punctured branch metrics from raw
                 received symbols **in-kernel**, cutting the per-step HBM
                 read from M·B to F·B floats (M = 2^n symbols vs F = n raw
                 values per step).
  survivors      the ACS select bits accumulate in a uint32 scratch word and
                 leave as ``(ceil(T/32), S, B)`` packed words — one bit per
                 (step, state, stream), which kernels/survivors.py traces
                 back without ever unpacking in HBM.
  validity       a ``window`` adds per-lane int32 ``(lo, hi)`` rows: a
                 lane only runs ACS on steps ``lo <= t < hi`` and passes its
                 path metrics through unchanged (survivor bit forced 0)
                 elsewhere.  This is what lets the tiled decoder fold P
                 time-tiles of *different* effective lengths (front warm-up,
                 ragged T%P / T%32 tails) into one uniform batched launch —
                 see kernels/tiling.py.

Per grid step:  data tile (F, bB) streams in;  1/32nd of a packed survivor
                tile (S, bB) streams out;  pm (S, bB) lives in scratch.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.trellis import NEG_UNREACHABLE, ConvCode
from repro.kernels.common import PACK_BITS, resolve_interpret


def _make_scan_kernel(carry: bool, windowed: bool = False):
    """Build the ACS scan kernel for one (init path, validity) combo.

    Ref order: p0, p1, b0, b1, rb, [pm0], [lo, hi], data, out_bp, out_pm,
    pm_scratch, pack_scratch.
    """

    def kernel(*refs):
        refs = list(refs)
        p0_ref, p1_ref, b0_ref, b1_ref, rb_ref = refs[:5]
        del refs[:5]
        pm0_ref = refs.pop(0) if carry else None
        lo_ref, hi_ref = (refs.pop(0), refs.pop(0)) if windowed else (None, None)
        data_ref, out_bp_ref, out_pm_ref, pm_scratch, pack_scratch = refs
        t = pl.program_id(1)

        @pl.when(t == 0)
        def _init():
            if carry:
                pm_scratch[...] = pm0_ref[...]
            else:
                # paths start in state 0 (paper §IV-B): pm = [0, +inf, ...]
                row = jax.lax.broadcasted_iota(jnp.int32, pm_scratch.shape, 0)
                pm_scratch[...] = jnp.where(row == 0, 0.0, NEG_UNREACHABLE)

        pm = pm_scratch[...]
        data = data_ref[0].astype(jnp.float32)
        hi = jax.lax.Precision.HIGHEST
        cand0 = (
            jax.lax.dot(p0_ref[...], pm, precision=hi)
            + jax.lax.dot(b0_ref[...], data, precision=hi)
            + rb_ref[:, 0:1]
        )
        cand1 = (
            jax.lax.dot(p1_ref[...], pm, precision=hi)
            + jax.lax.dot(b1_ref[...], data, precision=hi)
            + rb_ref[:, 1:2]
        )
        take1 = cand1 < cand0
        new_pm = jnp.where(take1, cand1, cand0)
        # clamp: unreachable-state metrics grow by BIG per matmul otherwise
        new_pm = jnp.minimum(new_pm, NEG_UNREACHABLE)
        if windowed:
            # outside a lane's [lo, hi) validity window the metrics pass
            # through untouched and the survivor bit is forced to 0 — the
            # step simply does not exist for that lane
            valid = (t >= lo_ref[...]) & (t < hi_ref[...])  # (1, bB)
            take1 = take1 & valid
            new_pm = jnp.where(valid, new_pm, pm)
        pm_scratch[...] = new_pm
        out_pm_ref[...] = new_pm.astype(out_pm_ref.dtype)

        pos = (t % PACK_BITS).astype(jnp.uint32)
        bit = take1.astype(jnp.uint32) << pos
        # pos == 0 starts a fresh word (the masked read of uninitialized
        # scratch on the first step is discarded by the where)
        word = jnp.where(pos == 0, jnp.uint32(0), pack_scratch[...]) | bit
        pack_scratch[...] = word
        # the out tile stays VMEM-resident for 32 steps (its block index
        # is t // 32); the value at the window's last visit — the fully
        # packed word — is what lands in HBM.
        out_bp_ref[0] = word

    return kernel


def _scan_call(
    code: ConvCode,
    pm0: Optional[jnp.ndarray],
    data: jnp.ndarray,
    b0: jnp.ndarray,
    b1: jnp.ndarray,
    rb: jnp.ndarray,
    block_b: int,
    interpret: Optional[bool],
    window: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The pallas_call plumbing of :func:`viterbi_scan`."""
    T, F, B = data.shape
    S = code.n_states
    P0, P1 = code.select_matrices
    carry = pm0 is not None
    grid = (B // block_b, T)  # time innermost: scratch carries pm across t
    tbl = lambda r, c: pl.BlockSpec((r, c), lambda b, t: (0, 0))  # noqa: E731
    in_specs = [tbl(S, S), tbl(S, S), tbl(S, F), tbl(S, F), tbl(S, 2)]
    args = [jnp.asarray(P0), jnp.asarray(P1), b0, b1, rb]
    if carry:
        in_specs.append(pl.BlockSpec((S, block_b), lambda b, t: (0, b)))
        args.append(pm0)
    if window is not None:
        lo, hi = window
        for w in (lo, hi):
            in_specs.append(pl.BlockSpec((1, block_b), lambda b, t: (0, b)))
            args.append(w.astype(jnp.int32))
    in_specs.append(pl.BlockSpec((1, F, block_b), lambda b, t: (t, 0, b)))
    args.append(data)
    n_words = pl.cdiv(T, PACK_BITS)
    bp_spec = pl.BlockSpec(
        (1, S, block_b), lambda b, t: (t // PACK_BITS, 0, b)
    )
    bp_shape = jax.ShapeDtypeStruct((n_words, S, B), jnp.uint32)
    scratch = [
        pltpu.VMEM((S, block_b), jnp.float32),
        pltpu.VMEM((S, block_b), jnp.uint32),
    ]
    bps, final_pm = pl.pallas_call(
        _make_scan_kernel(carry, windowed=window is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=[bp_spec, pl.BlockSpec((S, block_b), lambda b, t: (0, b))],
        out_shape=[bp_shape, jax.ShapeDtypeStruct((S, B), jnp.float32)],
        scratch_shapes=scratch,
        interpret=resolve_interpret(interpret),
    )(*args)
    return final_pm, bps


def table_weights(code: ConvCode):
    """Weights that make the generic kernel consume precomputed bm tables:
    the branch one-hots select bm[c] per transition, bias contributes 0."""
    OH0, OH1 = code.branch_onehot_pair
    rb = jnp.zeros((code.n_states, 2), jnp.float32)
    return jnp.asarray(OH0), jnp.asarray(OH1), rb


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def viterbi_scan(
    code: ConvCode,
    data: jnp.ndarray,
    b0: jnp.ndarray,
    b1: jnp.ndarray,
    rb: jnp.ndarray,
    block_b: int = 128,
    interpret: Optional[bool] = None,
    pm0: Optional[jnp.ndarray] = None,
    window: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Forward scan with bit-packed survivors and generic in-kernel metrics.

    The one entry into the kernel: the block decode (``pm0=None``), the
    streaming chunk scan (``pm0`` carried from the previous chunk) and the
    tiled launch (``pm0`` and ``window``).

    Args:
      data: (T, F, B) per-step inputs — precomputed bm tables (F = M,
        weights from ``table_weights``) or raw received symbols (F = n
        features, weights from kernels/metrics.py folded through the branch
        one-hots).  B must be a multiple of ``block_b``.
      b0, b1: (S, F) float32 per-parity metric weights.
      rb: (S, 2) float32 per-parity metric bias.
      pm0: optional (S, B) float32 path metrics entering the scan (held
        untouched through any leading invalid steps of a window); None
        starts every path in state 0.
      window: optional ``(lo, hi)``, each (1, B) int32 — lane b runs ACS
        only on steps lo[b] <= t < hi[b]; elsewhere the metrics pass through
        and the survivor bit is 0 (kernels/tiling.py folds P time-tiles into
        the lane axis this way).
    Returns:
      final_pm: (S, B) float32.
      packed: (ceil(T/32), S, B) uint32 — bit p of word w is the ACS select
        of trellis step ``t = 32*w + p`` (tail bits of a partial last word
        are zero).
    """
    return _scan_call(code, pm0, data, b0, b1, rb, block_b, interpret, window)
