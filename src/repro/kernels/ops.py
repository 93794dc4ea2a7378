"""Jit'd public wrappers around the Pallas kernels.

Handle layout conversion ((B, S) user layout <-> (S, B) kernel layout),
lane/sublane padding, interpret-mode selection (CPU container -> interpret;
real TPU -> compiled), and compose the full fused decoders.  Every Viterbi
decoder here runs the one packed-survivor forward entry
(kernels/viterbi_scan.viterbi_scan) and the Pallas traceback over its words:

  packed       viterbi_decode_packed: bm tables in, 32-per-word packed
               survivors, Pallas traceback — the survivors never exist
               unpacked in HBM.
  fused+packed viterbi_decode_fused_packed: raw received symbols in, branch
               metrics computed in-kernel (kernels/metrics.py), packed
               survivors, Pallas traceback — the full memory-lean hot path.
  tiled        viterbi_decode_tiled_op / _fused: P time-tiles of one long
               block on the lane axis of one windowed launch.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.trellis import NEG_UNREACHABLE, ConvCode
from repro.kernels import bcjr as _bcjr
from repro.kernels import minplus as _minplus
from repro.kernels import survivors as _surv
from repro.kernels import texpand as _texpand
from repro.kernels import tiling as _tiling
from repro.kernels import viterbi_scan as _vscan
from repro.kernels.common import lane_block, pad_axis_to, resolve_interpret
from repro.kernels.metrics import FusedMetricPlan


def texpand_op(
    code: ConvCode,
    pm: jnp.ndarray,
    bm_table: jnp.ndarray,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused ACS step in user layout.  pm: (B, S); bm_table: (B, M)."""
    B = pm.shape[0]
    pm_k = pm.T  # (S, B)
    bm_k = bm_table.T  # (M, B)
    block_b = lane_block(B)
    pm_k, _ = pad_axis_to(pm_k, 1, block_b, NEG_UNREACHABLE)
    bm_k, _ = pad_axis_to(bm_k, 1, block_b, 0.0)
    new_pm, bp = _texpand.texpand(
        code, pm_k.astype(jnp.float32), bm_k.astype(jnp.float32), block_b, interpret
    )
    return new_pm[:, :B].T, bp[:, :B].T


# --------------------------------------------------------------------------- #
# Packed-survivor pipeline: forward (+ optional in-kernel metrics), traceback. #
# --------------------------------------------------------------------------- #


def viterbi_forward_weighted_op(
    code: ConvCode,
    pm0: Optional[jnp.ndarray],
    data_btf: jnp.ndarray,
    weights: Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray],
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Generic packed forward: any (b0, b1, rb) metric weights, optional
    carried pm0 (None -> state-0 init).  data_btf: (B, T, F) user layout ->
    final_pm (B, S), packed (W, B, S) traceback layout.  The streaming
    subsystem calls this directly with its per-session weights."""
    B, T, F = data_btf.shape
    b0, b1, rb = weights
    data = data_btf.transpose(1, 2, 0).astype(jnp.float32)  # (T, F, B)
    block_b = lane_block(B)
    data, _ = pad_axis_to(data, 2, block_b, 0.0)
    pm_k = None
    if pm0 is not None:
        pm_k, _ = pad_axis_to(pm0.T, 1, block_b, NEG_UNREACHABLE)
        pm_k = pm_k.astype(jnp.float32)
    final_pm, packed = _vscan.viterbi_scan(
        code, data, b0, b1, rb, block_b, interpret, pm0=pm_k
    )
    return final_pm[:, :B].T, packed[:, :, :B].transpose(0, 2, 1)


def viterbi_traceback_op(
    code: ConvCode,
    packed: jnp.ndarray,
    final_state: jnp.ndarray,
    T: int,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """On-device traceback over packed survivors.

    packed: (W, B, S) uint32 (traceback layout); final_state: (B,) int32.
    Returns bits (B, T) — the survivors are never unpacked in HBM.
    """
    W, B, S = packed.shape
    pk = packed.transpose(0, 2, 1)  # (W, S, B)
    block_b = lane_block(B)
    pk, _ = pad_axis_to(pk, 2, block_b, 0)
    fs, _ = pad_axis_to(final_state.reshape(1, B).astype(jnp.int32), 1, block_b, 0)
    bits = _surv.traceback_packed(code, pk, fs, T, block_b, interpret)
    return bits[:T, :B].T


def _frontier(
    final_pm: jnp.ndarray, terminated: bool
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Traceback start state + winning metric from (B, S) frontier metrics."""
    if terminated:
        final_state = jnp.zeros(final_pm.shape[:1], dtype=jnp.int32)
        metric = final_pm[:, 0]
    else:
        final_state = jnp.argmin(final_pm, axis=-1).astype(jnp.int32)
        metric = final_pm.min(axis=-1)
    return final_state, metric


def viterbi_decode_packed(
    code: ConvCode,
    bm_tables: jnp.ndarray,
    terminated: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Drop-in Pallas replacement for core.viterbi.viterbi_decode: packed
    survivors + on-device traceback (bm tables in).

    bm_tables: (B, T, M) -> (bits (B, T), metric (B,)).
    """
    T = bm_tables.shape[1]
    # resolve interpret ONCE so the forward scan and the traceback kernel of
    # this decode can never auto-detect onto different code paths
    interpret = resolve_interpret(interpret)
    final_pm, packed = viterbi_forward_weighted_op(
        code, None, bm_tables, _vscan.table_weights(code), interpret
    )
    final_state, metric = _frontier(final_pm, terminated)
    bits = viterbi_traceback_op(code, packed, final_state, T, interpret)
    return bits, metric


def viterbi_decode_fused_packed(
    plan: FusedMetricPlan,
    received: jnp.ndarray,
    terminated: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The full memory-lean hot path: raw received symbols in, branch
    metrics computed in-kernel, bit-packed survivors, Pallas traceback.

    received: (B, T, n_out) -> (bits (B, T), metric (B,)).
    """
    T = received.shape[1]
    interpret = resolve_interpret(interpret)  # pinned per decode
    final_pm, packed = viterbi_forward_weighted_op(
        plan.code, None, plan.features(received, 0), plan.folded(), interpret
    )
    final_state, metric = _frontier(final_pm, terminated)
    bits = viterbi_traceback_op(plan.code, packed, final_state, T, interpret)
    return bits, metric


# --------------------------------------------------------------------------- #
# Time-parallel tiled decode: P tiles of one long block ride the lane axis.   #
# --------------------------------------------------------------------------- #


def _tile_lane_row(per_tile: np.ndarray, B: int, S: int = 1) -> jnp.ndarray:
    """Per-tile (P,) int vector -> per-lane (1, B*P*S) row in the canonical
    lane order (b outer, p middle, s inner)."""
    # host-side plan construction on a plain numpy vector, not a device sync
    v = np.tile(np.asarray(per_tile, np.int32), B)  # repr-lint: allow[RPR003]
    if S > 1:
        v = np.repeat(v, S)
    return jnp.asarray(v.reshape(1, -1))


@functools.partial(
    jax.jit, static_argnames=("code", "n_tiles", "overlap", "terminated", "interpret")
)
def _tiled_weighted_decode(
    code: ConvCode,
    data_btf: jnp.ndarray,
    weights: Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray],
    n_tiles: int,
    overlap: Optional[int],
    terminated: bool,
    interpret: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Shared tiled-decode core (see viterbi_decode_tiled_op for the
    contract).  data_btf: (B, T, F) user layout + (b0, b1, rb) weights.

    One jitted program, so the compiler plans its buffers: each pass
    widens the lanes S-fold (B*P*S lanes of spans and of packed survivors),
    and run op by op the intermediates of every pass stay alive together
    (B=128, T=65542, P=16, S=64 then overflows a 16 GiB chip)."""
    B, T, F = data_btf.shape
    S = code.n_states
    # any overlap covering the truncation depth is promoted to the exact
    # two-pass seam resolution: strictly better and guaranteed bit-exact
    exact = overlap is None or int(overlap) >= _tiling.truncation_depth(code)
    tp = _tiling.plan_tiles(T, n_tiles, 0 if exact else int(overlap))
    P, V = tp.n_tiles, tp.span
    if P == 1:
        # degenerate tiling: the plain packed pipeline IS the exact decode
        final_pm, packed = viterbi_forward_weighted_op(
            code, None, data_btf, weights, interpret
        )
        final_state, metric = _frontier(final_pm, terminated)
        bits = viterbi_traceback_op(code, packed, final_state, T, interpret)
        return bits, metric

    b0, b1, rb = weights
    lo_np, hi_np = tp.windows()
    data = data_btf.transpose(1, 2, 0).astype(jnp.float32)  # (T, F, B)
    # (V, F, B*P): every tile's span gathered onto the lane axis
    tiles = data[jnp.asarray(tp.gather_index())].transpose(1, 2, 3, 0)
    tiles = tiles.reshape(V, F, B * P)
    eye = jnp.where(jnp.arange(S)[:, None] == jnp.arange(S)[None, :],
                    0.0, NEG_UNREACHABLE)

    if exact:
        # pass 1 — per-tile (S, S) transfer maps: the S unit-entry-state
        # problems of every tile also ride the lane axis (lanes (b, p, j)),
        # so the map build costs one span-deep launch, not S of them
        lanes1 = B * P * S
        blk1 = lane_block(lanes1)
        t1, _ = pad_axis_to(jnp.repeat(tiles, S, axis=2), 2, blk1, 0.0)
        p1, _ = pad_axis_to(jnp.tile(eye, (1, B * P)), 1, blk1, NEG_UNREACHABLE)
        l1, _ = pad_axis_to(_tile_lane_row(lo_np, B, S), 1, blk1, 0)
        h1, _ = pad_axis_to(_tile_lane_row(hi_np, B, S), 1, blk1, 0)
        fpm1, _ = _vscan.viterbi_scan(
            code, t1, b0, b1, rb, blk1, interpret, pm0=p1, window=(l1, h1)
        )
        # map[b, p, i, j] = best metric entering tile p in state i, leaving j
        maps = fpm1[:, :lanes1].reshape(S, B, P, S).transpose(2, 1, 3, 0)
        excl, total = _minplus.prefix_maps(maps)
        entry = _minplus.tile_entry_metrics(excl)  # (P, B, S): exact seam pms
        final_pm = total[:, 0, :]  # (B, S) full-sequence metrics from state 0
        final_state, metric = _frontier(final_pm, terminated)
        pm0 = entry.transpose(2, 1, 0).reshape(S, B * P)  # lanes (b, p)
    else:
        # truncated warm-up: tile 0 enters in state 0, later tiles enter
        # "cold" (uniform 0) and converge over the overlap steps
        is_first = jnp.asarray((np.arange(B * P) % P) == 0)[None, :]
        pm0 = jnp.where(is_first, eye[:, :1], 0.0)  # (S, B*P)

    # forward over all tiles at once — survivors for V steps per tile
    lanes2 = B * P
    blk2 = lane_block(lanes2)
    t2, _ = pad_axis_to(tiles, 2, blk2, 0.0)
    p2, _ = pad_axis_to(pm0, 1, blk2, NEG_UNREACHABLE)
    l2, _ = pad_axis_to(_tile_lane_row(lo_np, B), 1, blk2, 0)
    h2, _ = pad_axis_to(_tile_lane_row(hi_np, B), 1, blk2, 0)
    fpm2, packed2 = _vscan.viterbi_scan(
        code, t2, b0, b1, rb, blk2, interpret, pm0=p2, window=(l2, h2)
    )
    packed2 = packed2[:, :, :lanes2]  # (ceil(V/32), S, B*P)
    if not exact:
        # approximate frontier: the last tile's span covers the block end;
        # its metric is relative (warm-up re-zeroed the earlier history)
        last_pm = fpm2[:, :lanes2].reshape(S, B, P)[:, :, -1].T  # (B, S)
        final_state, metric = _frontier(last_pm, terminated)

    # traceback — every tile from EVERY candidate exit state in one launch
    # (lanes (b, p, s)); each lane also reports the state it entered on, so
    # seam states resolve by chaining exit -> entry from the final frontier:
    # exactly the walk the sequential traceback would have done, tie-breaks
    # included
    lanesT = B * P * S
    blkT = lane_block(lanesT)
    pkT, _ = pad_axis_to(jnp.repeat(packed2, S, axis=2), 2, blkT, 0)
    stT, _ = pad_axis_to(_tile_lane_row(np.arange(S), B * P), 1, blkT, 0)
    ov = tp.overlap
    ltT, _ = pad_axis_to(jnp.full((1, lanesT), ov, jnp.int32), 1, blkT, 0)
    htT, _ = pad_axis_to(_tile_lane_row(hi_np, B, S), 1, blkT, 0)
    bits_all, ent = _surv.traceback_packed_window(
        code, pkT, stT, ltT, htT, blkT, interpret
    )
    bits_r = bits_all[:V, :lanesT].reshape(V, B, P, S)
    ent = ent[0, :lanesT].reshape(B, P, S)

    # stitch: walk the seam chain backwards, keep each tile's core bits
    state = final_state  # (B,) exit state of the last tile
    pieces = []
    for p in range(P - 1, -1, -1):
        sel = bits_r[:, :, p, :]  # (V, B, S) bits per candidate exit state
        piece = jnp.take_along_axis(sel, state[None, :, None], axis=2)[..., 0]
        pieces.append(piece[ov:int(hi_np[p])].T)  # (B, tile_length(p))
        state = jnp.take_along_axis(ent[:, p, :], state[:, None], axis=1)[:, 0]
    bits = jnp.concatenate(pieces[::-1], axis=1)  # (B, T)
    return bits, metric


def viterbi_decode_tiled_op(
    code: ConvCode,
    bm_tables: jnp.ndarray,
    n_tiles: int,
    overlap: Optional[int] = None,
    terminated: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Time-parallel tiled decode: split T into ``n_tiles`` tiles that all
    run through the packed Pallas scan in one launch, resolve the tile seams,
    and trace every tile back in parallel — O(T/P + seam work) wall-clock.

    ``overlap`` picks the seam regime (kernels/tiling.py): ``None`` or any
    value >= the truncation depth 5·K -> **exact** two-pass mode — per-tile
    (S, S) transfer maps composed with the min-plus algebra of
    kernels/minplus.py seed each tile's re-scan with the *exact* full-length
    forward metrics, so survivors, bits, and metric are bit-exact vs
    viterbi_decode_packed for integer-valued (hard) branch metrics (soft
    metrics agree to float32 rounding, exactly the kernels/metrics.py
    contract).  ``0 <= overlap < 5·K`` -> single-pass truncated warm-up:
    each tile re-converges from a cold metric vector over ``overlap`` extra
    steps — approximate, with BER drift bounded by the usual truncated
    -traceback argument (tests/test_tiled.py pins a seeded bound).

    bm_tables: (B, T, M) -> (bits (B, T), metric (B,)).
    """
    return _tiled_weighted_decode(
        code, bm_tables, _vscan.table_weights(code), n_tiles, overlap,
        terminated, resolve_interpret(interpret),  # pinned across all launches
    )


def viterbi_decode_tiled_fused(
    plan: FusedMetricPlan,
    received: jnp.ndarray,
    n_tiles: int,
    overlap: Optional[int] = None,
    terminated: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`viterbi_decode_tiled_op` fed raw received symbols — branch
    metrics are computed in-kernel per tile (kernels/metrics.py), so the
    (B, T, M) table never exists.  received: (B, T, n_out)."""
    feats = plan.features(received, 0)
    return _tiled_weighted_decode(
        plan.code, feats, plan.folded(), n_tiles, overlap, terminated,
        resolve_interpret(interpret),  # pinned across all launches
    )


def bcjr_llr_op(
    code,
    llr_coded: jnp.ndarray,
    llr_apriori: Optional[jnp.ndarray] = None,
    terminated: bool = False,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Max-log-MAP SISO decode of one RSC code block (kernels/bcjr.py).

    The SISO analogue of viterbi_decode_packed: forward (alpha) scan with
    VMEM-resident metrics, then a time-reversed backward scan that fuses the
    beta recursion with per-step LLR extraction.

    Args:
      code: an RSCCode (duck-typed — kernels/ never imports siso/).
      llr_coded: (B, T, n_out) per-coded-bit channel LLRs, convention
        ``lambda = log P(0)/P(1)`` (punctured positions = 0).
      llr_apriori: (B, T) a-priori LLRs on the info bits (None -> zeros).
      terminated: trellis flushed to state 0 (beta seeded there) vs open.
    Returns:
      llr: (B, T) float32 a-posteriori LLRs (negative -> decide bit 1).
      metric: (B,) float32 best-path terminal cost (renormalized per step,
        so meaningful relative to other streams of the same T, not absolute).
    """
    B, T, n = llr_coded.shape
    if llr_apriori is None:
        llr_apriori = jnp.zeros((B, T), jnp.float32)
    feat = jnp.concatenate(
        [llr_coded.astype(jnp.float32), llr_apriori[..., None].astype(jnp.float32)],
        axis=-1,
    )
    feat = feat.transpose(1, 2, 0)  # (T, F, B)
    block_b = lane_block(B)
    feat, _ = pad_axis_to(feat, 2, block_b, 0.0)
    interpret = resolve_interpret(interpret)  # pinned once for both kernels
    P0, P1 = code.select_matrices
    b0, b1 = code.alpha_weights
    alphas, final_pm = _bcjr.bcjr_alpha_scan(
        tuple(jnp.asarray(m) for m in (P0, P1, b0, b1)), feat, block_b, interpret
    )
    N0, N1 = code.beta_matrices
    U0, U1 = code.llr_matrices
    c0, c1 = code.beta_weights
    w0, w1 = code.llr_weights
    llr = _bcjr.bcjr_beta_llr_scan(
        tuple(jnp.asarray(m) for m in (N0, N1, U0, U1, c0, c1, w0, w1)),
        alphas, feat, terminated, block_b, interpret,
    )
    metric = final_pm[0, :B] if terminated else final_pm[:, :B].min(axis=0)
    return llr[:, 0, :B].T, metric


def minplus_matmul_op(
    a: jnp.ndarray, b: jnp.ndarray, interpret: Optional[bool] = None
) -> jnp.ndarray:
    """Batched (min,+) matmul with padding.  a: (..., I, K), b: (..., K, J)."""
    batch_shape = a.shape[:-2]
    I, K = a.shape[-2:]
    J = b.shape[-1]
    a2 = a.reshape((-1, I, K))
    b2 = b.reshape((-1, K, J))
    bi = min(128, max(8, I))
    bj = lane_block(J)
    bk = min(128, max(8, K))
    a2, _ = pad_axis_to(a2, 1, bi, NEG_UNREACHABLE)
    a2, _ = pad_axis_to(a2, 2, bk, NEG_UNREACHABLE)
    b2, _ = pad_axis_to(b2, 1, bk, NEG_UNREACHABLE)
    b2, _ = pad_axis_to(b2, 2, bj, NEG_UNREACHABLE)
    out = _minplus.minplus_matmul(
        a2.astype(jnp.float32), b2.astype(jnp.float32), bi, bj, bk, interpret
    )
    out = jnp.minimum(out, NEG_UNREACHABLE)  # padded lanes produced 2*BIG
    return out[:, :I, :J].reshape(batch_shape + (I, J))
