"""Pallas TPU kernels for the paper's compute hot-spot (trellis ACS).

texpand.py      — the paper's custom instruction: one fused ACS step
viterbi_scan.py — full-T / chunked / windowed scan with VMEM-resident path
                  metrics and bit-packed survivors, one entry: table or
                  in-kernel branch metrics
survivors.py    — survivor memory unit: 32-per-uint32 pack/unpack helpers +
                  the Pallas traceback kernel over packed words
metrics.py      — affine in-kernel branch-metric plans (hard/soft/punctured)
minplus.py      — (min,+) matmul for block-parallel / HMM Viterbi + the
                  state-map seam algebra (compose/prefix/entry/argmin)
tiling.py       — time-tiling plans for the tiled (time-parallel) decoder
ops.py          — jit'd public wrappers (layout, padding, interpret switch)
ref.py          — pure-jnp oracles
common.py       — shared interpret auto-detection + padding helpers
"""
from repro.kernels.metrics import FusedMetricPlan, fused_metric_plan
from repro.kernels.minplus import (
    compose_maps,
    identity_map,
    prefix_maps,
    seam_argmin,
    tile_entry_metrics,
)
from repro.kernels.ops import (
    minplus_matmul_op,
    texpand_op,
    viterbi_decode_fused_packed,
    viterbi_decode_packed,
    viterbi_decode_tiled_fused,
    viterbi_decode_tiled_op,
    viterbi_forward_weighted_op,
    viterbi_traceback_op,
)
from repro.kernels.survivors import (
    pack_survivors,
    traceback_packed,
    traceback_packed_window,
    unpack_survivors,
)
from repro.kernels.tiling import TilePlan, default_tiles, plan_tiles, truncation_depth

__all__ = [
    "FusedMetricPlan",
    "TilePlan",
    "compose_maps",
    "default_tiles",
    "fused_metric_plan",
    "identity_map",
    "minplus_matmul_op",
    "pack_survivors",
    "plan_tiles",
    "prefix_maps",
    "seam_argmin",
    "texpand_op",
    "tile_entry_metrics",
    "traceback_packed",
    "traceback_packed_window",
    "truncation_depth",
    "unpack_survivors",
    "viterbi_decode_fused_packed",
    "viterbi_decode_packed",
    "viterbi_decode_tiled_fused",
    "viterbi_decode_tiled_op",
    "viterbi_forward_weighted_op",
    "viterbi_traceback_op",
]
