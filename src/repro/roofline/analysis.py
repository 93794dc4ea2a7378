"""Roofline analysis from compiled dry-run artifacts.

Three terms, each a lower-bound execution time in seconds on the chip
named by its ``device_kind`` (peaks from :data:`PEAKS`):

  compute    = HLO_FLOPs_total        / (chips * peak_flops)   [bf16 MXU]
  memory     = HLO_bytes_total        / (chips * hbm_bw)       [HBM]
  collective = collective_bytes_total / (chips * ici_bw)       [per-link ICI]

``cost_analysis()`` reports per-device numbers for the SPMD module; totals
are per-device * chips, so the division by chips cancels — we compute the
terms directly from the per-device numbers and say so in EXPERIMENTS.md.

collective_bytes is NOT in cost_analysis: we parse the post-SPMD HLO and sum
operand sizes of every all-gather / all-reduce / reduce-scatter / all-to-all
/ collective-permute.  Bytes counted are the per-device shard bytes moved by
the op (operand size for AG/AR/A2A/CP; ×(1-1/n)≈1 ring-transfer convention),
a standard lower-bound convention for ring algorithms.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict



@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str
    peak_flops: float  # bf16 per chip
    hbm_bw: float  # bytes/s per chip
    ici_bw: float  # bytes/s per link
    hbm_bytes: float  # per chip


#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.  TPU v5e:
#: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at
#: 819 GB/s, 1,600 Gbit/s chip-to-chip over four 50 GB/s links.
PEAKS: Dict[str, Hardware] = {
    "TPU v5 lite": Hardware(
        name="tpu_v5e", peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9,
        hbm_bytes=16 * 2 ** 30,
    ),
}


def hardware(device_kind: str) -> Hardware:
    """Peaks of the chip JAX reports as ``device_kind``; a chip without
    published peaks here is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})"
        ) from None

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVE_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)
# e.g.:  %x = f32[8,128]{1,0} all-gather(f32[1,128]{1,0} %y), ...
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)


def _shape_bytes(shape_str: str) -> int:
    """Bytes of 'f32[8,128]' or a tuple '(f32[..], bf16[..])'."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum result-shape bytes of every collective op in the HLO, by kind.

    '-start' ops are counted; their '-done' twins are skipped (the shape
    appears on both).  Result-shape is the right operand-size convention for
    all-gather (full gathered bytes land per device) and all-to-all; for
    all-reduce and reduce-scatter it equals/bounds the per-device shard
    moved per ring pass.
    """
    out = {k: 0.0 for k in _COLLECTIVE_KINDS}
    counts = {k: 0 for k in _COLLECTIVE_KINDS}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        if f"{m.group(2)}-done(" in line:
            continue
        shape_str, kind = m.group(1), m.group(2)
        out[kind] += _shape_bytes(shape_str)
        counts[kind] += 1
    out_total = sum(out.values())
    return {"total": out_total, "per_kind": out, "counts": counts}


def roofline_terms(
    flops_per_device: float,
    bytes_per_device: float,
    collective_bytes_per_device: float,
    hw: Hardware,
) -> Dict[str, float]:
    """All inputs are per-device (the SPMD module's numbers)."""
    compute = flops_per_device / hw.peak_flops
    memory = bytes_per_device / hw.hbm_bw
    collective = collective_bytes_per_device / hw.ici_bw
    terms = {"compute_s": compute, "memory_s": memory, "collective_s": collective}
    dom = max(terms, key=terms.get)
    bound = max(compute, memory, collective)
    terms["dominant"] = dom
    terms["bound_s"] = bound
    # fraction of the bound that is useful MXU work — the roofline fraction
    terms["compute_fraction_of_bound"] = compute / bound if bound > 0 else 0.0
    return terms


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6·N·D for train (fwd+bwd), 2·N·D per decoded/prefilled
    token — with N = active params for MoE."""
    counts = cfg.param_count()
    n_active = counts["active"]
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        if cfg.family == "encdec":
            tokens = shape.global_batch * (shape.seq_len // cfg.dec_ratio)
            # encoder tokens ride at 2·N_enc — folded into active count approx
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        if cfg.family == "encdec":
            tokens = shape.global_batch * (shape.seq_len + shape.seq_len // cfg.dec_ratio)
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def roofline_report(cell: dict, hw: Hardware) -> dict:
    """Assemble the EXPERIMENTS.md row from one dry-run cell record.

    Prefers the trip-count-aware jaxpr costs (global / chips) over raw XLA
    cost_analysis (which counts loop bodies once); collective bytes come
    from the while-trip-corrected HLO parse, divided per device is already
    implicit (post-SPMD HLO is the per-device program)."""
    chips = cell.get("chips", 1)
    jx = cell.get("jaxpr_cost")
    if jx:
        flops = jx["flops_per_device"]
        byts = jx["bytes_per_device"]
    else:
        flops = cell["cost_analysis"].get("flops", 0.0)
        byts = cell["cost_analysis"].get("bytes accessed", 0.0)
    coll = cell["collectives"]["total"]
    terms = roofline_terms(flops, byts, coll, hw)
    mf = cell.get("model_flops", 0.0)
    terms["model_flops"] = mf
    terms["useful_ratio"] = (mf / chips) / flops if flops else 0.0
    terms["mfu_bound"] = (mf / chips / hw.peak_flops) / terms["bound_s"] \
        if terms["bound_s"] else 0.0
    return terms
