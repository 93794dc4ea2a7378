"""The decode backends, re-homed onto the DecoderRegistry.

Each backend is a thin adapter from the normalized
``decode(spec, bm_tables, *, ctx) -> DecodeResult`` signature onto the
existing implementation it wraps; the implementations themselves stay where
they live (core/, kernels/, parallel/, stream/).  Importing this module
(which ``repro.decode`` does) populates the registry.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.viterbi import viterbi_decode, viterbi_decode_parallel
from repro.decode.registry import BackendCapabilities, register_decoder
from repro.decode.request import DecodeContext, DecodeResult
from repro.decode.spec import CodecSpec

#: Largest trellis the VMEM-resident fused scan keeps on-chip comfortably:
#: path metrics + the (S, S) select matmuls stay within one VMEM working set
#: up to K=13 (4096 states); beyond that the planner falls back to the
#: lax.scan decoders, which spill to HBM gracefully.
FUSED_MAX_STATES = 4096


def _result(spec: CodecSpec, bits: jnp.ndarray, metric: jnp.ndarray, **diag) -> DecodeResult:
    return DecodeResult(bits=bits, path_metric=metric, spec=spec, diagnostics=diag)


def _fused_packed_from_received(
    spec: CodecSpec, received, *, ctx: DecodeContext
) -> DecodeResult:
    """Raw-symbol entry: branch metrics computed in-kernel — the (B, T, M)
    bm table never exists, in HBM or on the host."""
    from repro.kernels.metrics import fused_metric_plan
    from repro.kernels.ops import viterbi_decode_fused_packed

    plan = fused_metric_plan(spec.code, spec.metric, spec.puncture_array)
    bits, metric = viterbi_decode_fused_packed(
        plan, received, terminated=spec.terminated, interpret=ctx.interpret
    )
    return _result(spec, bits, metric, backend="fused_packed", metrics="in-kernel")


@register_decoder(
    "fused_packed",
    capabilities=BackendCapabilities(
        family="conv", max_states=FUSED_MAX_STATES, accepts_received=True
    ),
    from_received=_fused_packed_from_received,
)
def decode_fused_packed(spec: CodecSpec, bm_tables, *, ctx: DecodeContext) -> DecodeResult:
    """Pallas pipeline — the default block decoder: VMEM-resident scan
    (the paper's custom instruction) with bit-packed survivors + on-device
    packed traceback; given raw symbols it also computes branch metrics
    in-kernel."""
    from repro.kernels.ops import viterbi_decode_packed

    bits, metric = viterbi_decode_packed(
        spec.code, bm_tables, terminated=spec.terminated, interpret=ctx.interpret
    )
    return _result(spec, bits, metric, backend="fused_packed", metrics="table")


def _tile_count(ctx: DecodeContext, B: int, T: int, S: int) -> int:
    """ctx.tiles when the caller (or the planner) pinned one, else the
    shape-derived default."""
    if ctx.tiles is not None:
        return max(1, int(ctx.tiles))
    from repro.kernels.tiling import default_tiles

    return default_tiles(B, T, S)


def _tiled_from_received(
    spec: CodecSpec, received, *, ctx: DecodeContext
) -> DecodeResult:
    """Raw-symbol entry: each tile computes its branch metrics in-kernel."""
    from repro.kernels.metrics import fused_metric_plan
    from repro.kernels.ops import viterbi_decode_tiled_fused

    B, T = received.shape[:2]
    n = _tile_count(ctx, B, T, spec.code.n_states)
    plan = fused_metric_plan(spec.code, spec.metric, spec.puncture_array)
    bits, metric = viterbi_decode_tiled_fused(
        plan, received, n_tiles=n, overlap=ctx.tile_overlap,
        terminated=spec.terminated, interpret=ctx.interpret,
    )
    return _result(
        spec, bits, metric, backend="tiled", tiles=n,
        overlap=ctx.tile_overlap, metrics="in-kernel",
    )


@register_decoder(
    "tiled",
    capabilities=BackendCapabilities(
        family="conv", max_states=FUSED_MAX_STATES, accepts_received=True
    ),
    from_received=_tiled_from_received,
)
def decode_tiled(spec: CodecSpec, bm_tables, *, ctx: DecodeContext) -> DecodeResult:
    """Time-parallel tiled decode: T splits into ctx.tiles tiles that run
    through the packed Pallas scan as ONE batched launch (tiles on the lane
    axis), seams resolved via the min-plus state-map composition — O(T/P)
    critical path, bit-exact in the default exact-overlap regime."""
    from repro.kernels.ops import viterbi_decode_tiled_op

    B, T = bm_tables.shape[:2]
    n = _tile_count(ctx, B, T, spec.code.n_states)
    bits, metric = viterbi_decode_tiled_op(
        spec.code, bm_tables, n_tiles=n, overlap=ctx.tile_overlap,
        terminated=spec.terminated, interpret=ctx.interpret,
    )
    return _result(
        spec, bits, metric, backend="tiled", tiles=n,
        overlap=ctx.tile_overlap, metrics="table",
    )


@register_decoder("sequential", capabilities=BackendCapabilities(family="conv"))
def decode_sequential(spec: CodecSpec, bm_tables, *, ctx: DecodeContext) -> DecodeResult:
    """lax.scan reference decoder — the oracle every other backend is tested
    against."""
    bits, metric = viterbi_decode(spec.code, bm_tables, terminated=spec.terminated)
    return _result(spec, bits, metric, backend="sequential")


@register_decoder("parallel", capabilities=BackendCapabilities(family="conv"))
def decode_parallel(spec: CodecSpec, bm_tables, *, ctx: DecodeContext) -> DecodeResult:
    """(min,+) associative scan over chunk transfer matrices — log-depth in
    the number of chunks, the single-device long-block decoder."""
    bits, metric = viterbi_decode_parallel(
        spec.code, bm_tables, chunk=ctx.chunk, terminated=spec.terminated
    )
    return _result(spec, bits, metric, backend="parallel", chunk=ctx.chunk)


@register_decoder(
    "seqparallel",
    capabilities=BackendCapabilities(
        family="conv", supports_mesh=True, requires_mesh=True
    ),
)
def decode_seqparallel(spec: CodecSpec, bm_tables, *, ctx: DecodeContext) -> DecodeResult:
    """shard_map sequence-parallel decoder: the time axis is split across the
    mesh, chunk transfer matrices are all-gathered (n·S² floats, independent
    of T)."""
    from repro.parallel.collectives import viterbi_decode_seqparallel

    if ctx.mesh is None:
        raise ValueError("seqparallel backend needs ctx.mesh")
    bits, metric = viterbi_decode_seqparallel(
        spec.code, bm_tables, ctx.mesh, axis=ctx.mesh_axis, terminated=spec.terminated
    )
    return _result(
        spec, bits, metric, backend="seqparallel",
        mesh_axis=ctx.mesh_axis, mesh_size=int(ctx.mesh.shape[ctx.mesh_axis]),
    )


@register_decoder(
    "sharded_stream",
    capabilities=BackendCapabilities(
        family="conv",
        supports_mesh=True,
        requires_mesh=True,
        supports_streaming=True,
        sharded_stream=True,
        online=True,
        max_states=FUSED_MAX_STATES,
    ),
)
def decode_sharded_stream(spec: CodecSpec, bm_tables, *, ctx: DecodeContext) -> DecodeResult:
    """Mesh-sharded continuous-batching scheduler: the (B, T, M) block runs
    as B streams through ONE StreamScheduler whose slot table, input arena,
    and survivor ring are partitioned along ``ctx.batch_axis`` — every
    device on that axis decodes its slice of the slots each tick.  Each
    block row enters through ``submit`` — the documented adapter over the
    scheduler's chunk-fed ingestion path (``online=True``: live callers use
    open_stream/submit_chunk against the same machinery)."""
    import numpy as np

    from repro.parallel.collectives import mesh_axis_size
    from repro.stream import StreamScheduler
    from repro.stream.window import default_depth

    if ctx.mesh is None:
        raise ValueError("sharded_stream backend needs ctx.mesh")
    n = mesh_axis_size(ctx.mesh, ctx.batch_axis)
    if not n:
        raise ValueError(f"mesh lacks batch axis {ctx.batch_axis!r}")
    B, T = bm_tables.shape[:2]
    depth = ctx.stream_depth if ctx.stream_depth is not None else default_depth(spec.code)
    n_slots = -(-B // n) * n  # slot table must divide over the shards
    sched = StreamScheduler(
        spec, n_slots=n_slots, chunk=ctx.chunk, depth=depth,
        interpret=ctx.interpret, mesh=ctx.mesh, mesh_axis=ctx.batch_axis,
    )
    for i in range(B):
        sched.submit(str(i), bm_tables[i])
    out = sched.run()
    bits = jnp.asarray(np.stack([out[str(i)][0] for i in range(B)]))
    metric = jnp.asarray([out[str(i)][1] for i in range(B)], dtype=jnp.float32)
    return _result(
        spec, bits, metric, backend="sharded_stream", shards=n,
        batch_axis=ctx.batch_axis, n_slots=n_slots, depth=depth,
    )


def _bcjr_from_received(spec: CodecSpec, received, *, ctx: DecodeContext) -> DecodeResult:
    """Raw-symbol entry: channel output -> per-coded-bit LLR columns through
    the spec (puncture-masked), then the SISO kernel."""
    return decode_bcjr(spec, spec.branch_metrics(received), ctx=ctx)


@register_decoder(
    "bcjr",
    capabilities=BackendCapabilities(
        family="rsc", max_states=FUSED_MAX_STATES, accepts_received=True
    ),
    from_received=_bcjr_from_received,
)
def decode_bcjr(spec: CodecSpec, llr_coded, *, ctx: DecodeContext) -> DecodeResult:
    """Max-log-MAP BCJR SISO decoder (Pallas alpha/beta scans) for recursive
    systematic codes — bits are LLR signs, posterior LLRs ride along in the
    diagnostics for iterative (turbo) consumers."""
    from repro.kernels.ops import bcjr_llr_op

    llr, metric = bcjr_llr_op(
        spec.code, llr_coded, terminated=spec.terminated, interpret=ctx.interpret
    )
    bits = (llr < 0).astype(jnp.int32)
    return _result(spec, bits, metric, backend="bcjr", llr=llr)


def _turbo_from_received(spec, received, *, ctx: DecodeContext) -> DecodeResult:
    """Raw-symbol entry: channel output -> depunctured stream LLRs through
    the TurboSpec, then the iterative loop."""
    return decode_turbo(spec, spec.channel_llrs(received), ctx=ctx)


@register_decoder(
    "turbo",
    capabilities=BackendCapabilities(family="turbo", accepts_received=True),
    from_received=_turbo_from_received,
)
def decode_turbo(spec, llrs, *, ctx: DecodeContext) -> DecodeResult:
    """Iterative turbo decoder: two BCJR SISO passes per iteration exchanging
    scaled extrinsic LLRs through the spec's interleaver, early-exiting on
    LLR-sign agreement.  ``path_metric`` is the negated mean posterior |LLR|
    (lower = more confident, matching the minimized-metric convention).
    ``ctx.telemetry`` receives the loop's spans and counters."""
    from repro.siso.turbo import turbo_decode

    tel = ctx.telemetry
    result = turbo_decode(
        spec, llrs, interpret=ctx.interpret,
        metrics=None if tel is None else tel.metrics,
        tracer=None if tel is None else tel.tracer,
    )
    metric = -jnp.mean(jnp.abs(result.llr), axis=-1)
    return _result(
        spec, result.bits, metric, backend="turbo",
        iterations=result.iterations_run, converged=result.converged,
        agreement=result.agreement, llr=result.llr,
    )


@register_decoder(
    "streaming",
    capabilities=BackendCapabilities(
        family="conv", supports_streaming=True, online=True
    ),
)
def decode_streaming(spec: CodecSpec, bm_tables, *, ctx: DecodeContext) -> DecodeResult:
    """Truncated-traceback sliding window over the chunked Pallas scan —
    O(depth + chunk) memory, the online path behind sessions and the
    continuous-batching scheduler (stream/)."""
    from repro.stream.window import default_depth, viterbi_decode_windowed

    depth = ctx.stream_depth if ctx.stream_depth is not None else default_depth(spec.code)
    bits, metric = viterbi_decode_windowed(
        spec.code,
        bm_tables,
        depth=depth,
        chunk=ctx.chunk,
        terminated=spec.terminated,
        interpret=ctx.interpret,
    )
    return _result(spec, bits, metric, backend="streaming", depth=depth, chunk=ctx.chunk)
