"""Truncated-traceback sliding-window Viterbi — the streaming core.

Classic Viterbi hardware never materializes the full trellis: after D ≈ 5·K
steps all survivor paths merge with overwhelming probability, so a decoder
that traces back D steps from the current best state and commits everything
older is (a) within noise of the full-block optimum and (b) O(D) memory for
a stream of any length (Martina & Masera 2010, §Viterbi traceback units).

This module is the jittable core shared by sessions and the scheduler:

  StreamState     pytree carried across chunks: path metrics (B, S) and a
                  backpointer ring buffer — (R/32, B, S) uint32 packed
                  survivor words for ``fused_packed``, (R, B, S) int32 for
                  the ``scan`` reference (R = depth + chunk).
  stream_step     advance C trellis steps (the packed-survivor Pallas scan,
                  or a lax.scan reference), shift the ring, traceback from
                  the frontier, and commit the C oldest window positions.
  stream_flush    final traceback over the whole ring at end of stream.
  viterbi_decode_windowed
                  offline (B, T, M) -> (B, T) decode through the streaming
                  machinery — the equivalence oracle used by the tests.

Backends (``STREAM_BACKENDS``): ``fused_packed``, the default and the hot
path — bit-packed survivor ring, word-aligned ring shifts (requires
chunk % 32 == 0 and depth % 32 == 0, sessions round the depth up), Pallas
traceback over the packed words, and optional in-kernel branch metrics when
the caller feeds raw received symbols + folded metric weights; and ``scan``,
the jnp reference (unpacked int32 ring, XLA traceback, any chunk).

Exactness: when depth >= T nothing commits before the flush, the ring holds
the whole history, and the flush traceback from the terminated state IS the
full-block Viterbi traceback — bit-identical to core.viterbi.viterbi_decode.
Away from that regime the committed prefix differs from the full-block
decode only where survivor paths fail to merge within D steps.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.acs import acs_step
from repro.core.trellis import NEG_UNREACHABLE, ConvCode
from repro.core.viterbi import _initial_pm, _traceback
from repro.kernels.common import PACK_BITS

BIG = jnp.float32(NEG_UNREACHABLE)

DEPTH_MULTIPLIER = 5  # the textbook truncation rule: D = 5 * constraint

PACKED_BACKEND = "fused_packed"
#: the stream ``backend`` values: the Pallas hot path and the jnp reference
STREAM_BACKENDS = (PACKED_BACKEND, "scan")


def default_depth(code: ConvCode) -> int:
    return DEPTH_MULTIPLIER * code.constraint


def packed_depth(depth: int) -> int:
    """Round a traceback depth up to the packed ring's word granularity.
    A deeper window only improves accuracy; the session lag grows with it."""
    return -(-depth // PACK_BITS) * PACK_BITS


def resolve_stream_backend(spec, chunk: int, depth: int, backend: str, inputs: str):
    """Shared session/scheduler backend setup: validate the input kind,
    round the depth for the packed ring, and build the in-kernel metric plan.

    Returns (packed, depth, plan, weights): ``plan`` is the FusedMetricPlan
    for the packed backend (None otherwise); ``weights`` its folded kernel
    operands when raw symbols are fed (None -> bm-table weights).
    """
    if backend not in STREAM_BACKENDS:
        raise ValueError(
            f"stream backend must be one of {STREAM_BACKENDS}, got {backend!r}"
        )
    packed = backend == PACKED_BACKEND
    if inputs not in ("bm", "received"):
        raise ValueError(f"inputs must be 'bm' or 'received', got {inputs!r}")
    if inputs == "received" and not packed:
        raise ValueError("inputs='received' needs the fused_packed backend")
    plan = weights = None
    if packed:
        if chunk % PACK_BITS:
            raise ValueError(
                f"{PACKED_BACKEND} streaming needs chunk % {PACK_BITS} == 0"
            )
        depth = packed_depth(depth)
        from repro.kernels.metrics import fused_metric_plan

        plan = fused_metric_plan(spec.code, spec.metric, spec.puncture_array)
        if inputs == "received":
            weights = plan.folded()
    return packed, depth, plan, weights


class DeviceCounters(NamedTuple):
    """Per-slot decode statistics accumulated INSIDE the jitted tick.

    Every field is a (B,) array living on device; the scheduler/session
    carries the pytree across ticks like any other state and materializes it
    host-side only at drain / report time — device telemetry never adds a
    per-tick host sync.  This is the raw signal the adaptive-traceback-depth
    work consumes: ``merge_depth_*`` track the all-states-agree depth of the
    survivor ring (how far back the traceback must really reach), and
    ``renorm_sum`` the accumulated path-metric renormalization magnitude
    (a proxy for channel quality drift).

    ticks:            active ticks this slot advanced through.
    starved_ticks:    ticks the slot sat admitted-but-masked (no full chunk).
    merge_depth_last: survivor merge depth after the latest active tick.
    merge_depth_sum:  sum of per-tick merge depths (mean = sum / ticks).
    merge_depth_max:  worst merge depth observed.
    renorm_sum:       accumulated |path-metric renormalization offset|.
    """

    ticks: jnp.ndarray
    starved_ticks: jnp.ndarray
    merge_depth_last: jnp.ndarray
    merge_depth_sum: jnp.ndarray
    merge_depth_max: jnp.ndarray
    renorm_sum: jnp.ndarray


def init_device_counters(batch: int) -> DeviceCounters:
    z_i = jnp.zeros((batch,), dtype=jnp.int32)
    z_f = jnp.zeros((batch,), dtype=jnp.float32)
    return DeviceCounters(
        ticks=z_i, starved_ticks=z_i, merge_depth_last=z_i,
        merge_depth_sum=z_f, merge_depth_max=z_i, renorm_sum=z_f,
    )


def survivor_merge_depth(code: ConvCode, ring: jnp.ndarray) -> jnp.ndarray:
    """All-states-agree depth of a survivor ring: the smallest d such that
    tracing back d steps from the frontier collapses every state's survivor
    path onto one trellis node (R + 1 when the window never merges).

    Classic truncated-traceback theory commits bits older than the merge
    point losslessly — so this, tracked per stream, is exactly the signal an
    adaptive-depth controller needs (cf. the tile-merge convergence of GPU
    tile-parallel decoders).  ``ring``: (R, B, S) int32 backpointer parities
    or packed (R/32, B, S) uint32 words; returns (B,) int32.

    Cost: an S-walker vectorized traceback over the ring — same O(R) gather
    structure as the per-tick committed-bit traceback, S lanes wide; only
    run when device counters are enabled.
    """
    if ring.dtype == jnp.uint32:
        ring = unpack_ring(code, ring)
    R, B, S = ring.shape
    half = S // 2
    walkers0 = jnp.broadcast_to(
        jnp.arange(S, dtype=jnp.int32)[None, :], (B, S)
    )

    def step(walkers, bp_t):  # walkers: (B, S) current state of each walker
        j = jnp.take_along_axis(bp_t, walkers, axis=1)
        v = walkers & (half - 1) if half > 1 else jnp.zeros_like(walkers)
        prev = 2 * v + j
        merged = (prev == prev[:, :1]).all(axis=1)
        return prev, merged

    # reverse scan: merged[i] == "walkers coalesced after absorbing steps
    # R-1 .. i", i.e. within depth R - i of the frontier.  Coalesced walkers
    # stay coalesced, so merged is monotone in depth; the merge depth is the
    # shallowest True.
    _, merged = jax.lax.scan(step, walkers0, ring.astype(jnp.int32), reverse=True)
    idx = jnp.where(
        merged, jnp.arange(R, dtype=jnp.int32)[:, None], jnp.int32(-1)
    ).max(axis=0)
    return jnp.where(idx >= 0, R - idx, R + 1).astype(jnp.int32)


class StreamState(NamedTuple):
    """Carried decode state — everything a stream needs across chunks.

    pm:   (B, S) float32 path metrics at the stream frontier (renormalized,
          see stream_step).
    ring: backpointer ring over the last R = depth + chunk steps; slot i
          holds the backpointers of absolute step ``t - R + i`` (pre-stream
          slots hold zeros and are never committed by the session
          bookkeeping).  (R, B, S) int32 unpacked, or (R/32, B, S) uint32
          survivor words for the packed backend.
    """

    pm: jnp.ndarray
    ring: jnp.ndarray


def init_stream_state(
    code: ConvCode, batch: int, depth: int, chunk: int, packed: bool = False
) -> StreamState:
    """Fresh state: paths start in state 0 (paper §IV-B), empty ring."""
    R = depth + chunk
    if packed:
        if R % PACK_BITS:
            raise ValueError(
                f"packed ring needs (depth + chunk) % {PACK_BITS} == 0, "
                f"got depth={depth}, chunk={chunk} (see packed_depth())"
            )
        ring = jnp.zeros((R // PACK_BITS, batch, code.n_states), dtype=jnp.uint32)
    else:
        ring = jnp.zeros((R, batch, code.n_states), dtype=jnp.int32)
    return StreamState(pm=_initial_pm(code, (batch,)), ring=ring)


def chunk_forward_scan(
    code: ConvCode, pm: jnp.ndarray, bm_chunk: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """lax.scan reference for the chunked forward pass (the ``scan`` stream
    backend, the oracle for the carried Pallas scan, and the path used for
    odd-length stream tails).
    pm: (B, S); bm_chunk: (B, C, M) -> (new_pm, bps (C, B, S)).
    """

    def step(pm, bm_t):
        new_pm, bp = acs_step(code, pm, bm_t)
        return jnp.minimum(new_pm, BIG), bp

    return jax.lax.scan(step, pm, bm_chunk.swapaxes(0, 1))


def stream_step(
    code: ConvCode,
    state: StreamState,
    chunk_inputs: jnp.ndarray,
    weights=None,
    active: Optional[jnp.ndarray] = None,
    backend: str = PACKED_BACKEND,
    normalize: bool = True,
    interpret: Optional[bool] = None,
    counters: Optional[DeviceCounters] = None,
) -> Tuple[StreamState, jnp.ndarray, jnp.ndarray]:
    """One streaming update: advance C steps, commit the C oldest positions.

    Args:
      chunk_inputs: (B, C, M) branch metrics — or, for the packed backend
        with in-kernel metrics, (B, C, F) raw features matching ``weights``.
      weights: (b0, b1, rb) folded metric weights for ``fused_packed``
        (None -> the bm-table weights; ignored by ``scan``).
      active: optional (B,) bool mask — rows where it is False keep their
        pm/ring/offset EXACTLY as they were (the batched kernel still runs
        over them, but its result is discarded row-wise).  This is how the
        chunk-fed scheduler lets a starved slot idle without corrupting its
        carried state: advancing a real stream with zero branch metrics is
        NOT a no-op (the ACS min mixes predecessor metrics and pushes
        garbage backpointers into the ring), so masked slots must be
        re-selected, not just fed zeros.  None == all rows active.
      backend: 'fused_packed' (packed survivors + in-kernel metrics +
        Pallas traceback; C % 32 == 0) or 'scan' (jnp reference).
      normalize: subtract the per-stream min from the path metrics so an
        unbounded stream never overflows float32; the subtracted offset is
        returned so callers can reconstruct absolute metrics.
      counters: optional DeviceCounters pytree to advance inside the jitted
        step (merge depth, starved ticks, renorm magnitude).  When given the
        return value grows a fourth element — the updated counters — and the
        traced computation gains the S-walker merge-depth scan; rows masked
        inactive keep their last merge depth and count a starved tick.

    Returns:
      new_state: state after the chunk (ring shifted by C).
      committed: (B, C) decoded bits for the C oldest window positions —
        positions [t - R, t - D) where t is the new frontier.  The caller
        masks off any that predate the stream start (session bookkeeping);
        rows masked inactive hold garbage the caller must ignore.
      offset_delta: (B,) the amount subtracted from the path metrics (0 for
        masked rows).
      counters: updated DeviceCounters — only when ``counters`` was passed.
    """
    pm, ring = state
    C = chunk_inputs.shape[1]
    if backend == PACKED_BACKEND:
        from repro.kernels.ops import viterbi_forward_weighted_op, viterbi_traceback_op
        from repro.kernels.viterbi_scan import table_weights

        if C % PACK_BITS:
            raise ValueError(f"{PACKED_BACKEND} needs chunk % {PACK_BITS} == 0, got {C}")
        w = table_weights(code) if weights is None else weights
        new_pm, packed = viterbi_forward_weighted_op(
            code, pm, chunk_inputs, w, interpret
        )
        ring = jnp.concatenate([ring[C // PACK_BITS :], packed], axis=0)
        best = jnp.argmin(new_pm, axis=-1).astype(jnp.int32)
        R = ring.shape[0] * PACK_BITS
        bits = viterbi_traceback_op(code, ring, best, R, interpret)  # (B, R)
    elif backend == "scan":
        new_pm, bps = chunk_forward_scan(code, pm, chunk_inputs)
        ring = jnp.concatenate([ring[C:], bps], axis=0)
        # truncated traceback: from the best frontier state back through the
        # whole window; only the positions >= depth behind the frontier commit.
        best = jnp.argmin(new_pm, axis=-1).astype(jnp.int32)
        bits, _ = _traceback(code, ring, best)  # (B, R)
    else:
        raise KeyError(backend)
    committed = bits[:, :C]

    if normalize:
        delta = new_pm.min(axis=-1)
        new_pm = jnp.minimum(new_pm - delta[:, None], BIG)
    else:
        delta = jnp.zeros(new_pm.shape[:1], dtype=new_pm.dtype)
    if active is not None:
        keep = active.astype(jnp.bool_)
        new_pm = jnp.where(keep[:, None], new_pm, pm)
        ring = jnp.where(keep[None, :, None], ring, state.ring)
        delta = jnp.where(keep, delta, jnp.zeros_like(delta))
    new_state = StreamState(pm=new_pm, ring=ring)
    if counters is None:
        return new_state, committed, delta
    act = (
        active.astype(jnp.bool_)
        if active is not None
        else jnp.ones(new_pm.shape[:1], dtype=jnp.bool_)
    )
    # merge depth on the post-mask ring: inactive rows kept their ring, so
    # the recomputed value equals their previous one — jnp.where keeps the
    # bookkeeping explicit anyway.
    md = survivor_merge_depth(code, ring)
    advanced = act.astype(jnp.int32)
    counters = DeviceCounters(
        ticks=counters.ticks + advanced,
        starved_ticks=counters.starved_ticks + (1 - advanced),
        merge_depth_last=jnp.where(act, md, counters.merge_depth_last),
        merge_depth_sum=counters.merge_depth_sum
        + jnp.where(act, md, 0).astype(jnp.float32),
        merge_depth_max=jnp.maximum(counters.merge_depth_max, md * advanced),
        renorm_sum=counters.renorm_sum + jnp.abs(delta).astype(jnp.float32),
    )
    return new_state, committed, delta, counters


def state_shardings(mesh, axis: str):
    """NamedShardings that partition a StreamState along its batch/slot
    dimension: pm (B, S) on axis 0, ring (R, B, S) on axis 1.  The layout
    every mesh-aware stream component (sessions, the sharded scheduler)
    shares, so carried pytrees move between them without resharding."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    return StreamState(
        pm=NamedSharding(mesh, P(axis, None)),
        ring=NamedSharding(mesh, P(None, axis, None)),
    )


def shard_stream_state(mesh, axis: str, state: StreamState) -> StreamState:
    """Pin a StreamState to the per-shard layout (no-op when already there)."""
    sh = state_shardings(mesh, axis)
    return StreamState(
        pm=jax.device_put(state.pm, sh.pm), ring=jax.device_put(state.ring, sh.ring)
    )


#: (code, mesh, axis, chunk, backend, normalize, interpret, device_metrics)
#: -> tick; see make_sharded_stream_step (only weight-free configs are
#: memoizable).
_SHARDED_STEP_CACHE: dict = {}


def make_sharded_stream_step(
    code: ConvCode,
    mesh,
    axis: str,
    *,
    chunk: int,
    backend: str = PACKED_BACKEND,
    normalize: bool = True,
    interpret: Optional[bool] = None,
    weights=None,
    device_metrics: bool = False,
):
    """Build the mesh-sharded per-tick update for the stream scheduler.

    One shard_map spans the ``axis`` (``data``) mesh axis: each shard holds a
    contiguous block of decode slots, its slice of the input arena, and its
    slice of the survivor ring, and runs the tick — arena gather + forward +
    in-window traceback — entirely shard-locally.  There is NO cross-shard
    communication on the hot path (slots are independent streams); the only
    global coordination is the host-side admit/retire bookkeeping and the
    scalar reductions in parallel.collectives.

    Returns ``tick(arena, idx, active, state) -> (state, committed_bits,
    delta)`` where ``arena`` is the (n_shards, cap, W) stacked per-shard
    arena, ``idx`` the (n_slots, chunk) shard-LOCAL arena rows each slot
    decodes this tick (idle/starved slots point at the zero prefix — row
    indices rather than a base offset, because a chunk-fed stream's rows
    need not be contiguous in the arena), ``active`` the (n_slots,) bool
    mask of slots whose carried state actually advances (see stream_step),
    and the outputs keep the per-shard layout of ``state_shardings``.

    Ticks without custom ``weights`` are memoized on the static config (like
    jitted_stream_step), so every scheduler on the same (code, mesh, ...)
    shares one executable per shape instead of re-tracing per instance.

    With ``device_metrics=True`` the tick carries a DeviceCounters pytree —
    ``tick(arena, idx, active, state, counters)`` returning ``(state, bits,
    delta, counters)`` — with every (B,)-shaped counter leaf sharded P(axis)
    alongside the slots it describes, still shard-local (no collectives).
    """
    from repro.parallel.mesh import shard_map
    from jax.sharding import PartitionSpec as P

    cache_key = None
    if weights is None:
        cache_key = (code, mesh, axis, chunk, backend, normalize, interpret,
                     device_metrics)
        cached = _SHARDED_STEP_CACHE.get(cache_key)
        if cached is not None:
            return cached

    packed = backend == PACKED_BACKEND
    if packed and weights is None:
        from repro.kernels.viterbi_scan import table_weights

        weights = table_weights(code)

    n_counters = len(DeviceCounters._fields) if device_metrics else 0

    def local_tick(arena, idx, active, pm, ring, *rest):
        # arena: (1, cap, W) — this shard's slab; idx: (slots_per_shard, C)
        ctr = DeviceCounters(*rest[:n_counters]) if device_metrics else None
        w = rest[n_counters:]
        block = jnp.take(arena[0], idx, axis=0)  # (slots_per_shard, chunk, W)
        out = stream_step(
            code,
            StreamState(pm=pm, ring=ring),
            block,
            weights=w[0] if w else None,
            active=active,
            backend=backend,
            normalize=normalize,
            interpret=interpret,
            counters=ctr,
        )
        if device_metrics:
            state, bits, delta, ctr = out
            return (state.pm, state.ring, bits, delta) + tuple(ctr)
        state, bits, delta = out
        return state.pm, state.ring, bits, delta

    ctr_specs = tuple(P(axis) for _ in range(n_counters))
    w_specs: tuple = ()
    w_args: tuple = ()
    if packed:
        w_specs = tuple(P(*([None] * jnp.asarray(a).ndim)) for a in weights)
        w_args = (weights,)
    fn = jax.jit(
        shard_map(
            local_tick,
            mesh=mesh,
            in_specs=(P(axis, None, None), P(axis, None), P(axis),
                      P(axis, None), P(None, axis, None))
            + ctr_specs
            + ((w_specs,) if packed else ()),
            out_specs=(P(axis, None), P(None, axis, None), P(axis, None),
                       P(axis)) + ctr_specs,
        )
    )

    if device_metrics:

        def tick(arena, idx, active, state: StreamState, counters: DeviceCounters):
            out = fn(arena, idx, active, state.pm, state.ring,
                     *tuple(counters), *w_args)
            pm, ring, bits, delta = out[:4]
            return (StreamState(pm=pm, ring=ring), bits, delta,
                    DeviceCounters(*out[4:]))

    else:

        def tick(arena, idx, active, state: StreamState):
            pm, ring, bits, delta = fn(
                arena, idx, active, state.pm, state.ring, *w_args
            )
            return StreamState(pm=pm, ring=ring), bits, delta

    if cache_key is not None:
        _SHARDED_STEP_CACHE[cache_key] = tick
    return tick


@functools.lru_cache(maxsize=None)
def jitted_stream_step(
    code: ConvCode,
    backend: str = PACKED_BACKEND,
    normalize: bool = True,
    interpret: Optional[bool] = None,
):
    """Compiled stream_step, cached on the static config so every session and
    scheduler with the same (code, backend, flags) shares one executable per
    (batch, chunk) shape instead of re-tracing per instance.  The returned
    callable takes (state, chunk_inputs[, weights[, active[, counters]]]);
    passing ``counters=DeviceCounters(...)`` (a different pytree structure
    from the default None) traces the device-metrics variant, which returns
    the 4-tuple — the jit cache keeps both specializations apart."""
    return jax.jit(
        functools.partial(
            stream_step, code, backend=backend, normalize=normalize, interpret=interpret
        )
    )


def unpack_ring(code: ConvCode, ring: jnp.ndarray) -> jnp.ndarray:
    """Packed (R/32, B, S) uint32 ring -> unpacked (R, B, S) int32 — the
    off-hot-path escape hatch for odd-length tails and batched flushes."""
    from repro.kernels.survivors import unpack_survivors

    return unpack_survivors(ring, ring.shape[0] * PACK_BITS)


def stream_flush(
    code: ConvCode,
    state: StreamState,
    terminated: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """End-of-stream traceback over the full ring (packed or unpacked).

    Returns:
      bits: (B, R) bits for every ring position (caller slices the still-
        uncommitted tail).
      metric: (B,) winning path metric at the frontier (relative — add the
        session's accumulated normalization offset for the absolute value).
    """
    pm, ring = state
    B = pm.shape[0]
    if terminated:
        final_state = jnp.zeros((B,), dtype=jnp.int32)
        metric = pm[:, 0]
    else:
        final_state = jnp.argmin(pm, axis=-1).astype(jnp.int32)
        metric = pm.min(axis=-1)
    if ring.dtype == jnp.uint32:
        from repro.kernels.ops import viterbi_traceback_op

        bits = viterbi_traceback_op(
            code, ring, final_state, ring.shape[0] * PACK_BITS, interpret
        )
    else:
        bits, _ = _traceback(code, ring, final_state)
    return bits, metric


@functools.lru_cache(maxsize=None)
def jitted_stream_flush(
    code: ConvCode, terminated: bool = True, interpret: Optional[bool] = None
):
    """Compiled stream_flush, cached per (code, terminated).  Callers with a
    varying number of retiring streams (the scheduler's batched slot flush)
    pad the batch dimension to a fixed size so this compiles once per shape
    instead of once per cohort size."""
    return jax.jit(
        functools.partial(stream_flush, code, terminated=terminated, interpret=interpret)
    )


@functools.lru_cache(maxsize=None)
def jitted_chunk_forward(code: ConvCode):
    """Compiled chunk_forward_scan (odd-length stream tails; compiles once
    per tail length, shared across slots and sessions)."""
    return jax.jit(functools.partial(chunk_forward_scan, code))


def viterbi_decode_windowed(
    code: ConvCode,
    bm_tables: jnp.ndarray,
    depth: Optional[int] = None,
    chunk: int = 64,
    terminated: Optional[bool] = None,
    backend: str = PACKED_BACKEND,
    normalize: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Offline sliding-window decode of a full (B, T, M) block.

    Drop-in shape-compatible with core.viterbi.viterbi_decode, but runs the
    O(depth + chunk) streaming path: bit-identical when depth >= T, and
    within truncation noise (vanishing for depth >~ 5K) otherwise.
    ``code`` may be a bare ConvCode or a full decode.CodecSpec;
    ``terminated`` defaults to the spec's flag (True for a bare code).
    """
    from repro.stream.session import StreamSession

    B = bm_tables.shape[0]
    sess = StreamSession(
        code,
        batch=B,
        chunk=chunk,
        depth=depth,
        backend=backend,
        normalize=normalize,
        interpret=interpret,
    )
    return sess.decode_all(bm_tables, terminated=terminated)
