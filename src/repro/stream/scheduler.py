"""Continuous-batching stream scheduler with true online ingestion.

Thousands of independent broadcast streams, one jitted Pallas call: every
live stream is pinned to a slot of a fixed (n_slots, chunk) decode block —
the same compile-once bucket discipline as serve/kv_cache.py — and each
``step()`` tick advances ALL slots through one batched stream_step.  Streams
join when a slot frees (FIFO admission), leave when their input drains (the
tail + final traceback run per-slot, off the hot path), and their slot is
recycled for the next pending stream: classic continuous batching, applied
to trellis decode instead of token decode.

**Ingestion is chunk-fed.**  A caller serving live connections opens a
stream, feeds rows as they arrive, and closes it at EOF:

    sched.open_stream("uplink-7")
    while rx := conn.recv_symbols():
        while True:                     # StreamBusy accepts NOTHING — keep
            try:                        # the same rx and retry once a tick
                sched.submit_chunk("uplink-7", rx)   # rows, any size
                break                   # has drained the bounded queue
            except StreamBusy:
                emit(sched.step())
        emit(sched.step())
    sched.close("uplink-7")             # finalizes the mid-chunk tail

or attaches a ChunkProducer (generator / callable / socket-fed push buffer,
see stream/ingest.py) that the tick loop polls within the stream's credit.
Every stream has a **bounded input queue** (``max_buffered`` unconsumed
rows): ``submit_chunk`` returns the remaining credit and raises StreamBusy
on overrun, so backpressure propagates to the source instead of buffering
without bound.  ``submit(stream_id, full_table)`` survives as a thin
adapter over this one path — open, feed the whole table as a single chunk,
close — so offline and online decode share every line of ingestion code.

A slot whose stream has no full chunk ready **idles without being evicted**:
the batched kernel still runs over it (fixed shapes — that is the whole
point of the bucket discipline) but its carried pm/ring are re-selected
unchanged (``stream_step(active=...)``), because advancing a real stream
with zero branch metrics is not a no-op.  Streams that close mid-chunk
retire through the same grouped tail-feed + batched flush as before.

Per-stream input rows are **device-resident**: each accepted chunk is
given rows of one device arena and staged on the host; the next read of the
arena (in the tick, before its gather) writes everything staged with ONE
jitted, donated write, and every tick gathers the (n_slots, chunk, ·)
decode block by per-slot row indices in a single jitted take.  Chunks of
different streams interleave in arrival order, so a stream's rows are
tracked as explicit arena row indices (not a contiguous base offset); the
tick compacts the arena when retired/consumed rows dominate.

**Sharding.**  Given ``mesh=``, ONE scheduler spans every device on the
``data`` mesh axis: the slot table is partitioned into contiguous
slots-per-shard blocks (slot → shard ``slot // slots_per_shard``), and the
input arena, path metrics, and survivor ring are laid out per shard
(arena ``(n_shards, cap, ·)``, pm ``P(data, None)``, ring
``P(None, data, None)``).  The per-tick gather + forward + traceback runs
under one shard_map with NO cross-shard communication — slots are
independent streams — while admission, ingestion, and flush bookkeeping stay
host-side over global slot ids (a stream's chunks land in the slab of the
shard hosting its slot); the few mesh-global scalars (utilization, pending
work, queue depths) reduce through parallel.collectives.sum_across_shards.
Decode results are bit-exact with the single-device scheduler AND with the
offline block decode of the same symbols: arrival schedule and placement
never change what a slot's kernel sees.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.trellis import ConvCode
from repro.core.viterbi import _initial_pm
from repro.decode.spec import CodecSpec
from repro.kernels.common import resolve_interpret
from repro.obs import Telemetry
from repro.obs.metrics import DEPTH_BUCKETS, LATENCY_BUCKETS_S, TICK_BUCKETS
from repro.obs.trace import span
from repro.serve.kv_cache import SlotAllocator
from repro.stream import window as _w
from repro.stream.ingest import ChunkProducer, StreamBusy, as_producer
from repro.stream.resilience import StreamError, TickFault
from repro.train.fault_tolerance import StragglerDetector

#: Tick-phase span names, in order, as they nest under the "tick" parent —
#: the children list Tracer.coverage() checks the tick against.
TICK_PHASES = ("ingest", "admit", "gather", "step", "commit")


@dataclasses.dataclass(eq=False)
class _Stream:
    """Per-stream bookkeeping (host side; the rows themselves live in the
    device arena once accepted).  ``eq=False``: streams are identities, and
    the generated __eq__ would compare ndarray fields."""

    stream_id: str
    terminated: bool
    max_buffered: int  # backpressure bound on unconsumed rows
    producer: Optional[ChunkProducer] = None
    closed: bool = False  # no more input will arrive (close() / EOF)
    slot: Optional[int] = None  # decode slot while admitted
    shard: int = 0  # mesh shard hosting the stream's slot (0 unsharded)
    priority: int = 0  # overload shedding victimizes the lowest first
    deadline_tick: Optional[int] = None  # evict_expired() retires past this
    seq: int = 0  # admission sequence (shed tie-break: newest loses)
    fed: int = 0  # rows accepted into the device arena
    pos: int = 0  # steps consumed by the kernel
    committed: int = 0  # bits already emitted
    #: shard-local arena rows holding steps [pos, fed) — explicit indices,
    #: because chunks of concurrent streams interleave in the arena.
    rows: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), dtype=np.int32)
    )
    queued: List[np.ndarray] = dataclasses.field(default_factory=list)
    queued_rows: int = 0  # raw rows awaiting admission (no shard known yet)
    out: List[np.ndarray] = dataclasses.field(default_factory=list)
    #: (cumulative_rows_after_chunk, arrival_monotonic_ts) per accepted
    #: chunk, popped as commits pass the chunk's last row — the bounded
    #: bookkeeping behind the arrival-to-commit latency histogram.
    arrivals: Deque[Tuple[int, float]] = dataclasses.field(default_factory=deque)

    @property
    def available(self) -> int:
        """Rows in the arena the kernel has not consumed yet."""
        return self.fed - self.pos

    @property
    def buffered(self) -> int:
        """Unconsumed rows anywhere (arena + pre-admission queue) — what the
        per-stream credit is charged against."""
        return self.fed - self.pos + self.queued_rows


@dataclasses.dataclass
class SchedulerStats:
    ticks: int = 0
    streams_submitted: int = 0
    streams_finished: int = 0
    slot_claims: int = 0
    steps_decoded: int = 0  # trellis steps actually consumed by streams
    arena_compactions: int = 0
    arena_appends: int = 0  # chunks (or admitted backlogs) staged for the arena
    arena_writes: int = 0  # device writes of the staged rows into the arena
    chunks_submitted: int = 0  # submit_chunk / producer deliveries accepted
    busy_rejections: int = 0  # StreamBusy raised by submit_chunk
    starved_slot_ticks: int = 0  # slot-ticks spent admitted-but-starved
    poisoned_rejections: int = 0  # chunks rejected for non-finite values
    streams_quarantined: int = 0  # streams failed by poison / producer crash
    streams_expired: int = 0  # streams retired by evict_expired (TTL)
    streams_shed: int = 0  # streams dropped by the overload policy
    tick_device_failures: int = 0  # step-phase TickFaults absorbed (retried)
    straggler_ticks: int = 0  # tick wall times flagged by StragglerDetector

    def asdict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


class StreamScheduler:
    """Continuous batching of independent Viterbi streams.

    Args:
      spec: CodecSpec shared by all streams (a bare ConvCode is promoted);
        its ``terminated`` flag is the per-stream default.
      n_slots: decode-block batch size (compile-once; streams beyond this
        queue FIFO until a slot frees).
      chunk: trellis steps per tick per slot (a multiple of 32 for the
        packed backend).
      depth: truncated-traceback depth (default 5*K; rounded up to a
        multiple of 32 for the packed backend).
      backend: 'fused_packed' (default: Pallas scan, bit-packed survivor
        ring + Pallas traceback; chunk % 32 == 0) | 'scan' (jnp reference)
        forward pass for the hot loop.
      inputs: 'bm' — chunks are (t, M) branch-metric rows; 'received'
        (fused_packed only) — chunks are raw (t, n_out) channel symbols and
        branch metrics are computed in-kernel.
      max_buffered: default per-stream input-queue bound, in unconsumed rows
        (None -> 8 * chunk).  ``open_stream`` can override per stream.
      mesh: optional device mesh — shard the slot table, input arena, and
        survivor ring along ``mesh_axis`` so one scheduler spans all devices
        on that axis (n_slots must divide evenly; decode results stay
        bit-exact with the unsharded scheduler).
      mesh_axis: mesh axis the slots are partitioned over (default 'data').
      telemetry: obs.Telemetry bundle.  The metrics registry (always live)
        absorbs SchedulerStats plus the arrival-to-commit latency histogram;
        an attached tracer records tick-phase spans (see TICK_PHASES);
        ``device_counters=True`` makes the jitted tick accumulate per-stream
        survivor merge depth / starved ticks / renormalization magnitude
        into a device-resident buffer flushed only at retire / report time —
        the tick keeps exactly one host sync (the committed bits).

    Online usage (live connections):
      sched.open_stream("tv-0", producer=gen_of_chunks)  # or submit_chunk
      while serving:
          emitted = sched.step()           # {stream_id: np bits} this tick
      bits, metric = sched.pop_result("tv-0")

    Offline usage (whole table known) — the adapter over the same path:
      sched.submit("tv-0", bm_tables)      # == open + submit_chunk + close
      sched.run()
    """

    def __init__(
        self,
        spec: Union[CodecSpec, ConvCode],
        n_slots: int = 64,
        chunk: int = 64,
        depth: Optional[int] = None,
        backend: str = _w.PACKED_BACKEND,
        normalize: bool = True,
        interpret: Optional[bool] = None,
        inputs: str = "bm",
        max_buffered: Optional[int] = None,
        max_pending: Optional[int] = None,
        mesh: Optional[object] = None,
        mesh_axis: str = "data",
        telemetry: Optional[Telemetry] = None,
    ):
        self.spec = CodecSpec.of(spec)
        code = self.spec.code
        self.code = code
        self.n_slots = n_slots
        self.chunk = chunk
        self.depth = _w.default_depth(code) if depth is None else depth
        self.backend = backend
        self.normalize = normalize
        self.inputs = inputs
        if max_pending is not None and max_pending < 0:
            raise ValueError(f"max_pending must be >= 0, got {max_pending}")
        self.max_pending = max_pending
        self.max_buffered = 8 * chunk if max_buffered is None else int(max_buffered)
        if self.max_buffered < chunk:
            # rows only leave the queue in full-chunk ticks: a bound below
            # one chunk could never fill a tick and the stream would starve
            # forever with its credit pinned at zero
            raise ValueError(
                f"max_buffered ({self.max_buffered}) must be >= chunk ({chunk})"
            )
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        if mesh is not None:
            from repro.parallel.collectives import mesh_axis_size
            from repro.parallel.mesh import check_auto_mesh

            check_auto_mesh(mesh)
            self.n_shards = mesh_axis_size(mesh, mesh_axis)
            if not self.n_shards:
                raise ValueError(f"mesh has no {mesh_axis!r} axis: {mesh}")
            if n_slots % self.n_shards:
                raise ValueError(
                    f"n_slots={n_slots} must divide evenly over the "
                    f"{self.n_shards} shards of mesh axis {mesh_axis!r}"
                )
        else:
            self.n_shards = 1
        self.slots_per_shard = n_slots // self.n_shards
        self.packed, self.depth, self._plan, self._weights = _w.resolve_stream_backend(
            self.spec, chunk, self.depth, backend, inputs
        )
        self._width = (
            self._plan.n_features if inputs == "received" else code.n_symbols
        )
        self.state = _w.init_stream_state(
            code, n_slots, self.depth, chunk, packed=self.packed
        )
        self.offset = jnp.zeros((n_slots,), dtype=jnp.float32)
        self.alloc = SlotAllocator(n_slots)
        self.active: Dict[int, _Stream] = {}
        self.pending: Deque[_Stream] = deque()
        self._by_id: Dict[str, _Stream] = {}  # every OPEN stream, by id
        self.results: Dict[str, Tuple[np.ndarray, float]] = {}
        self.errors: Dict[str, StreamError] = {}  # early-terminated streams
        self.stats = SchedulerStats()
        self._seq = 0  # admission sequence counter (shed tie-break)
        #: straggler detection over per-tick wall time (only ticks that
        #: dispatched real work — idle ticks would poison the EMA).
        self.straggler = StragglerDetector()
        #: test/chaos seam: called with the tick number at the top of the
        #: step phase; a raised TickFault drops the tick (state untouched).
        self.tick_fault_hook = None
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self._tracer = self.telemetry.tracer
        self._latency_hist = self.telemetry.metrics.histogram(
            "stream_arrival_to_commit_seconds",
            buckets=LATENCY_BUCKETS_S,
            help="seconds from chunk arrival to its last bit committing",
        )
        self._depth_hist = self.telemetry.metrics.histogram(
            "stream_merge_depth",
            buckets=DEPTH_BUCKETS,
            help="survivor merge depth of retiring streams (trellis steps)",
        )
        self._tick_hist = self.telemetry.metrics.histogram(
            "stream_tick_seconds",
            buckets=TICK_BUCKETS,
            help="wall time of scheduler ticks that dispatched work",
        )
        self._retry_hist = self.telemetry.metrics.histogram(
            "stream_busy_retry_ticks",
            buckets=DEPTH_BUCKETS,
            help="retry_after_ticks hints handed out with StreamBusy",
        )
        m = self.telemetry.metrics
        self._straggler_ctr = m.counter(
            "stream_tick_straggler_total",
            help="ticks whose wall time the StragglerDetector flagged",
        )
        self._quarantine_ctr = m.counter(
            "stream_quarantined_total",
            help="streams quarantined (poisoned chunk / producer error)",
        )
        self._expired_ctr = m.counter(
            "stream_expired_total", help="streams retired by TTL deadline"
        )
        self._shed_ctr = m.counter(
            "stream_shed_total", help="streams dropped by the overload policy"
        )
        self._device_failure_ctr = m.counter(
            "stream_tick_device_failures_total",
            help="tick device-step failures absorbed (tick dropped + retried)",
        )
        self._poison_ctr = m.counter(
            "stream_poisoned_chunks_total",
            help="chunks rejected for non-finite values or bad shape",
        )
        self._counters = (
            _w.init_device_counters(n_slots)
            if self.telemetry.device_counters
            else None
        )
        self._pm0_row = _initial_pm(code, ())  # (S,) fresh-slot path metrics
        # interpret-mode resolution is pinned ONCE per scheduler (see
        # kernels/common.py): the forward and traceback kernels of every tick
        # and flush must run on the same code path.
        self._interpret = resolve_interpret(interpret)
        # device-resident input arena, laid out per shard: (n_shards, cap, ·)
        # with rows [0, chunk) of every shard kept zero — the read target for
        # idle/starved slots — and each accepted chunk appended to the slab
        # of the shard hosting its stream's slot.  Capacity grows
        # geometrically (so the jitted gather sees a handful of shapes over a
        # server's life, not one per chunk) and the used prefixes are
        # compacted when consumed/retired rows exceed _compact_ratio x the
        # live rows (past _compact_floor, so toy workloads never bother).
        self._arena = jnp.zeros((self.n_shards, chunk, self._width), jnp.float32)
        self._arena_len = [chunk] * self.n_shards  # used rows per shard
        #: per shard, the float32 rows accepted but not yet written, which
        #: end the used prefix: _arena is read only through _read_arena,
        #: which writes them first
        self._staged: List[List[np.ndarray]] = [[] for _ in range(self.n_shards)]
        self._write = _arena_writer(mesh, mesh_axis)
        self._compact_arena = _arena_compactor(mesh, mesh_axis)
        self._compact_ratio = 4
        self._compact_floor = 4096
        if mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            self._arena_sharding = NamedSharding(mesh, P(mesh_axis, None, None))
            self._start_sharding = NamedSharding(mesh, P(mesh_axis))
            self._index_sharding = NamedSharding(mesh, P(mesh_axis, None))
            self._counter_sharding = NamedSharding(mesh, P(mesh_axis))
            self.state = _w.shard_stream_state(mesh, mesh_axis, self.state)
            self._arena = jax.device_put(self._arena, self._arena_sharding)
            self._pin_counters()
            self._step_fn = None  # sharded tick replaces the plain jitted step
            self._sharded_step = _w.make_sharded_stream_step(
                code, mesh, mesh_axis, chunk=chunk, backend=backend,
                normalize=normalize, interpret=self._interpret,
                weights=self._weights,
                device_metrics=self._counters is not None,
            )
        else:
            self._arena_sharding = None
            self._start_sharding = None
            self._index_sharding = None
            self._counter_sharding = None
            self._sharded_step = None
            self._step_fn = _w.jitted_stream_step(
                code, backend=backend, normalize=normalize,
                interpret=self._interpret,
            )
        self._gather = jax.jit(
            lambda arena, idx: jnp.take(arena[0], idx, axis=0)
        )

    # ------------------------------ intake ------------------------------ #

    def open_stream(
        self,
        stream_id: str,
        *,
        terminated: Optional[bool] = None,
        producer=None,
        max_buffered: Optional[int] = None,
        priority: int = 0,
        ttl_ticks: Optional[int] = None,
    ) -> None:
        """Register a stream for chunk-fed decode.  It queues for a slot
        immediately (FIFO) and may sit admitted-but-starved until rows
        arrive via ``submit_chunk`` or the attached ``producer``.

        Args:
          terminated: stream ends in state 0 (defaults to the spec's flag).
          producer: optional chunk source polled every tick within the
            stream's credit — a ChunkProducer, a generator/iterable of row
            arrays, or a poll callable (see stream/ingest.py).  When it
            reports ``exhausted`` the stream is closed automatically.
          max_buffered: per-stream override of the input-queue bound.
          priority: overload-shedding rank — when ``max_pending`` is
            exceeded the LOWEST priority open stream is shed first (newest
            among equals; see ``errors`` for the structured record).
          ttl_ticks: optional deadline, in scheduler ticks from now; once it
            passes, ``evict_expired()`` (run at the top of every tick)
            retires the stream with a partial-result flush and an "expired"
            StreamError.
        """
        if terminated is None:
            terminated = self.spec.terminated
        if stream_id in self._by_id or stream_id in self.results:
            raise KeyError(f"duplicate stream_id {stream_id!r}")
        bound = self.max_buffered if max_buffered is None else int(max_buffered)
        if bound < self.chunk:
            raise ValueError(
                f"max_buffered ({bound}) must be >= chunk ({self.chunk}): a "
                "smaller bound can never buffer a full decode chunk, so the "
                "stream would starve forever"
            )
        if ttl_ticks is not None and ttl_ticks <= 0:
            raise ValueError(f"ttl_ticks must be > 0, got {ttl_ticks}")
        st = _Stream(
            stream_id=stream_id,
            terminated=bool(terminated),
            max_buffered=bound,
            producer=as_producer(producer) if producer is not None else None,
            priority=int(priority),
            deadline_tick=(
                None if ttl_ticks is None else self.stats.ticks + int(ttl_ticks)
            ),
            seq=self._seq,
        )
        self._seq += 1
        self._by_id[stream_id] = st
        self.pending.append(st)
        self.stats.streams_submitted += 1
        self._admit()
        self._shed_overload()

    def submit_chunk(self, stream_id: str, rows, *, close: bool = False) -> int:
        """Feed ``rows`` ((t, M) bm rows or (t, n_out) received symbols per
        the scheduler's ``inputs`` kind; any t >= 0) to an open stream.

        Returns the stream's remaining credit (rows its bounded queue can
        still take).  Raises StreamBusy — accepting nothing — when the chunk
        exceeds the current credit; callers throttle and retry after ticks
        drain the queue.  ``close=True`` marks EOF after accepting the rows
        (same as a separate ``close()``).

        Accepted rows are staged on the host; the device arena receives
        them at its next read (the next tick), in one write for all streams.

        An attached tracer records a ``submit`` span around the call, with
        ``submit.check`` (the stream is open, the rows are well formed and
        within credit) and ``submit.accept`` inside it, and in the latter
        ``submit.features`` and ``submit.append`` (the staging) for an
        admitted stream.  The same spans appear under the tick's ``ingest``
        for producer rows, and features and append under its ``admit`` for
        a queued backlog."""
        with span(self._tracer, "submit") as call:
            with call.first("submit.check") as check:
                st = self._open(stream_id)
                if st.closed:
                    raise RuntimeError(f"stream {stream_id!r} is closed")
                rows = np.asarray(rows, dtype=np.float32)
                self._check_rows(rows)
                n = rows.shape[0]
                credit = st.max_buffered - st.buffered
                if n > credit:
                    self.stats.busy_rejections += 1
                    # hint horizon: ticks until the queue can take this chunk —
                    # capped at the queue bound, since a chunk larger than
                    # max_buffered must be split and can never fit whole
                    retry = self._retry_after_ticks(
                        st, min(n, st.max_buffered) - max(0, credit)
                    )
                    self._retry_hist.observe(retry)
                    raise StreamBusy(
                        stream_id, max(0, credit), n, retry_after_ticks=retry
                    )
            with check.then("submit.accept"):
                if n:
                    self._accept_rows(st, rows)
                if close:
                    st.closed = True
                return max(0, credit - n)

    def attach_producer(self, stream_id: str, producer) -> None:
        """Attach (or replace) a chunk source on an open stream — the
        re-attach half of snapshot/restore, since producers are deliberately
        not serialized (see stream/resilience.py)."""
        st = self._open(stream_id)
        if st.closed:
            raise RuntimeError(f"stream {stream_id!r} is closed")
        st.producer = as_producer(producer)

    def close(self, stream_id: str) -> None:
        """Mark EOF: no more chunks will arrive.  The stream retires once its
        remaining buffered rows (including a mid-chunk tail shorter than one
        decode chunk) are drained — idempotent."""
        self._open(stream_id).closed = True

    def credit(self, stream_id: str) -> int:
        """Rows the stream's bounded input queue can accept right now."""
        st = self._open(stream_id)
        return max(0, st.max_buffered - st.buffered)

    def submit(self, stream_id: str, bm_tables, terminated: Optional[bool] = None) -> None:
        """Whole-table submission — a thin ADAPTER over the chunk path (the
        scheduler's one ingestion code path): opens the stream with enough
        credit for the full table, feeds it as a single chunk, and closes
        it.  bm_tables: (T, M) branch metrics — or raw (T, n_out) received
        symbols for ``inputs='received'``."""
        bm = np.asarray(bm_tables, dtype=np.float32)
        self._check_rows(bm)
        self.open_stream(
            stream_id,
            terminated=terminated,
            max_buffered=max(self.max_buffered, bm.shape[0]),
        )
        self.submit_chunk(stream_id, bm, close=True)

    def evict(self, stream_id: str) -> Optional[np.ndarray]:
        """Cancel a stream.  Returns the bits committed so far (or None if it
        was still awaiting a slot); the slot is recycled immediately.  Any
        attached producer is detached (its undelivered rows — pending credit
        included — are simply never polled again)."""
        st = self._by_id.pop(stream_id, None)
        if st is None:
            raise KeyError(stream_id)
        st.producer = None
        if st.slot is None:
            self.pending.remove(st)
            return None
        partial = self._collect(st)
        del self.active[st.slot]
        self.alloc.release(st.slot)  # state is re-initialized at next claim
        st.slot = None
        self._admit()
        return partial

    def evict_expired(self) -> List[str]:
        """Retire every open stream whose TTL deadline has passed: partial
        result flushed into ``results``, an "expired" StreamError recorded in
        ``errors``, slot recycled.  Runs at the top of every tick; callable
        directly too.  Returns the expired stream ids."""
        now_tick = self.stats.ticks
        expired = [
            st for st in list(self._by_id.values())
            if st.deadline_tick is not None and now_tick >= st.deadline_tick
        ]
        for st in expired:
            self._retire_early(
                st, "expired",
                f"deadline tick {st.deadline_tick} passed at tick {now_tick}",
            )
            self.stats.streams_expired += 1
            self._expired_ctr.inc()
        return [st.stream_id for st in expired]

    def pop_error(self, stream_id: str) -> StreamError:
        """Structured record of an early-terminated stream (+ drop), the
        error-side sibling of ``pop_result``."""
        return self.errors.pop(stream_id)

    # ------------------------------ ticking ------------------------------ #

    def pending_work(self) -> bool:
        return bool(self.active or self.pending)

    def step(self) -> Dict[str, np.ndarray]:
        """One scheduler tick: poll producers, retire drained streams, admit
        pending ones, then advance every slot with a full chunk ready
        through ONE jitted call (slots without one idle, state untouched).
        Returns the bits each stream newly committed this tick.

        When a tracer is attached the tick records a parent ``tick`` span
        with the TICK_PHASES children; disabled tracing costs one ``is
        None`` check per phase (see obs.trace.span)."""
        t0 = time.monotonic()
        ticks_before = self.stats.ticks
        with span(self._tracer, "tick"):
            out = self._step_traced()
        # straggler detection: only ticks that dispatched real device work
        # feed the EMA — idle/starved ticks are microseconds and would make
        # every working tick look like an outlier.
        if self.stats.ticks > ticks_before:
            self._observe_tick_time(time.monotonic() - t0)
        return out

    def _observe_tick_time(self, dt: float) -> None:
        self._tick_hist.observe(dt)
        if self.straggler.observe(self.stats.ticks, dt):
            self.stats.straggler_ticks += 1
            self._straggler_ctr.inc()

    def _step_traced(self) -> Dict[str, np.ndarray]:
        tr = self._tracer
        with span(tr, "ingest"):
            self.evict_expired()
            self._poll_producers()
        # 1. retire closed streams that cannot fill a full chunk (tail +
        #    flush run batched over all slots retiring this tick — off the
        #    hot path), re-admit, and repeat: an admitted pending stream may
        #    itself be closed with less than a chunk buffered and must
        #    retire before the gather sees it.
        with span(tr, "admit"):
            self._admit()
            while True:
                drained = [
                    slot for slot, st in self.active.items()
                    if st.closed and st.available < self.chunk
                ]
                if not drained:
                    break
                self._finish_slots(drained)
                self._admit()
        # 2. slots with a full chunk of rows ready advance; admitted slots
        #    that are starved (open stream, no chunk yet) idle masked —
        #    their gather reads the zero prefix and their carried state is
        #    re-selected unchanged inside stream_step.  Every row staged
        #    since the last tick reaches the device first, in one write
        #    (inside the compaction, when dead rows dominate the arena).
        with span(tr, "gather"):
            self._maybe_compact()
            arena = self._read_arena()
            ready = [
                slot for slot, st in self.active.items()
                if st.available >= self.chunk
            ]
            self.stats.starved_slot_ticks += len(self.active) - len(ready)
            if not ready:
                return {}
            idx = np.zeros((self.n_slots, self.chunk), dtype=np.int32)
            mask = np.zeros((self.n_slots,), dtype=bool)
            for slot in ready:
                idx[slot] = self.active[slot].rows[: self.chunk]
                mask[slot] = True
            idx_j, mask_j = jnp.asarray(idx), jnp.asarray(mask)

        # 3. the one jitted call for all live streams — under shard_map when
        #    the scheduler spans a mesh (gather + step fused, shard-local).
        #    The span measures dispatch, not device time: the only forced
        #    sync stays the bits transfer in the commit phase.
        with span(tr, "step"):
            try:
                if self.tick_fault_hook is not None:
                    # chaos/test seam: a raised TickFault simulates a
                    # transient device-step failure BEFORE any carried state
                    # is reassigned — the tick drops, the next one retries
                    # the identical gather, the decode is unchanged.
                    self.tick_fault_hook(self.stats.ticks)
                if self._sharded_step is not None:
                    if self._counters is not None:
                        self.state, bits, delta, self._counters = self._sharded_step(
                            arena, idx_j, mask_j, self.state, self._counters
                        )
                    else:
                        self.state, bits, delta = self._sharded_step(
                            arena, idx_j, mask_j, self.state
                        )
                else:
                    block = self._gather(arena, idx_j)  # (n_slots, chunk, ·)
                    weights = self._weights if self.packed else None
                    if self._counters is not None:
                        self.state, bits, delta, self._counters = self._step_fn(
                            self.state, block, weights, mask_j,
                            counters=self._counters,
                        )
                    else:
                        self.state, bits, delta = self._step_fn(
                            self.state, block, weights, mask_j
                        )
            except TickFault:
                self.stats.tick_device_failures += 1
                self._device_failure_ctr.inc()
                return {}
            self.offset = self.offset + delta

        # 4. the tick's ONE host sync, then distribute newly-final bits.
        with span(tr, "commit"):
            # The sanctioned device->host transfer: every other per-tick
            # value stays device-resident (DeviceCounters, arena, ring).
            bits_np = np.asarray(bits)  # repr-lint: allow[RPR003]
            self.stats.ticks += 1
            self.stats.steps_decoded += len(ready) * self.chunk
            now = time.monotonic()
            emitted: Dict[str, np.ndarray] = {}
            for slot in ready:
                st = self.active[slot]
                st.rows = st.rows[self.chunk :]
                st.pos += self.chunk
                committable = max(0, st.pos - self.depth)
                n_new = committable - st.committed
                st.committed = committable
                self._observe_commit_latency(st, now)
                if n_new:
                    fresh = bits_np[slot, self.chunk - n_new :]
                    st.out.append(fresh)
                    emitted[st.stream_id] = fresh
            return emitted

    def run(self) -> Dict[str, Tuple[np.ndarray, float]]:
        """Drain everything; returns {stream_id: (bits (T,), metric)}.

        Every open stream must either be closed or have a producer attached:
        a stream waiting on future ``submit_chunk`` calls can never make
        progress inside this loop, so that state raises instead of spinning
        (producer-fed streams busy-poll — their source delivers on its own
        clock)."""
        while self.pending_work():
            marker = self._progress_marker()
            self.step()
            if marker == self._progress_marker() and not any(
                st.producer is not None and not st.closed
                for st in self._by_id.values()
            ):
                starved = sorted(
                    st.stream_id for st in self._by_id.values() if not st.closed
                )
                raise RuntimeError(
                    f"StreamScheduler.run() stalled: open streams {starved} are "
                    "starved with no producer attached — drive step() from your "
                    "serving loop, attach a ChunkProducer, or close() them"
                )
        return self.results

    def _progress_marker(self) -> Tuple[int, int, int]:
        return (
            self.stats.ticks,
            self.stats.streams_finished,
            sum(st.fed + st.queued_rows for st in self._by_id.values()),
        )

    def result(self, stream_id: str) -> Tuple[np.ndarray, float]:
        return self.results[stream_id]

    def pop_result(self, stream_id: str) -> Tuple[np.ndarray, float]:
        """result() + drop — long-lived servers must use this (or otherwise
        prune ``results``) so finished-stream outputs don't accumulate
        forever."""
        return self.results.pop(stream_id)

    def utilization(self) -> float:
        return self.alloc.utilization()

    def load_report(self) -> Dict[str, object]:
        """Occupancy and queue depth per shard plus the mesh-global scalars.
        The per-shard counts come from this controller's bookkeeping; the
        totals reduce through parallel.collectives.sum_across_shards — the
        same psum a multi-controller deployment (one host per shard) would
        issue, so the global view never gathers any decode state.  Callers
        throttle on the queue-depth numbers: ``queued_rows_total`` is how
        much input sits unconsumed on-device, ``starved_active`` how many
        slots are idling for lack of it.

        ``latency_s`` summarizes the arrival-to-commit histogram (always
        tracked); with device counters enabled the report also carries
        ``merge_depth`` — per active stream, the survivor merge-depth
        last/mean/max plus starved ticks and renormalization magnitude,
        materialized here (an explicit drain point, never per tick)."""
        per_shard = np.zeros((self.n_shards,), dtype=np.int32)
        per_shard_queued = np.zeros((self.n_shards,), dtype=np.int32)
        starved = 0
        for slot, st in self.active.items():
            shard = slot // self.slots_per_shard
            per_shard[shard] += 1
            per_shard_queued[shard] += st.available
            if not st.closed and st.available < self.chunk:
                starved += 1
        per_shard_pending = np.zeros((self.n_shards,), dtype=np.int32)
        per_shard_pending[0] = len(self.pending)  # FIFO queue lives host-side
        pending_rows = sum(st.queued_rows for st in self.pending)
        if self.mesh is not None:
            from repro.parallel.collectives import sum_across_shards

            totals = sum_across_shards(
                self.mesh, self.mesh_axis,
                jnp.stack(
                    [
                        jnp.asarray(per_shard),
                        jnp.asarray(per_shard_pending),
                        jnp.asarray(per_shard_queued),
                    ],
                    1,
                ),
            )
            active_total, pending_total, queued_total = (
                int(x) for x in np.asarray(totals)
            )
        else:
            active_total = int(per_shard.sum())
            pending_total = len(self.pending)
            queued_total = int(per_shard_queued.sum())
        report: Dict[str, object] = {
            "n_shards": self.n_shards,
            "per_shard_active": per_shard.tolist(),
            "per_shard_queued_rows": per_shard_queued.tolist(),
            "active_total": active_total,
            "pending_total": pending_total,
            "queued_rows_total": queued_total,
            "pending_rows": pending_rows,
            # deepest single stream queue (vs its max_buffered bound) — the
            # number a throttling caller compares against the credit limit
            "max_stream_queued_rows": max(
                (st.buffered for st in self._by_id.values()), default=0
            ),
            "starved_active": starved,
            "utilization": active_total / self.n_slots,
            "latency_s": self._latency_hist.summary(),
        }
        if self._counters is not None:
            report["merge_depth"] = self.device_counter_report()
        return report

    def device_counter_report(self) -> Dict[str, Dict[str, float]]:
        """Materialize the device-resident counters for every ACTIVE stream:
        {stream_id: {ticks, starved_ticks, merge_depth_last, merge_depth_mean,
        merge_depth_max, renorm_sum}}.  One host transfer per counter leaf,
        only when called — never on the tick path."""
        if self._counters is None:
            raise RuntimeError(
                "device counters are off — construct the scheduler with "
                "telemetry=Telemetry(device_counters=True)"
            )
        leaves = {
            name: np.asarray(x)
            for name, x in zip(_w.DeviceCounters._fields, self._counters)
        }
        out: Dict[str, Dict[str, float]] = {}
        for slot, st in self.active.items():
            ticks = int(leaves["ticks"][slot])
            out[st.stream_id] = {
                "ticks": ticks,
                "starved_ticks": int(leaves["starved_ticks"][slot]),
                "merge_depth_last": int(leaves["merge_depth_last"][slot]),
                "merge_depth_mean": (
                    float(leaves["merge_depth_sum"][slot]) / ticks if ticks else 0.0
                ),
                "merge_depth_max": int(leaves["merge_depth_max"][slot]),
                "renorm_sum": float(leaves["renorm_sum"][slot]),
            }
        return out

    def metrics_snapshot(self) -> Dict[str, object]:
        """Mirror SchedulerStats into the metrics registry and return one
        JSON-ready snapshot (scalars + histogram summaries)."""
        m = self.telemetry.metrics
        for name, v in self.stats.asdict().items():
            m.counter(
                f"scheduler_{name}", help=f"SchedulerStats.{name}"
            ).set(v)
        m.gauge("scheduler_active_slots").set(len(self.active))
        m.gauge("scheduler_pending_streams").set(len(self.pending))
        m.gauge("scheduler_utilization").set(self.utilization())
        return m.snapshot()

    def metrics_text(self) -> str:
        """Prometheus text exposition of the scheduler's registry."""
        self.metrics_snapshot()
        return self.telemetry.metrics.render()

    # --------------------------- snapshot/restore --------------------------- #

    def snapshot(self):
        """Freeze the full serving state — slot table, device arena rows,
        path metrics, survivor ring, renorm offsets, DeviceCounters,
        per-stream queues/credits, stats/results/errors — into a versioned
        on-host :class:`~repro.stream.resilience.StreamSnapshot`.  The
        scheduler is untouched and keeps serving.  Call between ticks (every
        call site is one: the API is host-driven)."""
        from repro.stream.resilience import snapshot_scheduler

        return snapshot_scheduler(self)

    @classmethod
    def restore(
        cls,
        snap,
        *,
        mesh: Optional[object] = None,
        mesh_axis: str = "data",
        telemetry: Optional[Telemetry] = None,
        interpret: Optional[bool] = None,
    ) -> "StreamScheduler":
        """Resume a snapshot on a fresh scheduler — same or different mesh
        shape — with committed output bit-exact vs the uninterrupted run.
        Producers are not restored; re-attach with ``attach_producer``."""
        from repro.stream.resilience import restore_scheduler

        return restore_scheduler(
            snap, mesh=mesh, mesh_axis=mesh_axis,
            telemetry=telemetry, interpret=interpret,
        )

    # ------------------------------ internals ------------------------------ #

    def _shard_of(self, slot: int) -> int:
        return slot // self.slots_per_shard

    def _open(self, stream_id: str) -> _Stream:
        try:
            return self._by_id[stream_id]
        except KeyError:
            raise KeyError(
                f"unknown or finished stream {stream_id!r} (open_stream first)"
            ) from None

    def _check_rows(self, rows: np.ndarray) -> None:
        expected = (
            self.code.n_out if self.inputs == "received" else self.code.n_symbols
        )
        kind = "received symbols" if self.inputs == "received" else "bm tables"
        if rows.ndim != 2 or rows.shape[1] != expected:
            raise ValueError(
                f"{self.inputs!r} streams take {kind} shaped (t, {expected}), "
                f"got {rows.shape}"
            )
        if rows.size and not np.isfinite(rows).all():
            # a single NaN/Inf symbol would corrupt path metrics for EVERY
            # stream in the batch tick (renormalization subtracts a max over
            # the slot axis) — reject at the boundary, poison nothing.
            bad = int(np.count_nonzero(~np.isfinite(rows)))
            self.stats.poisoned_rejections += 1
            self._poison_ctr.inc()
            raise ValueError(
                f"non-finite input: {bad} NaN/Inf value(s) in a {rows.shape} "
                "chunk — non-finite symbols corrupt path metrics for the "
                "whole batch tick"
            )

    def _accept_rows(self, st: _Stream, rows: np.ndarray) -> None:
        """Route an accepted chunk: staged for the arena for admitted streams,
        host-side queue otherwise (no shard known until a slot is claimed)."""
        # latency bookkeeping: a chunk counts as committed once the commit
        # watermark passes its LAST row (fed + queued_rows is the cumulative
        # arrival count regardless of which side of admission the rows land)
        st.arrivals.append(
            (st.fed + st.queued_rows + rows.shape[0], time.monotonic())
        )
        if st.slot is not None:
            self._append_stream_rows(st, rows)
        else:
            st.queued.append(rows)
            st.queued_rows += rows.shape[0]
        self.stats.chunks_submitted += 1

    def _observe_commit_latency(self, st: _Stream, now: float) -> None:
        while st.arrivals and st.arrivals[0][0] <= st.committed:
            _, ts = st.arrivals.popleft()
            self._latency_hist.observe(now - ts)

    def _append_stream_rows(self, st: _Stream, rows: np.ndarray) -> None:
        """Stage a chunk for the stream's shard slab and extend its row map.
        Features are built here on the host, chunk-by-chunk (``t0=st.fed``
        keeps the puncture phase right no matter how arrival sizes slice the
        stream), into a fresh array: the caller may reuse its buffer before
        the rows reach the device."""
        with span(self._tracer, "submit.features"):
            if self.inputs == "received":
                data = self._plan.host_features(rows, t0=st.fed)
            else:
                data = np.array(rows, dtype=np.float32)
        with span(self._tracer, "submit.append"):
            start = self._append_rows(st.shard, data)
            st.rows = np.concatenate(
                [st.rows, np.arange(start, start + rows.shape[0], dtype=np.int32)]
            )
            st.fed += rows.shape[0]

    def _poll_producers(self) -> None:
        """Pull from attached producers into each stream's queue, never past
        its credit — the scheduler-side half of the backpressure contract.

        One stream's fault never fails the tick: a poisoned chunk (bad
        values/shape) or a raised producer exception quarantines THAT stream
        — partial result flushed, structured StreamError recorded — and the
        loop moves on to the next producer."""
        for st in list(self.active.values()) + list(self.pending):
            if st.producer is None or st.closed:
                continue
            try:
                credit = st.max_buffered - st.buffered
                if credit > 0:
                    got = st.producer.poll(credit)
                    if got is not None:
                        got = np.asarray(got, dtype=np.float32)
                        if got.shape[0]:
                            with span(self._tracer, "submit.check"):
                                self._check_rows(got)
                            if got.shape[0] > credit:
                                raise ValueError(
                                    f"producer for {st.stream_id!r} returned "
                                    f"{got.shape[0]} rows against credit {credit}"
                                )
                            with span(self._tracer, "submit.accept"):
                                self._accept_rows(st, got)
                if st.producer.exhausted:
                    st.closed = True
            except ValueError as e:
                self._quarantine(st, "poisoned_chunk", repr(e))
            except Exception as e:  # noqa: BLE001 — producer code is untrusted
                self._quarantine(st, "producer_error", repr(e))

    # --------------------- graceful degradation --------------------- #

    def _quarantine(self, st: _Stream, reason: str, detail: str) -> None:
        self._retire_early(st, reason, detail)
        self.stats.streams_quarantined += 1
        self._quarantine_ctr.inc()

    def _retire_early(self, st: _Stream, reason: str, detail: str) -> None:
        """Fail ONE stream without failing the tick: flush the partial
        result it already DECODED (committed prefix + the traceback window),
        recycle the slot, and record a structured StreamError in ``errors``.
        Buffered-but-undecoded input is dropped — a failing stream's salvage
        is its decoded prefix, and a multi-hundred-row backlog cannot pass
        through the flush tail-feed (the survivor ring only spans
        depth + chunk steps)."""
        st.producer = None
        st.closed = True
        st.queued, st.queued_rows = [], 0
        st.rows = st.rows[:0]
        st.fed = st.pos
        # an early cut is a truncation: the encoder never flushed to state 0
        # at the cut point, so the final traceback must start from the best
        # state, not the terminated=True state-0 path
        st.terminated = False
        if st.slot is not None:
            self._finish_slots([st.slot])
        else:
            self.pending.remove(st)
            del self._by_id[st.stream_id]
        result = self.results.get(st.stream_id)
        self.errors[st.stream_id] = StreamError(
            stream_id=st.stream_id,
            reason=reason,
            detail=detail,
            tick=self.stats.ticks,
            committed_bits=0 if result is None else int(result[0].shape[0]),
        )
        self._admit()

    def _shed_overload(self) -> None:
        """Overload policy: when the pending queue outgrows ``max_pending``,
        shed the globally lowest-priority open stream (pending preferred over
        active among equals, newest last-in first) with a partial-result
        flush — admission never stalls, and the victim is recorded in
        ``errors`` rather than silently dropped."""
        if self.max_pending is None:
            return
        while len(self.pending) > self.max_pending:
            victim = min(
                self._by_id.values(),
                key=lambda s: (s.priority, 0 if s.slot is None else 1, -s.seq),
            )
            self._retire_early(
                victim, "shed",
                f"overload: {len(self.pending)} pending > max_pending "
                f"{self.max_pending}; priority {victim.priority} shed",
            )
            self.stats.streams_shed += 1
            self._shed_ctr.inc()

    def _retry_after_ticks(self, st: _Stream, deficit: int) -> int:
        """Backoff hint handed out with StreamBusy: admitted streams drain
        one chunk per tick, so the deficit converts directly; a pending
        stream first waits out its FIFO position (approximated as one tick
        per admission ahead of it)."""
        ticks = max(1, -(-int(deficit) // self.chunk))
        if st.slot is None:
            try:
                ticks += self.pending.index(st) + 1
            except ValueError:
                ticks += 1
        return ticks

    def _pin_arena(self) -> None:
        """Re-assert the per-shard arena placement after its eager growth."""
        if self._arena_sharding is not None:
            self._arena = jax.device_put(self._arena, self._arena_sharding)

    def _pin_state(self) -> None:
        if self.mesh is not None:
            self.state = _w.shard_stream_state(self.mesh, self.mesh_axis, self.state)

    def _pin_counters(self) -> None:
        if self._counters is not None and self._counter_sharding is not None:
            self._counters = _w.DeviceCounters(
                *(jax.device_put(x, self._counter_sharding) for x in self._counters)
            )

    def _admit(self) -> None:
        while self.pending and self.alloc.free:
            st = self.pending.popleft()
            slot = self.alloc.claim(st.stream_id)
            # reset at CLAIM time, not release time: a recycled slot's pm/ring
            # must not leak the previous resident's state into the
            # start-in-state-0 constraint (paper §IV-B) for the next stream.
            self._reset_slot(slot)
            st.slot = slot
            st.shard = self._shard_of(slot)
            self.active[slot] = st
            self.stats.slot_claims += 1
            if st.queued:
                queued, st.queued, st.queued_rows = st.queued, [], 0
                self._append_stream_rows(st, np.concatenate(queued, axis=0))

    def _append_rows(self, shard: int, rows: np.ndarray) -> int:
        """Give float32 ``rows`` the next rows of a shard's used prefix and
        stage them on the host for the next arena write; returns the
        shard-local start row."""
        start = self._arena_len[shard]
        self._staged[shard].append(rows)
        self._arena_len[shard] = start + rows.shape[0]
        self.stats.arena_appends += 1
        return start

    def _read_arena(self) -> jax.Array:
        """The device arena with every staged row written: the one way the
        tick, compaction, tail feeds and snapshots read it.

        Staged rows go in with ONE jitted, donated write over all shards.  A
        shard's staged rows are the end of its used prefix (appends take the
        next rows, and compaction reads, so writes, first), so they land as
        one block per shard.  Blocks are padded with zeros to a power-of-two
        row count, at least ``chunk``, so a server compiles a handful of
        shapes; the padding falls on rows past the used prefix, which stay
        zero, and the (uniform) capacity is doubled first where a block
        would overrun it."""
        if not any(self._staged):
            return self._arena
        with span(self._tracer, "arena.write"):
            counts = [sum(r.shape[0] for r in staged) for staged in self._staged]
            bucket = 1 << (max(max(counts), self.chunk) - 1).bit_length()
            start = np.array(
                [n - k for n, k in zip(self._arena_len, counts)], np.int32
            )
            cap = self._arena.shape[1]
            need = int(start.max()) + bucket
            if need > cap:
                new_cap = max(2 * cap, need)
                self._arena = jnp.concatenate(
                    [
                        self._arena,
                        jnp.zeros(
                            (self.n_shards, new_cap - cap, self._width), jnp.float32
                        ),
                    ],
                    axis=1,
                )
                if self._arena_sharding is not None:
                    with span(self._tracer, "arena.pin"):
                        self._pin_arena()
            block = np.zeros((self.n_shards, bucket, self._width), np.float32)
            for shard, staged in enumerate(self._staged):
                if staged:
                    np.concatenate(staged, out=block[shard, : counts[shard]])
                    staged.clear()
            if self._arena_sharding is not None:
                start = jax.device_put(start, self._start_sharding)
                block = jax.device_put(block, self._arena_sharding)
            self._arena = self._write(self._arena, start, block)
            self.stats.arena_writes += 1
        return self._arena

    def _maybe_compact(self) -> None:
        """Rebuild every shard's used prefix from its live (unconsumed)
        segments when dead rows dominate the arena (checked once a tick,
        before its gather; keeps long-lived servers bounded).  Capacity is
        kept when the live rows fit, so the tick's compiled shape survives
        the compaction."""
        live = sum(st.available for st in self.active.values()) + sum(
            st.queued_rows for st in self._by_id.values()
        )
        if sum(self._arena_len) <= max(
            self._compact_ratio * (live + self.n_shards * self.chunk),
            self._compact_floor,
        ):
            return
        with span(self._tracer, "compact"):
            self._compact()

    def _compact(self) -> None:
        """Rebuild every shard's slab as the zero prefix, then each admitted
        stream's unconsumed rows in order, then zeros to the capacity: one
        jitted row gather (shard-local on a mesh) by a host-built source
        index whose shape depends only on the capacity, so it compiles once
        per capacity.  Zero rows read row 0."""
        arena = self._read_arena()
        src = np.zeros(arena.shape[:2], np.int32)
        cursor = [self.chunk] * self.n_shards
        for st in self.active.values():
            at, n = cursor[st.shard], st.available
            src[st.shard, at : at + n] = st.rows
            st.rows = np.arange(at, at + n, dtype=np.int32)
            cursor[st.shard] = at + n
        src = jax.device_put(src, self._index_sharding)
        self._arena = self._compact_arena(arena, src)
        self._arena_len = cursor
        self.stats.arena_compactions += 1

    def _collect(self, st: _Stream) -> np.ndarray:
        return (
            np.concatenate(st.out) if st.out else np.zeros((0,), dtype=np.int32)
        ).astype(np.int32)

    def _reset_slot(self, slot: int) -> None:
        self.state = _w.StreamState(
            pm=self.state.pm.at[slot].set(self._pm0_row),
            ring=self.state.ring.at[:, slot].set(0),
        )
        self._pin_state()
        self.offset = self.offset.at[slot].set(0.0)
        if self._counters is not None:
            # counters reset at claim for the same reason as pm/ring: the
            # recycled slot must not leak the previous resident's statistics
            self._counters = _w.DeviceCounters(
                *(x.at[slot].set(0) for x in self._counters)
            )
            self._pin_counters()

    def _tail_rows(self, st: _Stream) -> jnp.ndarray:
        """(r, M) bm tables for a stream's remaining sub-chunk tail, gathered
        from its shard's arena slab by row index (raw features go through
        the metric plan)."""
        seg = jnp.take(self._read_arena()[st.shard], jnp.asarray(st.rows), axis=0)
        if self.inputs == "received":
            return self._plan.bm_from_features(seg)
        return seg

    def _finish_slots(self, slots: Sequence[int]) -> None:
        """Tail-feed + final traceback for every drained stream retiring this
        tick, then recycle the slots.  Tails are fed grouped by length (one
        jitted_chunk_forward per distinct tail length) and the final
        traceback over all retirees runs as ONE batched jitted_stream_flush
        per termination kind — not one dispatch per slot.  Every batched call
        is padded to ``n_slots`` rows so cohort size never creates a new
        compiled shape (padded rows decode garbage that is sliced away).
        Packed survivor rings are unpacked here, once, off the hot path."""
        with span(self._tracer, "flush"):
            self._finish_slots_traced(slots)

    def _finish_slots_traced(self, slots: Sequence[int]) -> None:
        streams = [(slot, self.active.pop(slot)) for slot in slots]
        if self._counters is not None:
            # retirement IS the device-counter drain point: one host read of
            # the (B,) merge-depth leaf for the whole cohort, off the hot path
            md_last = np.asarray(self._counters.merge_depth_last)
            for slot, _ in streams:
                self._depth_hist.observe(int(md_last[slot]))

        def pad_rows(x: jnp.ndarray, axis: int) -> jnp.ndarray:
            extra = self.n_slots - x.shape[axis]
            if extra <= 0:
                return x
            widths = [(0, 0)] * x.ndim
            widths[axis] = (0, extra)
            return jnp.pad(x, widths)

        # the flush math below slices slot subsets with fancy indexing; on a
        # sharded state every such op would become its own cross-shard
        # gather, so materialize the retiring cohort's state onto one device
        # first (off the hot path, and the tick state itself is untouched).
        pm_frontier = self.state.pm
        ring = self.state.ring
        if self.mesh is not None:
            pm_frontier = jnp.asarray(np.asarray(pm_frontier))
            ring = jnp.asarray(np.asarray(ring))
        if self.packed:
            ring = _w.unpack_ring(self.code, ring)  # (R, n_slots, S)

        # tail-feed, grouped by tail length r (each group one batched call)
        by_r: Dict[int, List[Tuple[int, _Stream]]] = {}
        for slot, st in streams:
            by_r.setdefault(st.available, []).append((slot, st))
        ordered: List[Tuple[int, _Stream]] = []
        pm_parts: List[jnp.ndarray] = []
        ring_parts: List[jnp.ndarray] = []
        for r, group in sorted(by_r.items()):
            n = len(group)
            idx = jnp.asarray([slot for slot, _ in group])
            pm_g = pm_frontier[idx]  # (n, S)
            ring_g = ring[:, idx]  # (R, n, S)
            if r > 0:
                tails = pad_rows(
                    jnp.stack([self._tail_rows(st) for _, st in group]), 0
                )  # (n_slots, r, M)
                pm_p, bps = _w.jitted_chunk_forward(self.code)(
                    pad_rows(pm_g, 0), tails
                )
                pm_g = pm_p[:n]
                ring_g = jnp.concatenate([ring_g[r:], bps[:, :n]], axis=0)
                for _, st in group:
                    st.pos += r
                    st.rows = st.rows[r:]
            ordered.extend(group)
            pm_parts.append(pm_g)
            ring_parts.append(ring_g)
        pm_all = jnp.concatenate(pm_parts, axis=0)  # (n_total, S)
        ring_all = jnp.concatenate(ring_parts, axis=1)  # (R, n_total, S)

        # one flush per termination kind (a single call in the common case
        # of uniformly-terminated streams)
        flushed: Dict[int, Tuple[np.ndarray, float]] = {}
        for term in (True, False):
            rows = [i for i, (_, st) in enumerate(ordered) if st.terminated == term]
            if not rows:
                continue
            sel = jnp.asarray(rows)
            bits, metric = _w.jitted_stream_flush(
                self.code, terminated=term, interpret=self._interpret
            )(
                _w.StreamState(
                    pm=pad_rows(pm_all[sel], 0), ring=pad_rows(ring_all[:, sel], 1)
                )
            )
            bits_np, metric_np = np.asarray(bits), np.asarray(metric)
            for k, i in enumerate(rows):
                flushed[i] = (bits_np[k], float(metric_np[k]))

        R = ring.shape[0]
        offset_np = np.asarray(self.offset)  # one transfer, not one per slot
        now = time.monotonic()
        for i, (slot, st) in enumerate(ordered):
            bits_i, metric_i = flushed[i]
            n_rest = st.pos - st.committed
            if n_rest:
                st.out.append(bits_i[R - n_rest :])
            st.committed = st.pos
            self._observe_commit_latency(st, now)
            self.results[st.stream_id] = (
                self._collect(st), metric_i + float(offset_np[slot])
            )
            self.stats.streams_finished += 1
            st.slot = None
            del self._by_id[st.stream_id]
            self.alloc.release(slot)  # state is re-initialized at next claim


def _per_shard(fn, mesh, axis: str, ranks: Sequence[int]):
    """``fn`` over the (n_shards, cap, W) arena and per-shard arguments of
    the given ranks, jitted with the arena donated.  On a mesh each chip
    runs ``fn`` on its own slab and argument rows under shard_map, so
    nothing crosses chips; without one, the one shard is the whole arena."""
    if mesh is None:
        return jax.jit(fn, donate_argnums=0)
    from jax.sharding import PartitionSpec as P

    from repro.parallel.mesh import shard_map

    specs = tuple(P(axis, *(None,) * (rank - 1)) for rank in ranks)
    return jax.jit(
        shard_map(fn, mesh=mesh, in_specs=specs, out_specs=specs[0]),
        donate_argnums=0,
    )


@functools.lru_cache(maxsize=None)
def _arena_writer(mesh, axis: str):
    """``write(arena, start, block)``: writes shard ``s``'s rows
    ``block[s]`` into its slab of the (n_shards, cap, W) arena from row
    ``start[s]`` on, in place (the arena is donated), shard-local on a
    mesh.  One jitted callable per (mesh, axis), shared by every scheduler.

    A dynamic_update_slice, not a row scatter: on the TPU a (cap, W) slab
    with W of 2 keeps its rows along the lanes, and a scatter of rows there
    relayouts the whole arena through a lane-padded temporary."""

    def write(arena, start, block):
        # one shard's slab: (1, cap, W); a scheduler without a mesh has one
        return jax.lax.dynamic_update_slice(arena, block, (0, start[0], 0))

    return _per_shard(write, mesh, axis, (3, 1, 3))


@functools.lru_cache(maxsize=None)
def _arena_compactor(mesh, axis: str):
    """``compact(arena, src)``: the (n_shards, cap, W) arena rebuilt into
    its own (donated) buffer, each shard's slab row ``r`` taken from its row
    ``src[s, r]``, shard-local on a mesh.  One jitted callable per (mesh,
    axis), shared by every scheduler.

    On the TPU the (cap, W) slab keeps its rows along the lanes, so the
    gather goes through a lane-padded temporary of about 1 KB per row."""

    def compact(arena, src):
        # one shard's slab: (1, cap, W); a scheduler without a mesh has one
        return jnp.take(arena[0], src[0], axis=0, mode="clip")[None]

    return _per_shard(compact, mesh, axis, (3, 2))
