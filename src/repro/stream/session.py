"""Stateful streaming decode sessions.

A StreamSession owns the carried StreamState for one (optionally batched)
bitstream and the python-side bookkeeping that the jittable core cannot do:
how many steps have been pushed, how many bits are already committed, and
therefore which slice of each chunk's committed window is actually valid.
Memory is O(depth + chunk) regardless of stream length; path metrics are
renormalized every chunk so float32 never saturates, with the accumulated
offset tracked so ``finish`` still reports the absolute path metric.

Backends: ``fused_packed`` (the default) runs the memory-lean pipeline —
bit-packed survivor ring, on-device traceback — on (B, chunk, M)
branch-metric tables, or with ``inputs="received"`` on raw (B, chunk, n_out)
channel symbols, computing branch metrics in-kernel (kernels/metrics.py).
The packed ring shifts whole uint32 words, so the chunk must be a multiple
of 32 and the depth is rounded up to one (a deeper window only helps
accuracy; the lag grows accordingly).  ``scan`` is the jnp reference on
branch-metric tables, at any chunk and depth.

Typical use:

    sess = StreamSession(code, chunk=64)
    for bm_chunk in channel:                  # (B, 64, M) each
        emit(sess.push(bm_chunk))             # (B, <=64) newly-final bits
    emit(*sess.finish(terminated=True))       # the last `depth` bits + metric
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.trellis import ConvCode
from repro.decode.spec import CodecSpec
from repro.kernels.common import resolve_interpret
from repro.obs import Telemetry
from repro.obs.trace import span
from repro.stream import window as _w


class StreamSession:
    """Online Viterbi decoder for one stream (or a batch sharing timing).

    Args:
      spec: a CodecSpec (or a bare ConvCode, promoted with defaults) — its
        ``terminated`` flag is the default for ``finish``/``decode_all``.
      batch: number of independent streams advanced in lock-step (one jitted
        call decodes all of them; the scheduler uses this with batch=n_slots).
      chunk: trellis steps consumed per push (fixed — one compiled shape).
      depth: truncated-traceback depth D; bits commit D steps behind the
        frontier.  Default 5*K (the textbook rule); rounded up to a multiple
        of 32 for the packed backend.
      backend: 'fused_packed' (Pallas scan, packed survivors + on-device
        traceback; chunk % 32 == 0) or 'scan' (jnp reference).
      inputs: 'bm' — push takes (B, chunk, M) branch-metric tables;
        'received' (fused_packed only) — push takes raw (B, chunk, n_out)
        channel symbols and the kernel computes the metrics.
      normalize: renormalize path metrics every chunk (required for streams
        longer than ~1e30/bm_max steps; cheap, on by default).
      mesh: optional device mesh — carry the session state as per-shard
        pytrees partitioned along ``mesh_axis`` (batch must divide evenly);
        pushed chunks are placed with the same layout so the jitted step
        runs batch-parallel across the mesh with no resharding.
      mesh_axis: mesh axis the batch is sharded over (default 'data').
      telemetry: obs.Telemetry bundle — an attached tracer records ``push``
        / ``finish`` spans; ``device_counters=True`` carries a DeviceCounters
        pytree through every push (merge depth, renorm magnitude), exposed
        host-side via :meth:`device_counter_report`, materialized only there.
    """

    def __init__(
        self,
        spec: Union[CodecSpec, ConvCode],
        batch: int = 1,
        chunk: int = 64,
        depth: Optional[int] = None,
        backend: str = _w.PACKED_BACKEND,
        normalize: bool = True,
        interpret: Optional[bool] = None,
        inputs: str = "bm",
        mesh: Optional[object] = None,
        mesh_axis: str = "data",
        telemetry: Optional[Telemetry] = None,
        validate: bool = True,
    ):
        if chunk < 1:
            raise ValueError("chunk must be >= 1")
        #: reject non-finite chunks at push time (a single NaN/Inf poisons
        #: the carried path metrics for every stream in the batch, silently).
        #: ``validate=False`` skips the host-side isfinite scan for callers
        #: feeding device arrays on a measured hot path.
        self.validate = bool(validate)
        self.spec = CodecSpec.of(spec)
        code = self.spec.code
        self.code = code
        self.batch = batch
        self.chunk = chunk
        self.depth = _w.default_depth(code) if depth is None else depth
        if self.depth < 1:
            raise ValueError("depth must be >= 1")
        self.backend = backend
        self.inputs = inputs
        self.packed, self.depth, self._plan, self._weights = _w.resolve_stream_backend(
            self.spec, chunk, self.depth, backend, inputs
        )
        self.state = _w.init_stream_state(
            code, batch, self.depth, chunk, packed=self.packed
        )
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self._chunk_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from repro.parallel.collectives import mesh_axis_size

            n = mesh_axis_size(mesh, mesh_axis)
            if not n:
                raise ValueError(f"mesh has no {mesh_axis!r} axis: {mesh}")
            if batch % n:
                raise ValueError(
                    f"batch={batch} must divide evenly over the {n} shards "
                    f"of mesh axis {mesh_axis!r}"
                )
            self.state = _w.shard_stream_state(mesh, mesh_axis, self.state)
            self._chunk_sharding = NamedSharding(mesh, P(mesh_axis, None, None))
        self.offset = jnp.zeros((batch,), dtype=jnp.float32)
        self.t = 0  # trellis steps pushed so far
        self.committed = 0  # bits already handed to the caller
        self.closed = False
        # pin interpret-mode resolution once per session (kernels/common.py):
        # every kernel this session dispatches — forward chunks, tail feeds,
        # the flush traceback — must resolve to the same code path.
        self._interpret = resolve_interpret(interpret)
        self._step = _w.jitted_stream_step(
            code, backend=backend, normalize=normalize, interpret=self._interpret
        )
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self._tracer = self.telemetry.tracer
        self._counters = (
            _w.init_device_counters(batch)
            if self.telemetry.device_counters
            else None
        )

    @property
    def ring_size(self) -> int:
        return self.depth + self.chunk

    @property
    def lag(self) -> int:
        """Bits pushed but not yet committed (== depth at steady state)."""
        return self.t - self.committed

    def push(self, chunk_data: jnp.ndarray) -> jnp.ndarray:
        """Advance the stream by exactly ``chunk`` steps.

        Args:
          chunk_data: (B, chunk, M) branch-metric tables, or raw
            (B, chunk, n_out) symbols for ``inputs='received'``.
        Returns:
          (B, n_new) newly-committed bits, n_new in [0, chunk] — 0 while the
          window warms up, exactly ``chunk`` at steady state.
        """
        if self.closed:
            raise RuntimeError("session is finished")
        if chunk_data.shape[:2] != (self.batch, self.chunk):
            raise ValueError(
                f"expected ({self.batch}, {self.chunk}, ·) chunk, got {chunk_data.shape}"
            )
        if self.validate:
            flat = np.asarray(chunk_data)
            if not np.isfinite(flat).all():
                bad = int(np.count_nonzero(~np.isfinite(flat)))
                raise ValueError(
                    f"non-finite input: {bad} NaN/Inf value(s) in a "
                    f"{flat.shape} chunk — they would silently corrupt the "
                    "carried path metrics for the whole batch "
                    "(validate=False to skip this check)"
                )
        if self.inputs == "received":
            chunk_data = self._plan.features(chunk_data, t0=self.t)
        if self._chunk_sharding is not None:
            chunk_data = jax.device_put(jnp.asarray(chunk_data), self._chunk_sharding)
        weights = self._weights if self.packed else None
        with span(self._tracer, "push"):
            if self._counters is not None:
                self.state, bits, delta, self._counters = self._step(
                    self.state, chunk_data, weights, counters=self._counters
                )
            else:
                self.state, bits, delta = self._step(self.state, chunk_data, weights)
        self.offset = self.offset + delta
        self.t += self.chunk
        committable = max(0, self.t - self.depth)
        n_new = committable - self.committed
        self.committed = committable
        # the committed window covers positions [t-R, t-D); its valid tail
        # (positions >= previous commit point) is the last n_new entries.
        return bits[:, self.chunk - n_new :] if n_new else bits[:, :0]

    def _tail_bm(self, tail: jnp.ndarray) -> jnp.ndarray:
        """Branch-metric tables for an odd-length tail (raw symbols are
        converted through the metric plan, phased at the current step)."""
        if self.inputs == "received":
            return self._plan.bm_tables(tail, t0=self.t)
        return tail

    def finish(
        self,
        bm_tail: Optional[jnp.ndarray] = None,
        terminated: Optional[bool] = None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Consume an optional odd-length tail and flush the window.

        Args:
          bm_tail: (B, r, ·) with 0 < r < chunk, or None (same input kind as
            ``push``).
          terminated: the stream ends in state 0 (encoder flushed); defaults
            to the spec's ``terminated`` flag.
        Returns:
          bits: (B, lag) the remaining uncommitted bits.
          metric: (B,) absolute winning path metric (normalization undone).
        """
        if self.closed:
            raise RuntimeError("session is finished")
        if terminated is None:
            terminated = self.spec.terminated
        if bm_tail is not None and bm_tail.shape[1]:
            r = bm_tail.shape[1]
            if r >= self.chunk or bm_tail.shape[0] != self.batch:
                raise ValueError(f"tail must be (B, <chunk, ·), got {bm_tail.shape}")
            if self.validate and not np.isfinite(np.asarray(bm_tail)).all():
                raise ValueError(
                    "non-finite input: NaN/Inf value(s) in the finish() tail "
                    "(validate=False to skip this check)"
                )
            tail_bm = self._tail_bm(bm_tail)
            ring = self.state.ring
            if self.packed:
                # word shifts can't absorb an odd tail: unpack once, off the
                # hot path — the flush runs on the unpacked ring.
                ring = _w.unpack_ring(self.code, ring)
            new_pm, bps = _w.jitted_chunk_forward(self.code)(self.state.pm, tail_bm)
            ring = jnp.concatenate([ring[r:], bps], axis=0)
            self.state = _w.StreamState(pm=new_pm, ring=ring)
            self.t += r
        with span(self._tracer, "finish"):
            bits, metric = _w.jitted_stream_flush(
                self.code, terminated=terminated, interpret=self._interpret
            )(self.state)
        n_rest = self.t - self.committed
        self.committed = self.t
        self.closed = True
        R = bits.shape[1]
        return bits[:, R - n_rest :] if n_rest else bits[:, :0], metric + self.offset

    def device_counter_report(self) -> dict:
        """Materialize the per-row device counters (one host transfer per
        leaf, never on the push path): {field: (B,) list} plus the derived
        ``merge_depth_mean``."""
        if self._counters is None:
            raise RuntimeError(
                "device counters are off — construct the session with "
                "telemetry=Telemetry(device_counters=True)"
            )
        leaves = {
            name: np.asarray(x)
            for name, x in zip(_w.DeviceCounters._fields, self._counters)
        }
        ticks = np.maximum(leaves["ticks"], 1)
        out = {name: x.tolist() for name, x in leaves.items()}
        out["merge_depth_mean"] = (leaves["merge_depth_sum"] / ticks).tolist()
        return out

    def decode_all(
        self, bm_tables: jnp.ndarray, terminated: Optional[bool] = None
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Push a full (B, T, ·) block through this session and return the
        complete (B, T) decode + metric.  Convenience for tests/benchmarks
        (tables or raw symbols per the session's ``inputs`` kind)."""
        B, T = bm_tables.shape[:2]
        out = []
        n_full = T // self.chunk
        for i in range(n_full):
            out.append(self.push(bm_tables[:, i * self.chunk : (i + 1) * self.chunk]))
        tail = bm_tables[:, n_full * self.chunk :]
        rest, metric = self.finish(tail if tail.shape[1] else None, terminated=terminated)
        out.append(rest)
        return jnp.concatenate(out, axis=1), metric
