"""Request/result/context dataclasses of the unified decode API.

DecodeContext  everything about *where/how* to run that is not part of the
               codec itself: mesh, chunking, streaming window depth,
               interpret-mode override.  The planner consumes it to pick a
               backend; the chosen backend consumes it to execute.
DecodeRequest  one decode job: a CodecSpec plus either raw channel output
               (``received``) or precomputed branch-metric tables.
DecodeResult   bits + path metric + per-stream diagnostics + the plan that
               produced them.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Any, Dict, Optional

import jax.numpy as jnp

from repro.decode.spec import CodecSpec

if TYPE_CHECKING:  # planner imports this module; annotation only
    from repro.decode.planner import DecodePlan


@dataclasses.dataclass(frozen=True)
class DecodeContext:
    """Execution context shared by the planner and every backend.

    Attributes:
      mesh: jax device mesh for distributed backends (None = single device).
      mesh_axis: mesh axis name the sequence is sharded over.
      batch_axis: mesh axis name batch/slot-parallel backends shard over
        (the sharded stream scheduler's slot table spans this axis).
      chunk: chunk length for chunked backends (parallel scan, streaming);
        a multiple of 32 for the streaming backends' packed survivor ring.
      stream_depth: truncated-traceback depth for the streaming backend
        (None = the textbook 5*K).
      streaming: a live session context — the caller consumes bits a fixed
        lag behind the channel, so the planner must pick a windowed backend.
      tiles: time-tile count for the ``tiled`` backend (None = the planner
        picks one from predicted costs, or kernels/tiling.default_tiles).
      tile_overlap: per-tile warm-up steps for the ``tiled`` backend.  None
        (the default) and any value >= the truncation depth 5·K select the
        exact min-plus seam resolution (bit-exact); smaller values select
        the cheaper truncated warm-up approximation.
      interpret: force Pallas interpret mode (None = auto: interpret off-TPU).
      telemetry: the caller's repro.obs Telemetry, for backends that record
        inside themselves (``turbo``: its iteration spans and counters);
        ``decode(telemetry=...)`` puts it here.  None records nothing.  An
        observer, not part of how to decode: left out of equality and hash.
    """

    mesh: Optional[object] = None
    mesh_axis: str = "model"
    batch_axis: str = "data"
    chunk: int = 64
    stream_depth: Optional[int] = None
    streaming: bool = False
    tiles: Optional[int] = None
    tile_overlap: Optional[int] = None
    interpret: Optional[bool] = None
    telemetry: Optional[Any] = dataclasses.field(default=None, compare=False)


@dataclasses.dataclass(frozen=True)
class DecodeRequest:
    """One decode job.  Provide ``received`` (channel output, shaped
    (B, T, n_out)) or ``bm_tables`` ((B, T, n_symbols), already built)."""

    spec: CodecSpec
    received: Optional[jnp.ndarray] = None
    bm_tables: Optional[jnp.ndarray] = None

    def shape(self):
        """(B, T) problem shape for the planner — derivable from either
        input form without building branch metrics."""
        src = self.bm_tables if self.bm_tables is not None else self.received
        if src is None:
            raise ValueError("DecodeRequest needs received or bm_tables")
        return src.shape[:2]

    def metrics(self) -> jnp.ndarray:
        """Branch-metric tables for this request (built from ``received``
        through the spec unless precomputed tables were handed in)."""
        if self.bm_tables is not None:
            return self.bm_tables
        if self.received is None:
            raise ValueError("DecodeRequest needs received or bm_tables")
        return self.spec.branch_metrics(self.received)


@dataclasses.dataclass
class DecodeResult:
    """What every backend returns, in one normalized shape.

    Attributes:
      bits: (B, T) decoded input bits, *including* flush bits when the spec
        is terminated — ``info_bits`` strips them.
      path_metric: (B,) winning path metric (minimized).
      spec: the CodecSpec that was decoded.
      plan: the DecodePlan that chose the backend (filled by plan.execute).
      diagnostics: per-backend extras (backend name, chunking, depth, ...).
    """

    bits: jnp.ndarray
    path_metric: jnp.ndarray
    spec: CodecSpec
    plan: Optional["DecodePlan"] = None
    diagnostics: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def info_bits(self) -> jnp.ndarray:
        """Decoded information bits (flush bits stripped per the spec)."""
        return self.spec.strip_flush(self.bits)
