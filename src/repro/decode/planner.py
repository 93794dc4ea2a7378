"""Shape-aware decode planner.

``plan_decode(spec, shape)`` picks a backend from the code family, the
problem shape (B, T, S), the device kind, and mesh presence — replacing the
mode string the old serving head forced onto every caller.  The choice is a
pure function of its inputs (deterministic), can always be overridden with
``backend=...``, and every plan carries an ``explain()`` string for
debuggability.

Selection policy (each branch has a planner unit test):

  * explicit ``backend=`` override wins (validated against capabilities);
  * non-Viterbi code families route first — a TurboSpec to ``turbo``, an
    RSC CodecSpec to ``bcjr`` — so family dispatch stays a registry rule
    and the Viterbi shape rules below are untouched by new families;
  * a streaming context (``ctx.streaming``) with a multi-device ``data``
    (``ctx.batch_axis``) mesh axis -> ``sharded_stream`` (one scheduler
    spanning the axis); otherwise -> ``streaming``;
  * long blocks (T >= LONG_BLOCK_T) -> ``seqparallel`` when a mesh is
    present and T divides across it; without a usable mesh the rule
    ``long-conv-tiled`` routes to the time-parallel ``tiled`` backend and
    picks the tile count P by scoring ``predicted_costs()`` over candidate
    counts (``_pick_tiles``; ``parallel`` remains the fallback for
    trellises past the tiled VMEM cap).  A picked P=1 is planned as
    ``fused_packed``, the pipeline one tile runs;
  * everything else (short batched blocks) -> ``fused_packed`` (bit-packed
    survivors + on-device traceback; in-kernel branch metrics when the
    request carries raw symbols), falling back to ``parallel`` for
    trellises too large for the VMEM-resident scan.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import numpy as np

from repro.core.trellis import ConvCode
from repro.decode import backends as _backends  # noqa: F401  (populates the registry)
from repro.decode.registry import RegisteredDecoder, get_decoder
from repro.decode.request import DecodeContext, DecodeRequest, DecodeResult
from repro.decode.spec import CodecSpec, spec_family
from repro.obs import Telemetry, Tracer, span
from repro.siso.turbo import TurboSpec

#: family -> SISO backend the planner routes non-Viterbi specs to.
FAMILY_BACKENDS = {"rsc": "bcjr", "turbo": "turbo"}

#: Above this many trellis steps the log-depth chunk decoders beat the
#: sequential-scan forward pass (the scan's T-deep dependency chain stops
#: fitting latency budgets long before memory runs out).
LONG_BLOCK_T = 1024


@dataclasses.dataclass(frozen=True)
class DecodePlan:
    """A resolved decode: spec + shape + backend choice + why."""

    spec: CodecSpec
    backend: str
    batch: int
    steps: int
    ctx: DecodeContext
    reason: str
    device_kind: str

    @property
    def decoder(self) -> RegisteredDecoder:
        return get_decoder(self.backend)

    def predicted_costs(self) -> Optional[dict]:
        """Roofline-predicted flops/bytes of the planned decode: trace the
        backend on zeros of the planned shape and walk the jaxpr
        (roofline.jaxpr_cost, trip-count aware).  Returns {"flops", "bytes",
        "input_bytes"}, or None for backends whose host-side orchestration
        reads traced values (the stream schedulers' tick loops, turbo's
        early exit).  Any other tracing failure propagates."""
        import jax.numpy as jnp

        from repro.roofline.jaxpr_cost import count_fn_costs

        M = self.spec.table_width
        bm = jnp.zeros((self.batch, self.steps, M), dtype=jnp.float32)
        try:
            return count_fn_costs(
                lambda t: self.decoder(self.spec, t, ctx=self.ctx).bits, bm
            )
        except (jax.errors.ConcretizationTypeError,
                jax.errors.TracerArrayConversionError):
            return None

    def explain(self, costs: bool = False) -> str:
        """Human-readable plan summary; ``costs=True`` appends the roofline
        prediction (predicted flops/bytes and arithmetic intensity) when the
        backend is traceable."""
        caps = self.decoder.capabilities
        text = (
            f"plan: backend={self.backend!r} for shape (B={self.batch}, T={self.steps}, "
            f"S={self.spec.code.n_states}) on {self.device_kind}\n"
            f"  spec: {self.spec.describe()}\n"
            f"  why:  {self.reason}\n"
            f"  caps: mesh={caps.supports_mesh} streaming={caps.supports_streaming} "
            f"max_states={caps.max_states} needs_terminated={caps.needs_terminated}"
        )
        if costs:
            c = self.predicted_costs()
            if c is None:
                text += "\n  cost: untraceable (host-side orchestration backend)"
            else:
                intensity = c["flops"] / c["bytes"] if c["bytes"] else 0.0
                text += (
                    f"\n  cost: ~{c['flops']:.3g} flops, ~{c['bytes']:.3g} bytes "
                    f"moved ({intensity:.2f} flops/byte), "
                    f"{c['input_bytes']:.3g} input bytes"
                )
        return text

    def execute(self, bm_tables) -> DecodeResult:
        """Run the planned backend on (B, T, M) branch-metric tables."""
        result = self.decoder(self.spec, bm_tables, ctx=self.ctx)
        result.plan = self
        return result

    def execute_request(
        self, request: "DecodeRequest", tracer: Optional[Tracer] = None
    ) -> DecodeResult:
        """Run the plan on a DecodeRequest, routing raw channel output to
        the backend's in-kernel-metric entry when it has one — the bm table
        is only materialized for backends that need it.  Precomputed
        ``bm_tables`` take precedence over ``received`` (the DecodeRequest
        contract), so callers with custom tables never get them recomputed.

        An attached ``tracer`` records ``decode.check`` (the host copy and
        finiteness test of raw symbols, on that path only) and
        ``decode.dispatch`` (the backend call up to its return, not the
        device work)."""
        if (
            request.bm_tables is None
            and request.received is not None
            and self.decoder.from_received is not None
        ):
            with span(tracer, "decode.check"):
                received = np.asarray(request.received)
                if not np.isfinite(received).all():
                    # the in-kernel metric path skips every host-side table
                    # build where bad values would otherwise surface — guard
                    # here, or a single NaN symbol poisons the whole decode
                    bad = int(np.count_nonzero(~np.isfinite(received)))
                    raise ValueError(
                        f"non-finite input: {bad} NaN/Inf value(s) in received "
                        f"symbols {received.shape} — in-kernel branch metrics "
                        "would silently corrupt the path metrics"
                    )
            with span(tracer, "decode.dispatch"):
                result = self.decoder.decode_received(
                    self.spec, request.received, ctx=self.ctx
                )
            result.plan = self
            return result
        with span(tracer, "decode.dispatch"):
            return self.execute(request.metrics())


@functools.lru_cache(maxsize=128)
def _pick_tiles(
    spec: CodecSpec, B: int, T: int, device_kind: str, chunk: int,
    interpret: Optional[bool],
) -> Tuple[int, str]:
    """Tile count for a long-block tiled decode, chosen from the roofline
    cost model: trace the tiled backend once per candidate P (the same
    ``predicted_costs()`` surface ``explain(costs=True)`` reports) and take
    the argmin of predicted (flops + bytes) / P — the critical path when the
    P tiles run time-parallel on the lane axis.  The tiled backend is fully
    traceable, so a candidate that fails to trace is an error, never a
    quietly different plan.  Cached per (spec, shape, device kind): planning
    stays cheap and deterministic."""
    from repro.kernels.tiling import MIN_TILE_CORE, default_tiles

    S = spec.code.n_states
    cap = max(1, T // MIN_TILE_CORE)
    candidates = sorted(
        {p for p in (1, 2, 4, 8, 16, 32) if p <= cap} | {default_tiles(B, T, S)}
    )
    scored = {}
    for p in candidates:
        plan = DecodePlan(
            spec=spec, backend="tiled", batch=B, steps=T,
            ctx=DecodeContext(chunk=chunk, interpret=interpret, tiles=p),
            reason="tile-count candidate", device_kind=device_kind,
        )
        c = plan.predicted_costs()
        if c is None:
            raise RuntimeError(f"tiled decode at P={p} did not trace:\n{plan.explain()}")
        scored[p] = (c["flops"] + c["bytes"]) / p
    best = min(scored, key=scored.get)
    return best, (
        f"argmin of predicted (flops+bytes)/P over P in {list(scored)} "
        "(roofline predicted_costs)"
    )


def _normalize_shape(shape: Sequence[int]) -> Tuple[int, int]:
    """Accept (B, T) or a full (B, T, M) bm-table shape."""
    if len(shape) == 2:
        return int(shape[0]), int(shape[1])
    if len(shape) == 3:
        return int(shape[0]), int(shape[1])
    raise ValueError(f"shape must be (B, T) or (B, T, M), got {tuple(shape)}")


def _normalize_spec(spec):
    """Promote a bare ConvCode to a CodecSpec; family specs with their own
    encode/metric surface (TurboSpec) pass through untouched."""
    if isinstance(spec, (CodecSpec, ConvCode)):
        return CodecSpec.of(spec)
    return spec


def _validate(decoder: RegisteredDecoder, spec, ctx: DecodeContext) -> None:
    caps = decoder.capabilities
    fam = spec_family(spec)
    if caps.family != fam:
        raise ValueError(
            f"backend {decoder.name!r} decodes the {caps.family!r} code family, "
            f"spec is {fam!r} — pick a backend registered for that family"
        )
    S = spec.code.n_states
    if caps.requires_mesh and ctx.mesh is None:
        raise ValueError(f"backend {decoder.name!r} requires a mesh (pass mesh=/ctx.mesh)")
    if caps.max_states is not None and S > caps.max_states:
        raise ValueError(
            f"backend {decoder.name!r} handles at most {caps.max_states} states, "
            f"spec has {S}"
        )
    if caps.needs_terminated and not spec.terminated:
        raise ValueError(f"backend {decoder.name!r} only decodes terminated trellises")
    if (caps.sharded_stream and ctx.mesh is not None
            and not int(ctx.mesh.shape.get(ctx.batch_axis, 0))):
        raise ValueError(
            f"backend {decoder.name!r} shards over mesh axis "
            f"{ctx.batch_axis!r}, which {ctx.mesh} lacks"
        )


def plan_decode(
    spec: Union[CodecSpec, ConvCode, TurboSpec],
    shape: Sequence[int],
    *,
    mesh: Optional[object] = None,
    backend: Optional[str] = None,
    ctx: Optional[DecodeContext] = None,
) -> DecodePlan:
    """Pick (or validate) a decode backend for a (B, T[, M]) problem.

    Args:
      spec: the CodecSpec (a bare ConvCode is promoted with defaults).
      shape: (B, T) or the full (B, T, M) branch-metric table shape.
      mesh: convenience override for ``ctx.mesh``.
      backend: explicit registry name — skips auto-selection (still
        capability-validated).
      ctx: execution context (chunking, stream depth, streaming flag, ...).

    Returns:
      DecodePlan; ``plan.execute(bm_tables)`` runs it, ``plan.explain()``
      says why.
    """
    spec = _normalize_spec(spec)
    B, T = _normalize_shape(shape)
    ctx = ctx or DecodeContext()
    if mesh is not None:
        ctx = dataclasses.replace(ctx, mesh=mesh)
    device_kind = jax.devices()[0].device_kind
    S = spec.code.n_states

    fam = spec_family(spec)
    if fam == "turbo" and T != spec.n_steps(spec.block_len):
        raise ValueError(
            f"a {spec.describe()} block is {spec.n_steps(spec.block_len)} rows "
            f"(K={spec.block_len} + {spec.n_tail_rows} tail rows), got T={T}"
        )
    if backend is not None:
        choice, reason = backend, f"explicit backend={backend!r} override"
    elif fam in FAMILY_BACKENDS:
        choice = FAMILY_BACKENDS[fam]
        reason = (
            f"code family {fam!r} -> registry family rule routes to "
            f"{choice!r} (shape rules below select only among 'conv'/Viterbi "
            "backends)"
        )
    elif ctx.streaming:
        n_data = (
            int(ctx.mesh.shape.get(ctx.batch_axis, 0)) if ctx.mesh is not None else 0
        )
        sharded_max = get_decoder("sharded_stream").capabilities.max_states
        if n_data > 1 and (sharded_max is None or S <= sharded_max):
            choice = "sharded_stream"
            reason = (
                f"session context with a multi-device mesh "
                f"({ctx.batch_axis}={n_data}) -> one scheduler spanning the "
                f"{ctx.batch_axis!r} axis (slot table sharded per device)"
            )
        elif n_data > 1:
            choice = "streaming"
            reason = (
                f"session context, {ctx.batch_axis}={n_data} mesh, but S={S} "
                f"exceeds the sharded hot-loop VMEM cap ({sharded_max}) -> "
                "single-device windowed decode"
            )
        else:
            choice = "streaming"
            reason = "session context given -> windowed online decode (O(depth+chunk) memory)"
    elif T >= LONG_BLOCK_T:
        n = int(ctx.mesh.shape.get(ctx.mesh_axis, 0)) if ctx.mesh is not None else 0
        if n and T % n == 0:
            choice = "seqparallel"
            reason = (
                f"long block (T={T} >= {LONG_BLOCK_T}) with a mesh "
                f"({ctx.mesh_axis}={n}, T divisible) -> shard the time axis"
            )
        else:
            if ctx.mesh is None:
                why_not = "no mesh"
            elif not n:
                why_not = f"mesh lacks axis {ctx.mesh_axis!r}"
            else:
                why_not = f"T % {ctx.mesh_axis}={n} != 0"
            tiled_max = get_decoder("tiled").capabilities.max_states
            if tiled_max is not None and S > tiled_max:
                choice = "parallel"
                reason = (
                    f"long block (T={T} >= {LONG_BLOCK_T}), {why_not}, and "
                    f"S={S} exceeds the tiled VMEM cap ({tiled_max}) -> "
                    "single-device (min,+) associative scan"
                )
            else:
                choice = "tiled"
                pinned = ctx.tiles is not None
                if pinned:
                    tiles, how = int(ctx.tiles), "ctx.tiles pinned by caller"
                else:
                    tiles, how = _pick_tiles(
                        spec, B, T, device_kind, ctx.chunk, ctx.interpret
                    )
                    ctx = dataclasses.replace(ctx, tiles=tiles)
                reason = (
                    f"long block (T={T} >= {LONG_BLOCK_T}), {why_not} -> "
                    f"rule 'long-conv-tiled': time-parallel tiled decode, "
                    f"P={tiles} ({how})"
                )
                if tiles == 1 and not pinned:
                    # one tile IS the plain packed pipeline: name the
                    # backend that does the work
                    choice = "fused_packed"
                    reason += " -> one tile is the fused_packed pipeline"
    else:
        fused_max = get_decoder("fused_packed").capabilities.max_states
        if fused_max is not None and S > fused_max:
            choice = "parallel"
            reason = (
                f"short block but S={S} exceeds the fused VMEM budget "
                f"({fused_max}) -> chunked scan"
            )
        else:
            choice = "fused_packed"
            reason = (
                f"short batched block (T={T} < {LONG_BLOCK_T}) -> "
                "VMEM-resident Pallas scan with packed survivors + "
                "on-device traceback"
            )

    decoder = get_decoder(choice)
    _validate(decoder, spec, ctx)
    return DecodePlan(
        spec=spec, backend=choice, batch=B, steps=T, ctx=ctx,
        reason=reason, device_kind=device_kind,
    )


def decode(
    request: Union[DecodeRequest, CodecSpec],
    received=None,
    *,
    mesh: Optional[object] = None,
    backend: Optional[str] = None,
    ctx: Optional[DecodeContext] = None,
    telemetry: Optional[Telemetry] = None,
) -> DecodeResult:
    """One-shot decode: plan + execute.

    Either ``decode(DecodeRequest(spec, received=rx))`` or the shorthand
    ``decode(spec, rx)``.  Returns a DecodeResult whose ``info_bits`` has
    flush bits stripped per the spec.  When the request carries raw channel
    output and the planned backend computes metrics in-kernel
    (``accepts_received``), the symbols go straight to the kernel — no
    (B, T, M) bm table is built.

    ``telemetry`` with a tracer records a ``decode`` span around the call
    and its phases inside it: ``decode.plan``, ``decode.check`` (raw-symbol
    path only) and ``decode.dispatch``.  It also reaches the backend as
    ``ctx.telemetry`` (the ``turbo`` loop records its iterations there).
    ``None`` (default) traces nothing; the bits are the same either way.
    """
    tracer = None if telemetry is None else telemetry.tracer
    with span(tracer, "decode"):
        with span(tracer, "decode.plan"):
            if telemetry is not None:
                ctx = dataclasses.replace(ctx or DecodeContext(), telemetry=telemetry)
            if not isinstance(request, DecodeRequest):
                request = DecodeRequest(
                    spec=_normalize_spec(request), received=received
                )
            plan = plan_decode(
                request.spec, request.shape(), mesh=mesh, backend=backend, ctx=ctx
            )
        return plan.execute_request(request, tracer)
