"""The registered hot-path catalog: what the jaxpr contract lint traces.

Every decoder in the registry maps to exactly ONE catalog entry that knows
how to build a traceable callable for its hot loop plus the :class:`Contract`
that loop must satisfy:

  * the block backends (sequential / parallel / fused_packed / tiled /
    bcjr) trace their registry entry directly on a small abstract
    workload;
  * the scheduler-driven backends (streaming, sharded_stream) are Python
    orchestration around a jitted tick — the tick body IS the hot path, so
    the catalog traces ``stream_step`` / ``make_sharded_stream_step``
    (the shard_map variant, device counters on: the richest tick we ship);
  * seqparallel traces under a unit ``data`` mesh with its seam-gather
    collectives explicitly allowlisted — everything else is comms-free;
  * turbo's Python-level iteration loop carries host-side early-exit
    bookkeeping, so its catalog entry traces the jitted single-iteration
    SISO pass (two BCJR kernel launches + extrinsic exchange), which is
    where all its device time goes.

``check_hot_paths()`` is the CI entry: it asserts the catalog covers every
registered decoder (a new backend without a contract fails the build) and
returns a per-path report of equation counts and violations.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.jaxpr_lint import Contract, ContractViolation, trace_contract

#: outputs of a block decode: (bits, path_metric)
_BLOCK_OUTPUTS = 2
#: outputs of the plain tick: (pm, ring, committed_bits, offset_delta)
_TICK_OUTPUTS = 4


@dataclasses.dataclass(frozen=True)
class HotPath:
    """One traceable hot path: its backend, its contract, and a builder
    returning ``(fn, args)`` ready for ``jax.make_jaxpr``."""

    name: str
    backend: str               # the registry entry this path covers
    contract: Contract
    build: Callable[[], Tuple[Callable, Sequence]]
    summary: str = ""


def _unit_mesh(axis: str = "data"):
    from repro.parallel.mesh import make_mesh

    return make_mesh((1,), (axis,), devices=jax.devices()[:1])


def _conv_spec():
    from repro.configs.paper_viterbi import DECODE_SPEC

    return DECODE_SPEC


def _rsc_spec():
    from repro.decode import CodecSpec
    from repro.siso import RSC_K4_LTE

    return CodecSpec(code=RSC_K4_LTE, metric="soft", terminated=False)


def _block_builder(backend: str, B: int = 2, T: int = 64):
    """Registry backend on an abstract (B, T, M) bm table, interpret mode
    resolved ONCE up front (the pinning rule the repo-rule linter enforces
    at call sites)."""

    def build():
        from repro.decode import DecodeContext, get_decoder
        from repro.kernels.common import resolve_interpret

        spec = _conv_spec()
        ctx = DecodeContext(interpret=resolve_interpret(None), chunk=32)
        dec = get_decoder(backend)
        bm = jax.ShapeDtypeStruct((B, T, 2 ** spec.code.n_out), jnp.float32)

        def fn(tables):
            res = dec(spec, tables, ctx=ctx)
            return res.bits, res.path_metric

        return fn, (bm,)

    return build


def _seqparallel_builder():
    def build():
        from repro.decode import DecodeContext, get_decoder
        from repro.kernels.common import resolve_interpret

        spec = _conv_spec()
        mesh = _unit_mesh()
        ctx = DecodeContext(
            interpret=resolve_interpret(None), mesh=mesh, mesh_axis="data"
        )
        dec = get_decoder("seqparallel")
        bm = jax.ShapeDtypeStruct((2, 64, 2 ** spec.code.n_out), jnp.float32)

        def fn(tables):
            res = dec(spec, tables, ctx=ctx)
            return res.bits, res.path_metric

        return fn, (bm,)

    return build


def _stream_tick_builder(chunk: int = 32):
    """The single-device tick body behind sessions and the scheduler
    (streaming backend), as the stream cells run it: one packed
    ``stream_step`` on raw received features with folded metric weights,
    over a uint32 (R/32, B, S) survivor ring."""

    def build():
        from repro.kernels.common import PACK_BITS, resolve_interpret
        from repro.stream import window as w

        spec = _conv_spec()
        code = spec.code
        interpret = resolve_interpret(None)
        _, depth, plan, weights = w.resolve_stream_backend(
            spec, chunk, w.default_depth(code), w.PACKED_BACKEND, "received"
        )
        B, R = 4, depth + chunk
        pm = jax.ShapeDtypeStruct((B, code.n_states), jnp.float32)
        ring = jax.ShapeDtypeStruct((R // PACK_BITS, B, code.n_states), jnp.uint32)
        feats = jax.ShapeDtypeStruct((B, chunk, plan.n_features), jnp.float32)
        active = jax.ShapeDtypeStruct((B,), jnp.bool_)

        def fn(pm, ring, feats, active):
            state, bits, delta = w.stream_step(
                code, w.StreamState(pm=pm, ring=ring), feats, weights=weights,
                active=active, backend=w.PACKED_BACKEND, interpret=interpret,
            )
            return state.pm, state.ring, bits, delta

        return fn, (pm, ring, feats, active)

    return build


def _sharded_tick_builder(chunk: int = 32):
    """The shard_map tick (sharded_stream backend) with device counters on —
    the richest per-tick computation we ship, and the one whose comms-free
    guarantee the multi-device scaling depends on."""

    def build():
        from repro.kernels.common import PACK_BITS, resolve_interpret
        from repro.stream import window as w

        spec = _conv_spec()
        code = spec.code
        mesh = _unit_mesh()
        tick = w.make_sharded_stream_step(
            code, mesh, "data", chunk=chunk, backend=w.PACKED_BACKEND,
            interpret=resolve_interpret(None), device_metrics=True,
        )
        B = 4
        depth = w.packed_depth(w.default_depth(code))
        R = depth + chunk
        arena = jax.ShapeDtypeStruct((1, 4 * chunk, 2 ** code.n_out), jnp.float32)
        idx = jax.ShapeDtypeStruct((B, chunk), jnp.int32)
        active = jax.ShapeDtypeStruct((B,), jnp.bool_)
        pm = jax.ShapeDtypeStruct((B, code.n_states), jnp.float32)
        ring = jax.ShapeDtypeStruct((R // PACK_BITS, B, code.n_states), jnp.uint32)
        ctr_i = jax.ShapeDtypeStruct((B,), jnp.int32)
        ctr_f = jax.ShapeDtypeStruct((B,), jnp.float32)
        counters = w.DeviceCounters(
            ticks=ctr_i, starved_ticks=ctr_i, merge_depth_last=ctr_i,
            merge_depth_sum=ctr_f, merge_depth_max=ctr_i, renorm_sum=ctr_f,
        )

        def fn(arena, idx, active, pm, ring, *ctr):
            state, bits, delta, out_ctr = tick(
                arena, idx, active, w.StreamState(pm=pm, ring=ring),
                w.DeviceCounters(*ctr),
            )
            return (state.pm, state.ring, bits, delta) + tuple(out_ctr)

        return fn, (arena, idx, active, pm, ring) + tuple(counters)

    return build


def _bcjr_builder(B: int = 2, N: int = 64):
    def build():
        from repro.decode import DecodeContext, get_decoder
        from repro.kernels.common import resolve_interpret

        spec = _rsc_spec()
        ctx = DecodeContext(interpret=resolve_interpret(None))
        dec = get_decoder("bcjr")
        llr = jax.ShapeDtypeStruct((B, N, 1 + spec.code.n_parity), jnp.float32)

        def fn(llr_coded):
            res = dec(spec, llr_coded, ctx=ctx)
            return res.bits, res.path_metric

        return fn, (llr,)

    return build


def _turbo_iteration_builder(B: int = 2):
    def build():
        from repro.kernels.common import resolve_interpret
        from repro.siso import QPPInterleaver, RSC_K4_LTE, TurboSpec
        from repro.siso.turbo import _iteration_fn

        spec = TurboSpec(code=RSC_K4_LTE, interleaver=QPPInterleaver(64, 7, 16))
        step = _iteration_fn(spec, resolve_interpret(None))
        N = spec.block_len
        llrs = jax.ShapeDtypeStruct((B, N, spec.n_streams), jnp.float32)
        le2 = jax.ShapeDtypeStruct((B, N), jnp.float32)
        prev = jax.ShapeDtypeStruct((B, N), jnp.int32)
        done = jax.ShapeDtypeStruct((B,), jnp.bool_)
        return step, (llrs, le2, prev, done)

    return build


def _contract(name: str, **kw) -> Contract:
    return Contract(name=name, **kw)


def hot_path_catalog() -> Tuple[HotPath, ...]:
    """One entry per registered decoder.  Adding a backend without extending
    this catalog fails ``check_hot_paths`` (and the CI static-analysis job)."""
    comms_free = dict(allowed_collectives=frozenset())
    return (
        HotPath(
            name="sequential", backend="sequential",
            contract=_contract("sequential", max_outputs=_BLOCK_OUTPUTS,
                               **comms_free),
            build=_block_builder("sequential"),
            summary="lax.scan oracle block decode",
        ),
        HotPath(
            name="parallel", backend="parallel",
            contract=_contract("parallel", max_outputs=_BLOCK_OUTPUTS,
                               **comms_free),
            build=_block_builder("parallel"),
            summary="(min,+) associative-scan block decode",
        ),
        HotPath(
            name="fused_packed", backend="fused_packed",
            contract=_contract("fused_packed", max_outputs=_BLOCK_OUTPUTS,
                               **comms_free),
            build=_block_builder("fused_packed"),
            summary="packed-survivor Pallas pipeline",
        ),
        HotPath(
            name="tiled", backend="tiled",
            contract=_contract("tiled", max_outputs=_BLOCK_OUTPUTS,
                               **comms_free),
            build=_block_builder("tiled", T=128),
            summary="time-parallel tiled decode, exact min-plus seams",
        ),
        HotPath(
            name="seqparallel", backend="seqparallel",
            # the ONE path allowed to communicate: it gathers per-chunk
            # (S, S) transfer maps across the time shards — tiny, T-independent
            contract=_contract(
                "seqparallel", max_outputs=_BLOCK_OUTPUTS,
                allowed_collectives=frozenset({"all_gather", "psum"}),
            ),
            build=_seqparallel_builder(),
            summary="shard_map sequence-parallel decode (seam gather)",
        ),
        HotPath(
            name="stream_tick", backend="streaming",
            contract=_contract("stream_tick", max_outputs=_TICK_OUTPUTS,
                               **comms_free),
            build=_stream_tick_builder(),
            summary="single-device packed tick on received symbols",
        ),
        HotPath(
            name="sharded_stream_tick", backend="sharded_stream",
            # comms-free by construction: slots are independent streams, so
            # the shard_map body must contain ZERO collectives
            contract=_contract(
                "sharded_stream_tick",
                max_outputs=_TICK_OUTPUTS + 6,  # + DeviceCounters leaves
                **comms_free,
            ),
            build=_sharded_tick_builder(),
            summary="sharded shard_map tick, device counters on",
        ),
        HotPath(
            name="bcjr", backend="bcjr",
            contract=_contract("bcjr", max_outputs=_BLOCK_OUTPUTS,
                               **comms_free),
            build=_bcjr_builder(),
            summary="max-log-MAP BCJR kernel pair (alpha scan + beta/LLR)",
        ),
        HotPath(
            name="turbo_iteration", backend="turbo",
            # (le2, bits, llr, done, agree) from the jitted iteration
            contract=_contract("turbo_iteration", max_outputs=5, **comms_free),
            build=_turbo_iteration_builder(),
            summary="jitted turbo iteration (2 BCJR SISO passes)",
        ),
    )


def check_hot_paths(
    catalog: Tuple[HotPath, ...] = None,
) -> Dict[str, Dict[str, object]]:
    """Trace every catalog entry and check its contract.

    Returns {path name: {backend, equations, violations: [...], summary}}.
    Raises AssertionError if the catalog does not cover the full decoder
    registry — tracing "every backend" must mean every backend."""
    from repro.decode import list_decoders

    paths = hot_path_catalog() if catalog is None else catalog
    covered = {p.backend for p in paths}
    registered = set(list_decoders())
    assert covered == registered, (
        f"hot-path catalog out of sync with the registry: "
        f"missing {sorted(registered - covered)}, "
        f"stale {sorted(covered - registered)}"
    )
    report: Dict[str, Dict[str, object]] = {}
    for p in paths:
        fn, args = p.build()
        closed, violations = trace_contract(fn, args, p.contract)
        report[p.name] = {
            "backend": p.backend,
            "equations": _count_eqns(closed.jaxpr),
            "violations": violations,
            "summary": p.summary,
        }
    return report


def _count_eqns(jaxpr) -> int:
    from repro.analysis.jaxpr_lint import _sub_jaxprs

    n = len(jaxpr.eqns)
    for eqn in jaxpr.eqns:
        for param in eqn.params.values():
            for sub in _sub_jaxprs(param):
                n += _count_eqns(sub)
    return n


def flatten_violations(
    report: Dict[str, Dict[str, object]],
) -> List[ContractViolation]:
    out: List[ContractViolation] = []
    for row in report.values():
        out.extend(row["violations"])
    return out
