"""Jaxpr-level contract lint for the decode hot paths.

The paper's thesis is that the Viterbi inner loop is a small, precisely
specified contract (the ACS "custom instruction") whose guarantees must not
erode as the system around it grows.  Our jax_pallas equivalents of those
guarantees — no host callbacks inside a jitted hot path, zero cross-shard
collectives in the sharded tick, every path metric staying in the declared
``metric_dtype``, a bounded number of outputs per launch — were previously
enforced only by scattered hand-written spy tests.  This module checks them
mechanically: walk the closed jaxpr of a registered hot path (the same
equation-walking idiom as ``roofline.jaxpr_cost``, which *counts* where this
module *asserts*) and report every equation that violates the declared
:class:`Contract` as a structured :class:`ContractViolation` naming the
primitive and its source line.

Checked properties:

  host callbacks   ``pure_callback`` / ``io_callback`` / ``debug_callback``
                   (and the legacy host_callback bridges) force a host
                   round-trip per launch — forbidden on every hot path.
  collectives      ``psum`` / ``ppermute`` / ``all_gather`` / … are only
                   legal where a contract explicitly allowlists them
                   (seqparallel's seam gather); the sharded streaming tick
                   allows NONE — its speedup depends on a comms-free body.
  dtype policy     no float64 anywhere (a silent x64 leak doubles VMEM and
                   halves lane width), and no floating dtype outside the
                   contract's ``metric_dtype`` + ``extra_float_dtypes`` (the
                   hook the quantized-metric ROADMAP item will use: an int8
                   ACS ships with a contract whose metric_dtype is int8).
  output count     ``max_outputs`` bounds the top-level results a hot path
                   may emit — each output is a device buffer the host may
                   later sync on.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterable, List, Optional, Sequence, Tuple

import jax
import numpy as np

#: primitives that call back into Python from inside a compiled computation
HOST_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "host_callback_call",
})

#: named-axis communication primitives (anything that moves data between
#: shards); a hot path must allowlist every one it legitimately uses.
#: shard_map's replication-rewrite emits ``psum2``/``pbroadcast2`` variants —
#: ``_canonical_prim`` folds those onto the public names so contracts are
#: written (and allowlisted) in user-facing terms.
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmin", "pmax", "pbroadcast", "ppermute", "pgather",
    "all_gather", "all_to_all", "reduce_scatter", "psum_scatter",
})

_PRIM_ALIASES = {"psum2": "psum", "pbroadcast2": "pbroadcast"}


def _canonical_prim(name: str) -> str:
    return _PRIM_ALIASES.get(name, name)


@dataclasses.dataclass(frozen=True)
class Contract:
    """Declarative hot-path contract, checked equation-by-equation.

    Attributes:
      name: contract label used in reports (usually the backend name).
      metric_dtype: the one floating dtype the path may compute in; every
        float-dtyped value outside this (plus ``extra_float_dtypes``) is a
        ``dtype`` violation.  float64 is always a violation of its own kind.
      extra_float_dtypes: additional tolerated float dtypes (e.g. a bf16
        accumulator a future quantized backend declares explicitly).
      allowed_collectives: collective primitives this path may emit —
        empty for every comms-free path.
      allow_host_callbacks: opt-out for debug-only paths; no shipped
        contract sets it.
      max_outputs: bound on the top-level jaxpr outputs (None = unbounded).
    """

    name: str
    metric_dtype: str = "float32"
    extra_float_dtypes: Tuple[str, ...] = ()
    allowed_collectives: frozenset = frozenset()
    allow_host_callbacks: bool = False
    max_outputs: Optional[int] = None
    notes: str = ""

    def allowed_floats(self) -> frozenset:
        return frozenset((self.metric_dtype,) + self.extra_float_dtypes)


@dataclasses.dataclass(frozen=True)
class ContractViolation:
    """One broken guarantee: which contract, what kind, where."""

    contract: str
    kind: str        # "host-callback" | "collective" | "float64" | "dtype" | "outputs"
    primitive: str
    detail: str
    where: str       # best-effort "file.py:line (function)" of the equation
    path: str        # nesting of enclosing primitives, e.g. "pjit/shard_map/scan"

    def __str__(self) -> str:
        loc = f" at {self.where}" if self.where else ""
        ctx = f" [{self.path}]" if self.path else ""
        return (
            f"{self.contract}: {self.kind} violation — {self.detail} "
            f"(primitive {self.primitive!r}){loc}{ctx}"
        )


def _source_of(eqn) -> str:
    """Best-effort source line for an equation (private API, so guarded)."""
    try:
        from jax._src import source_info_util

        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return ""


def _sub_jaxprs(value) -> Iterable:
    """Yield every (Closed)Jaxpr reachable from one eqn param value."""
    from jax.extend.core import Jaxpr

    if isinstance(value, Jaxpr):
        yield value
    elif hasattr(value, "jaxpr") and isinstance(getattr(value, "jaxpr"), Jaxpr):
        yield value.jaxpr
    elif isinstance(value, (tuple, list)):
        for item in value:
            yield from _sub_jaxprs(item)


def _eqn_dtypes(eqn):
    for v in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(v, "aval", None)
        dt = getattr(aval, "dtype", None)
        if dt is not None:
            yield np.dtype(dt)


def check_jaxpr(
    jaxpr, contract: Contract, _path: Tuple[str, ...] = ()
) -> List[ContractViolation]:
    """Walk ``jaxpr`` (a Jaxpr or ClosedJaxpr) recursively — the same
    sub-jaxpr recursion as ``roofline.jaxpr_cost.count_jaxpr``, covering
    scan/while/cond bodies, pjit/remat calls, and shard_map — and collect
    every equation that breaks ``contract``."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    out: List[ContractViolation] = []
    allowed_floats = contract.allowed_floats()
    for eqn in inner.eqns:
        name = _canonical_prim(eqn.primitive.name)
        if name in HOST_CALLBACK_PRIMS and not contract.allow_host_callbacks:
            out.append(ContractViolation(
                contract=contract.name, kind="host-callback", primitive=name,
                detail="host callback inside a compiled hot path",
                where=_source_of(eqn), path="/".join(_path),
            ))
        if name in COLLECTIVE_PRIMS and name not in contract.allowed_collectives:
            out.append(ContractViolation(
                contract=contract.name, kind="collective", primitive=name,
                detail="cross-shard collective outside the contract allowlist",
                where=_source_of(eqn), path="/".join(_path),
            ))
        seen = set()
        for dt in _eqn_dtypes(eqn):
            key = str(dt)
            if key in seen:
                continue
            seen.add(key)
            if key == "float64":
                out.append(ContractViolation(
                    contract=contract.name, kind="float64", primitive=name,
                    detail="float64 value leaked into the hot path",
                    where=_source_of(eqn), path="/".join(_path),
                ))
            elif (
                jax.dtypes.issubdtype(dt, np.floating)  # incl. bf16/float8
                and key not in allowed_floats
            ):
                out.append(ContractViolation(
                    contract=contract.name, kind="dtype", primitive=name,
                    detail=(
                        f"{key} value outside the declared metric dtype "
                        f"{contract.metric_dtype!r}"
                    ),
                    where=_source_of(eqn), path="/".join(_path),
                ))
        for param in eqn.params.values():
            for sub in _sub_jaxprs(param):
                out.extend(check_jaxpr(sub, contract, _path + (name,)))
    return out


def trace_contract(
    fn: Callable,
    args: Sequence,
    contract: Contract,
) -> Tuple["jax.extend.core.ClosedJaxpr", List[ContractViolation]]:
    """Trace ``fn(*args)`` abstractly (args may be ShapeDtypeStructs) and
    check the resulting jaxpr against ``contract``.  Returns the closed
    jaxpr (so callers can report equation counts) and the violations."""
    closed = jax.make_jaxpr(fn)(*args)
    violations = check_jaxpr(closed, contract)
    n_out = len(closed.jaxpr.outvars)
    if contract.max_outputs is not None and n_out > contract.max_outputs:
        violations.append(ContractViolation(
            contract=contract.name, kind="outputs", primitive="<jaxpr>",
            detail=f"{n_out} outputs exceed the contract bound "
                   f"{contract.max_outputs}",
            where="", path="",
        ))
    return closed, violations
