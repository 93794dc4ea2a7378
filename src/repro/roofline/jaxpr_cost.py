"""Trip-count-aware cost accounting at the jaxpr level.

XLA's ``compiled.cost_analysis()`` counts while-loop bodies ONCE — with
scan-over-layers and microbatch accumulation that undercounts FLOPs by the
product of trip counts (we verified: adding microbatches=4 divided reported
FLOPs by 4).  This module walks the closed jaxpr of the step function and
counts:

  flops — dot_general counted exactly (2·M·N·K·batch); elementwise ops at
          1 flop/element; scan bodies multiplied by their length; remat
          (checkpoint) recompute included (its jaxpr is inlined by recursion)
  bytes — per-equation output bytes + input bytes, EXCLUDING pure layout ops
          (reshape/transpose/broadcast/convert/slice), a fusion-blind upper
          bound on HBM traffic, with the same trip-count multiplication.

Numbers are GLOBAL (pre-SPMD); divide by chip count for per-device terms
(valid when every large tensor is sharded, which the dry-run shardings
ensure).  Recorded next to the raw XLA numbers in every dry-run cell.
"""
from __future__ import annotations

from typing import Dict

import jax
import numpy as np

# layout-only ops: no flops, no HBM traffic of their own after fusion
_LAYOUT_PRIMS = {
    "reshape", "transpose", "broadcast_in_dim", "convert_element_type",
    "squeeze", "slice", "rev", "bitcast_convert_type", "copy",
    "stop_gradient", "dynamic_slice", "dynamic_update_slice",
    "gather", "concatenate", "pad", "iota",
    # Pallas ref reads/writes move data within on-chip memory, not HBM
    "get", "swap", "program_id",
}
# control/bookkeeping ops: skip entirely
_SKIP_PRIMS = {
    "add_any", "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
}


def _aval_bytes(aval) -> float:
    try:
        return float(np.prod(aval.shape)) * np.dtype(aval.dtype).itemsize
    except Exception:
        return 0.0


def _aval_elems(aval) -> float:
    try:
        return float(np.prod(aval.shape))
    except Exception:
        return 0.0


def _dot_flops(eqn) -> float:
    ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
    a, b = eqn.invars[0].aval, eqn.invars[1].aval
    batch = np.prod([a.shape[i] for i in lb]) if lb else 1.0
    contract = np.prod([a.shape[i] for i in lc]) if lc else 1.0
    m = np.prod([a.shape[i] for i in range(a.ndim) if i not in lc and i not in lb]) or 1.0
    n = np.prod([b.shape[i] for i in range(b.ndim) if i not in rc and i not in rb]) or 1.0
    return 2.0 * float(batch) * float(m) * float(n) * float(contract)


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    # 2 * output elems * (reduction window = rhs elems / out-features)
    feat = rhs.shape[eqn.params["dimension_numbers"].rhs_spec[0]] \
        if hasattr(eqn.params.get("dimension_numbers"), "rhs_spec") else 1
    red = float(np.prod(rhs.shape)) / max(1, feat)
    return 2.0 * _aval_elems(out) * red


def count_jaxpr(jaxpr, mult: float = 1.0) -> Dict[str, float]:
    flops = 0.0
    byts = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _SKIP_PRIMS:
            continue
        if name == "dot_general":
            flops += mult * _dot_flops(eqn)
            byts += mult * (sum(_aval_bytes(v.aval) for v in eqn.invars)
                            + sum(_aval_bytes(v.aval) for v in eqn.outvars))
            continue
        if name in ("conv_general_dilated",):
            flops += mult * _conv_flops(eqn)
            byts += mult * (sum(_aval_bytes(v.aval) for v in eqn.invars)
                            + sum(_aval_bytes(v.aval) for v in eqn.outvars))
            continue
        if name == "scan":
            inner = count_jaxpr(eqn.params["jaxpr"].jaxpr, mult * eqn.params["length"])
            flops += inner["flops"]
            byts += inner["bytes"]
            continue
        if name == "while":
            # raw while: unknown trips -> count once (we never emit raw whiles)
            inner = count_jaxpr(eqn.params["body_jaxpr"].jaxpr, mult)
            flops += inner["flops"]
            byts += inner["bytes"]
            continue
        if name == "cond":
            branches = [count_jaxpr(b.jaxpr, mult) for b in eqn.params["branches"]]
            flops += max(b["flops"] for b in branches)
            byts += max(b["bytes"] for b in branches)
            continue
        if name in ("jit", "remat2", "closed_call"):
            sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if sub is not None:
                inner = count_jaxpr(getattr(sub, "jaxpr", sub), mult)
                flops += inner["flops"]
                byts += inner["bytes"]
            continue
        if name == "pallas_call":
            # the kernel body's flops once per grid step; its HBM traffic
            # is what it writes out (its reads ride the same blocks)
            steps = float(np.prod(eqn.params["grid_mapping"].grid))
            flops += count_jaxpr(eqn.params["jaxpr"], mult * steps)["flops"]
            byts += mult * sum(_aval_bytes(v.aval) for v in eqn.outvars)
            continue
        # default: elementwise-ish op
        out_elems = sum(_aval_elems(v.aval) for v in eqn.outvars)
        if name not in _LAYOUT_PRIMS:
            flops += mult * out_elems
            byts += mult * (sum(_aval_bytes(v.aval) for v in eqn.invars)
                            + sum(_aval_bytes(v.aval) for v in eqn.outvars))
    return {"flops": flops, "bytes": byts}


def count_fn_costs(fn, *args, **kwargs) -> Dict[str, float]:
    """Trace ``fn`` abstractly and count global trip-aware costs."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    out = count_jaxpr(closed.jaxpr)
    # count reading every input once (params, caches, batch)
    out["input_bytes"] = sum(_aval_bytes(v.aval) for v in closed.jaxpr.invars)
    return out
