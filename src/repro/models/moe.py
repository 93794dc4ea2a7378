"""Mixture-of-Experts with top-k routing and batch-local sort dispatch.

SPMD-friendly by construction: dispatch (sort, capacity, scatter) happens
independently per leading-batch row (vmap), so every intermediate keeps the
``batch`` sharding and GSPMD never has to reshard a global scatter — the
failure mode that made a global-sort dispatch materialize the full (E·C, d)
buffer per device.  Expert weights carry the 'expert' logical axis
(-> 'model' mesh axis); the expert einsum contracts locally because the
dispatch buffer is replicated across 'model' (activations are batch-sharded)
— zero dispatch collectives on the dry-run meshes.

Capacity is per batch row: C = ceil(S·k/E · capacity_factor) (Switch-style
per-shard capacity; overflow tokens drop).  No (T, E, C) one-hot tensor is
ever built: positions-in-expert come from a sorted cummax trick, dispatch is
a batched scatter, combine a batched gather.

Aux losses: switch load-balance loss + router z-loss.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm


def _batch_axes_for(mesh, B: int):
    """Mesh axes the batch dim can shard over (empty tuple -> no shard_map)."""
    if mesh is None:
        return ()
    ba = tuple(a for a in ("pod", "data") if a in mesh.shape)
    if not ba:
        return ()
    dp = 1
    for a in ba:
        dp *= mesh.shape[a]
    return ba if (dp > 0 and B % dp == 0) else ()


def moe_specs(cfg, stack: int) -> Dict[str, Any]:
    d = cfg.d_model
    moe = cfg.moe
    ff = moe.d_expert or cfg.d_ff
    E = moe.n_experts

    def expert_dense(in_d, out_d, in_ax, out_ax):
        shape = (E, in_d, out_d)
        axes = ("expert", in_ax, out_ax)
        if stack:
            shape = (stack,) + shape
            axes = ("layers",) + axes
        return {"kernel": cm.ParamSpec(shape, axes, "normal", 1.0, in_d)}

    p = {
        "router": cm.dense_spec((d,), (E,), ("embed",), ("expert",), stack=stack),
        "gate": expert_dense(d, ff, "embed", "expert_ff"),
        "up": expert_dense(d, ff, "embed", "expert_ff"),
        "down": expert_dense(ff, d, "expert_ff", "embed"),
    }
    if moe.n_shared:
        from repro.models.mlp import mlp_specs

        p["shared"] = mlp_specs(cfg, stack, d_ff=ff * moe.n_shared)
    return p


def _dispatch_row(xt, expert_idx, gate_vals, E: int, C: int, k: int, cd):
    """Per-batch-row dispatch.  xt: (S, d); expert_idx/gate_vals: (S, k).
    Returns (buf (E, C, d), slot (S*k,), tok_sorted (S*k,), keep, gates_sorted).
    """
    S = xt.shape[0]
    flat_e = expert_idx.reshape(-1)  # (S*k,)
    order = jnp.argsort(flat_e, stable=True)  # ties keep token order
    e_sorted = flat_e[order]
    idx = jnp.arange(S * k)
    # position within each expert run: idx - index of the run's first element
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), e_sorted[1:] != e_sorted[:-1]])
    run_start = jax.lax.cummax(jnp.where(is_start, idx, 0))
    pos_in_e = idx - run_start
    keep = pos_in_e < C
    slot = jnp.where(keep, e_sorted * C + pos_in_e, E * C)  # E*C = drop bin
    tok_sorted = order // k
    buf = jnp.zeros((E * C + 1, xt.shape[1]), cd)
    buf = buf.at[slot].set(xt[tok_sorted].astype(cd), mode="drop")
    gates_sorted = gate_vals.reshape(-1)[order]
    return buf[: E * C].reshape(E, C, xt.shape[1]), slot, tok_sorted, keep, gates_sorted


def _combine_row(yb, slot, tok_sorted, gates_sorted, S: int, cd):
    """Inverse of _dispatch_row.  yb: (E, C, d) -> y (S, d)."""
    d = yb.shape[-1]
    yb_flat = jnp.concatenate([yb.reshape(-1, d), jnp.zeros((1, d), cd)], axis=0)
    gathered = yb_flat[slot]  # dropped tokens hit the zero row
    contrib = gathered * gates_sorted[:, None].astype(cd)
    return jnp.zeros((S, d), cd).at[tok_sorted].add(contrib)


def _dispatch_batch(x, expert_idx, gate_vals, E, C, k, cd):
    return jax.vmap(
        lambda xr, er, gr: _dispatch_row(xr, er, gr, E, C, k, cd)
    )(x, expert_idx, gate_vals)


def _combine_batch(yb, slot, tok_sorted, gates_sorted, S, cd):
    return jax.vmap(
        lambda ybr, sl, ts, gs: _combine_row(ybr, sl, ts, gs, S, cd)
    )(yb, slot, tok_sorted, gates_sorted)


def moe_apply(params, cfg, x: jnp.ndarray, mesh=None) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B, S, d) -> (y, aux) with aux = {load_balance_loss, router_z_loss}."""
    cd = jnp.dtype(cfg.compute_dtype)
    moe = cfg.moe
    B, S, d = x.shape
    E, k = moe.n_experts, moe.top_k
    act = cm.activation(cfg.act)

    logits = cm.dense(params["router"], x, "bsd,de->bse", cd).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # (B, S, k)
    if moe.renormalize:
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # per-row capacity; k distinct experts per token guarantee C>=k covers S=1
    C = max(int(S * k / E * moe.capacity_factor) + 1, 1)

    # Dispatch under shard_map over the batch axes when possible: GSPMD has
    # no good sharding for batched sort/scatter and replicates the (E·C, d)
    # buffers otherwise (measured ~68 GB/layer on jamba).  shard_map pins
    # every dispatch intermediate to its batch shard; there are no
    # collectives inside (dispatch is per-row math).
    ba = _batch_axes_for(mesh, B)
    if ba:
        from repro.parallel.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        bspec = ba if len(ba) > 1 else ba[0]
        disp = shard_map(
            lambda xr, er, gr: _dispatch_batch(xr, er, gr, E, C, k, cd),
            mesh=mesh,
            in_specs=(P(bspec), P(bspec), P(bspec)),
            out_specs=(P(bspec), P(bspec), P(bspec), P(bspec), P(bspec)),
        )
        buf, slot, tok_sorted, keep, gates_sorted = disp(x, expert_idx, gate_vals)
    else:
        buf, slot, tok_sorted, keep, gates_sorted = _dispatch_batch(
            x, expert_idx, gate_vals, E, C, k, cd)

    # expert computation: b batch-sharded, e expert(model)-sharded
    g = jnp.einsum("becd,edf->becf", buf, params["gate"]["kernel"].astype(cd))
    u = jnp.einsum("becd,edf->becf", buf, params["up"]["kernel"].astype(cd))
    yb = jnp.einsum("becf,efd->becd", act(g) * u, params["down"]["kernel"].astype(cd))

    if ba:
        from repro.parallel.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        bspec = ba if len(ba) > 1 else ba[0]
        comb = shard_map(
            lambda ybr, sl, ts, gs: _combine_batch(ybr, sl, ts, gs, S, cd),
            mesh=mesh,
            in_specs=(P(bspec), P(bspec), P(bspec), P(bspec)),
            out_specs=P(bspec),
        )
        y = comb(yb, slot, tok_sorted, gates_sorted)
    else:
        y = _combine_batch(yb, slot, tok_sorted, gates_sorted, S, cd)

    if moe.n_shared:
        from repro.models.mlp import mlp_apply

        y = y + mlp_apply(params["shared"], cfg, x)

    # switch load-balance: E * sum_e f_e * p_e  (f from kept+dropped picks)
    me = probs.mean(axis=(0, 1))  # (E,)
    ce = jax.vmap(lambda fe: jnp.zeros((E,), jnp.float32).at[fe.reshape(-1)].add(1.0))(
        expert_idx).sum(axis=0) / (B * S * k)
    lb = E * jnp.sum(ce * me)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    aux = {"load_balance_loss": lb, "router_z_loss": z}
    return y, aux
