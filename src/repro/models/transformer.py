"""Decoder-only LM assembly: heterogeneous block stacks, scan-over-groups,
train / prefill / decode paths, cache management.

The layer stack is organized as ``n_groups`` repetitions of the config's
``pattern`` (a tuple of (mixer, ffn) block kinds).  All parameters of block
position ``p`` are stacked over groups, and the forward pass is a
``lax.scan`` over groups — HLO size and compile time are O(group), not
O(n_layers).  Heterogeneous stacks (gemma3 5:1 local:global, jamba 1:7
attn:mamba, xlstm 7:1 mLSTM:sLSTM) scan over the repeating group.

Caches are pytrees stacked the same way ((n_groups, ...) leading dim) so the
decode step scans over (params, caches) jointly.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.attention import (
    attention_specs,
    self_attention,
    self_attention_decode,
)
from repro.models.mlp import mlp_apply, mlp_specs

ATTN_KINDS = ("attn", "attn_bidir", "attn_local")


# --------------------------------------------------------------------------- #
# Block specs                                                                  #
# --------------------------------------------------------------------------- #


def _mixer_specs(cfg, mixer: str, stack: int):
    if mixer in ATTN_KINDS:
        return attention_specs(cfg, stack)
    if mixer == "mla":
        return mla_mod.mla_specs(cfg, stack)
    if mixer == "mamba":
        return ssm_mod.ssm_specs(cfg, stack)
    if mixer == "mlstm":
        return xlstm_mod.mlstm_specs(cfg, stack)
    if mixer == "slstm":
        return xlstm_mod.slstm_specs(cfg, stack)
    raise ValueError(f"unknown mixer {mixer}")


def _ffn_specs(cfg, ffn: str, stack: int):
    if ffn == "mlp":
        return mlp_specs(cfg, stack)
    if ffn == "moe":
        return moe_mod.moe_specs(cfg, stack)
    if ffn == "none":
        return None
    raise ValueError(f"unknown ffn {ffn}")


def block_specs(cfg, mixer: str, ffn: str, stack: int, cross: bool = False):
    style = "rms"
    p: Dict[str, Any] = {
        "ln1": cm.norm_spec(cfg.d_model, stack=stack, style=style),
        "mixer": _mixer_specs(cfg, mixer, stack),
    }
    if cfg.norm_style == "sandwich":
        p["ln1_post"] = cm.norm_spec(cfg.d_model, stack=stack, style=style)
    if cross:
        p["ln_cross"] = cm.norm_spec(cfg.d_model, stack=stack, style=style)
        p["cross"] = attention_specs(cfg, stack)
    f = _ffn_specs(cfg, ffn, stack)
    if f is not None:
        p["ln2"] = cm.norm_spec(cfg.d_model, stack=stack, style=style)
        p["ffn"] = f
        if cfg.norm_style == "sandwich":
            p["ln2_post"] = cm.norm_spec(cfg.d_model, stack=stack, style=style)
    return p


def lm_specs(cfg, part) -> Dict[str, Any]:
    """Full parameter spec tree for a decoder-only LM."""
    stack = cfg.n_groups
    p: Dict[str, Any] = {"embed": cm.embed_spec(cfg.vocab, cfg.d_model)}
    p["blocks"] = {
        f"p{i}": block_specs(cfg, mixer, ffn, stack)
        for i, (mixer, ffn) in enumerate(cfg.pattern)
    }
    p["final_norm"] = cm.norm_spec(cfg.d_model, stack=0)
    if not cfg.tie_embeddings:
        p["lm_head"] = cm.dense_spec(
            (cfg.d_model,), (cfg.vocab,), ("embed",), ("vocab",), scale=1.0
        )
    if cfg.modality == "vision":
        p["frontend_proj"] = cm.dense_spec(
            (cfg.frontend_dim,), (cfg.d_model,), ("frontend",), ("embed",)
        )
    return p


# --------------------------------------------------------------------------- #
# Cache specs                                                                  #
# --------------------------------------------------------------------------- #


def _mixer_cache_specs(cfg, part, mixer: str, B: int, S: int, stack: int):
    """ParamSpec tree for one mixer's decode cache (stacked over groups).

    Logical axes: 'kv_seq' shards the cache sequence dim over 'model' when
    flash-decode is on (resolve_axes drops it gracefully otherwise).
    """
    bf16 = jnp.bfloat16
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    seq_ax = "kv_seq" if part.flash_decode else None
    L = ("layers",)

    def PS(shape, axes, dtype=bf16):
        return cm.ParamSpec((stack,) + shape, L + axes, "zeros", dtype=dtype)

    if mixer in ("attn", "attn_bidir"):
        kv = PS((B, S, KV, hd), ("batch", seq_ax, "kv_heads", "head_dim"))
        return {"k": kv, "v": kv}
    if mixer == "attn_local":
        W = min(cfg.window, S)
        kv = PS((B, W, KV, hd), ("batch", None, "kv_heads", "head_dim"))
        pos = PS((B, W), ("batch", None), dtype=jnp.int32)
        return {"k": kv, "v": kv, "pos": pos}
    if mixer == "mla":
        m = cfg.mla
        return {
            "c_kv": PS((B, S, m.kv_lora_rank), ("batch", seq_ax, "kv_lora")),
            "k_rope": PS((B, S, m.rope_head_dim), ("batch", seq_ax, "head_dim")),
        }
    if mixer == "mamba":
        s = cfg.ssm
        d_in = s.expand * cfg.d_model
        return {
            "ssm": PS((B, d_in, s.d_state), ("batch", "dinner", "dstate"), jnp.float32),
            "conv": PS((B, s.d_conv - 1, d_in), ("batch", None, "dinner")),
        }
    if mixer == "mlstm":
        x = cfg.xlstm
        d_in = int(x.mlstm_proj_factor * cfg.d_model)
        H = cfg.n_heads
        dh = d_in // H
        return {
            "C": PS((B, H, dh, dh), ("batch", "heads", None, None), jnp.float32),
            "n": PS((B, H, dh), ("batch", "heads", None), jnp.float32),
            "m": PS((B, H), ("batch", "heads"), jnp.float32),
            "conv": PS((B, x.conv_kernel - 1, d_in), ("batch", None, "dinner")),
        }
    if mixer == "slstm":
        d = cfg.d_model
        st = {
            k: PS((B, d), ("batch", "dinner"), jnp.float32) for k in ("c", "n", "h", "m")
        }
        return {"state": st}
    raise ValueError(mixer)


def cache_specs(cfg, part, B: int, S: int) -> Dict[str, Any]:
    stack = cfg.n_groups
    return {
        f"p{i}": _mixer_cache_specs(cfg, part, mixer, B, S, stack)
        for i, (mixer, _) in enumerate(cfg.pattern)
    }


def init_cache(cfg, part, B: int, S: int):
    """Zero caches (slstm m / mlstm m start at -inf; attn_local pos at -1)."""
    specs = cache_specs(cfg, part, B, S)
    caches = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), specs, is_leaf=cm._is_spec
    )
    for i, (mixer, _) in enumerate(cfg.pattern):
        c = caches[f"p{i}"]
        if mixer == "attn_local":
            c["pos"] = jnp.full_like(c["pos"], -1)
        elif mixer == "mlstm":
            c["m"] = jnp.full_like(c["m"], -1e30)
        elif mixer == "slstm":
            c["state"]["m"] = jnp.full_like(c["state"]["m"], -1e30)
    return caches


# --------------------------------------------------------------------------- #
# Block application                                                            #
# --------------------------------------------------------------------------- #


def _norm(params, cfg, x):
    return cm.rmsnorm(params, x, cfg.norm_eps, compute_dtype=jnp.dtype(cfg.compute_dtype))


def apply_block_full(
    bp, cfg, part, mixer: str, ffn: str, x, *,
    positions=None, cache=None, mesh=None, rules=None,
):
    """Full-sequence block (train / prefill).  Returns (x, new_cache, aux)."""
    h = _norm(bp["ln1"], cfg, x)
    new_cache = None
    if mixer in ATTN_KINDS:
        y, new_cache = self_attention(
            bp["mixer"], cfg, part, h, kind=mixer, positions=positions,
            cache=cache, mesh=mesh)
    elif mixer == "mla":
        y, new_cache = mla_mod.mla_attention(
            bp["mixer"], cfg, part, h, positions=positions, cache=cache)
    elif mixer == "mamba":
        y, new_cache = ssm_mod.ssm_apply(bp["mixer"], cfg, h, cache=cache)
    elif mixer == "mlstm":
        y, new_cache = xlstm_mod.mlstm_apply(bp["mixer"], cfg, h, cache=cache)
    elif mixer == "slstm":
        y, new_cache = xlstm_mod.slstm_apply(bp["mixer"], cfg, h, cache=cache)
    else:
        raise ValueError(mixer)
    if cfg.norm_style == "sandwich":
        y = _norm(bp["ln1_post"], cfg, y)
    x = x + y
    aux = {}
    if ffn != "none":
        h = _norm(bp["ln2"], cfg, x)
        if ffn == "mlp":
            y = mlp_apply(bp["ffn"], cfg, h)
        else:
            y, aux = moe_mod.moe_apply(bp["ffn"], cfg, h, mesh=mesh)
        if cfg.norm_style == "sandwich":
            y = _norm(bp["ln2_post"], cfg, y)
        x = x + y
    if part.seq_shard_activations and mesh is not None:
        x = cm.constrain(x, mesh, rules, ("batch", "seq_shard", None))
    return x, new_cache, aux


def _local_ring_decode(params, cfg, part, x, *, positions, cache):
    """Sliding-window decode against a ring cache of width W.

    cache: k/v (B, W, KV, hd) with RoPE pre-applied at write; pos (B, W)
    absolute positions (-1 = empty).  New entry lands in slot pos % W — the
    ring invariant keeps exactly the last W positions resident, so validity
    is just ``pos >= 0``.
    """
    cd = jnp.dtype(cfg.compute_dtype)
    hd = cfg.resolved_head_dim
    B = x.shape[0]
    W = cache["k"].shape[1]
    q = cm.dense(params["wq"], x, "...d,dhk->...hk", cd)
    k_new = cm.dense(params["wk"], x, "...d,dhk->...hk", cd)
    v_new = cm.dense(params["wv"], x, "...d,dhk->...hk", cd)
    if cfg.qk_norm:
        q = cm.headwise_rmsnorm(params["qknorm"]["q_scale"], q, cfg.norm_eps)
        k_new = cm.headwise_rmsnorm(params["qknorm"]["k_scale"], k_new, cfg.norm_eps)
    cos, sin = cm.rope_angles(positions[:, None], hd, cfg.rope_local_theta)
    q = cm.apply_rope(q, cos, sin)
    k_new = cm.apply_rope(k_new, cos, sin)
    slot = (positions % W).astype(jnp.int32)
    iota = jnp.arange(W).reshape(1, -1, 1, 1)
    sel = iota == slot.reshape(B, 1, 1, 1)
    k_cache = jnp.where(sel, k_new.astype(cache["k"].dtype), cache["k"])
    v_cache = jnp.where(sel, v_new.astype(cache["v"].dtype), cache["v"])
    pos_arr = jnp.where(
        jnp.arange(W)[None, :] == slot[:, None], positions[:, None], cache["pos"]
    ).astype(cache["pos"].dtype)
    # attend over valid ring slots
    KV = cfg.n_kv_heads
    H = cfg.n_heads
    G = H // KV
    q4 = (q[:, 0] * (hd ** -0.5)).reshape(B, KV, G, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", q4, k_cache.astype(cd))
    s = s.astype(jnp.float32)
    if cfg.logit_softcap:
        s = jnp.tanh(s / cfg.logit_softcap) * cfg.logit_softcap
    valid = pos_arr >= 0
    s = jnp.where(valid[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(cd), v_cache.astype(cd))
    out = out.reshape(B, 1, H, hd)
    y = cm.dense(params["wo"], out, "...hk,hkd->...d", cd)
    return y, {"k": k_cache, "v": v_cache, "pos": pos_arr}


def apply_block_decode(
    bp, cfg, part, mixer: str, ffn: str, x, *, positions, cache, mesh=None, rules=None
):
    """Single-token block.  x: (B, 1, d).  Returns (x, new_cache, aux)."""
    h = _norm(bp["ln1"], cfg, x)
    if mixer in ("attn", "attn_bidir"):
        y, new_cache = self_attention_decode(
            bp["mixer"], cfg, part, h, kind=mixer, positions=positions,
            cache=cache, mesh=mesh)
    elif mixer == "attn_local":
        y, new_cache = _local_ring_decode(
            bp["mixer"], cfg, part, h, positions=positions, cache=cache)
    elif mixer == "mla":
        y, new_cache = mla_mod.mla_attention_decode(
            bp["mixer"], cfg, part, h, positions=positions, cache=cache)
    elif mixer == "mamba":
        y, new_cache = ssm_mod.ssm_decode(bp["mixer"], cfg, h, cache=cache)
    elif mixer == "mlstm":
        y, new_cache = xlstm_mod.mlstm_decode(bp["mixer"], cfg, h, cache=cache)
    elif mixer == "slstm":
        y, new_cache = xlstm_mod.slstm_decode(bp["mixer"], cfg, h, cache=cache)
    else:
        raise ValueError(mixer)
    if cfg.norm_style == "sandwich":
        y = _norm(bp["ln1_post"], cfg, y)
    x = x + y
    if ffn != "none":
        h = _norm(bp["ln2"], cfg, x)
        if ffn == "mlp":
            y = mlp_apply(bp["ffn"], cfg, h)
        else:
            y, _ = moe_mod.moe_apply(bp["ffn"], cfg, h, mesh=mesh)
        if cfg.norm_style == "sandwich":
            y = _norm(bp["ln2_post"], cfg, y)
        x = x + y
    return x, new_cache


# --------------------------------------------------------------------------- #
# Group scan                                                                   #
# --------------------------------------------------------------------------- #


def _remat_policy(part):
    if part.remat == "full":
        return jax.checkpoint_policies.nothing_saveable
    if part.remat == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return None


def remat_scan(body, carry, xs, n: int, policy, scan: bool = True):
    """O(sqrt(L)) recursive activation checkpointing over a layer scan.

    A flat ``lax.scan`` backward stores every iteration's residuals —
    O(L·block) memory even with block-level remat (measured: ~2.8 GiB/layer
    on the 110B config).  Factoring the scan as outer(≈sqrt L, checkpointed)
    × inner(sqrt L) stores only outer boundaries plus one inner pass:
    O(sqrt(L)·carry + block).
    """
    if not scan:
        ys = []
        for g in range(n):
            xg = jax.tree_util.tree_map(lambda a: a[g], xs)
            carry, y = body(carry, xg)
            ys.append(y)
        if all(y is None for y in ys):
            return carry, None
        return carry, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)

    if policy is None or n < 4:
        return jax.lax.scan(body, carry, xs)

    import math

    no = int(math.ceil(math.sqrt(n)))
    while n % no:
        no += 1
    ni = n // no
    xs2 = jax.tree_util.tree_map(
        lambda a: a.reshape((no, ni) + a.shape[1:]), xs)

    def outer(c, xo):
        return jax.lax.scan(body, c, xo)

    outer = jax.checkpoint(outer, policy=policy)
    carry, ys2 = jax.lax.scan(outer, carry, xs2)
    if ys2 is None:
        return carry, None
    ys = jax.tree_util.tree_map(
        lambda a: a.reshape((n,) + a.shape[2:]), ys2)
    return carry, ys


def run_stack_full(
    params_blocks, cfg, part, x, *,
    positions=None, caches=None, mesh=None, rules=None, collect_aux=True,
):
    """Scan the (stacked) block groups over a full-sequence input.

    caches: optional stacked cache tree (prefill) — consumed/produced as
    scan xs/ys.  Returns (x, new_caches, aux_sums).
    """
    policy = _remat_policy(part)

    def group_fn(carry, xs):
        x, aux_acc = carry
        gp, gc = xs
        new_caches = {}
        for i, (mixer, ffn) in enumerate(cfg.pattern):
            cache_i = None if gc is None else gc.get(f"p{i}")
            def block_fn(bp, x, cache, _mixer=mixer, _ffn=ffn):
                return apply_block_full(
                    bp, cfg, part, _mixer, _ffn, x,
                    positions=positions, cache=cache, mesh=mesh, rules=rules)

            if policy is not None:
                # remat at BLOCK granularity: backward recomputes one block's
                # internals at a time (peak = one block, not a whole group)
                block_fn = jax.checkpoint(block_fn, policy=policy)
            x, nc, aux = block_fn(gp[f"p{i}"], x, cache_i)
            if nc is not None:
                new_caches[f"p{i}"] = nc
            if aux and collect_aux:
                aux_acc = (
                    aux_acc[0] + aux.get("load_balance_loss", 0.0),
                    aux_acc[1] + aux.get("router_z_loss", 0.0),
                )
        return (x, aux_acc), (new_caches if gc is not None else None)

    aux0 = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
    (x, aux), new_caches = remat_scan(
        group_fn, (x, aux0), (params_blocks, caches), cfg.n_groups, policy,
        scan=part.scan_layers)
    return x, new_caches, {"load_balance_loss": aux[0], "router_z_loss": aux[1]}


def run_stack_decode(
    params_blocks, cfg, part, x, *, positions, caches, mesh=None, rules=None
):
    """Scan block groups for one decode step; caches are scan xs -> ys."""

    def group_fn(x, xs):
        gp, gc = xs
        new_caches = {}
        for i, (mixer, ffn) in enumerate(cfg.pattern):
            x, nc = apply_block_decode(
                gp[f"p{i}"], cfg, part, mixer, ffn, x,
                positions=positions, cache=gc[f"p{i}"], mesh=mesh, rules=rules)
            new_caches[f"p{i}"] = nc
        return x, new_caches

    if part.scan_layers:
        x, new_caches = jax.lax.scan(group_fn, x, (params_blocks, caches))
    else:
        outs = []
        for g in range(cfg.n_groups):
            gp = jax.tree_util.tree_map(lambda a: a[g], params_blocks)
            gc = jax.tree_util.tree_map(lambda a: a[g], caches)
            x, yc = group_fn(x, (gp, gc))
            outs.append(yc)
        new_caches = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *outs)
    return x, new_caches


# --------------------------------------------------------------------------- #
# Embedding / head                                                             #
# --------------------------------------------------------------------------- #


def embed_tokens(params, cfg, tokens, patches=None):
    """tokens: (B, S_tok); patches: (B, n_prefix, frontend_dim) for VLMs.
    Returns (B, S, d) with patches projected and prefixed."""
    cd = jnp.dtype(cfg.compute_dtype)
    x = cm.embed_lookup(params["embed"], tokens, cd)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cd)
    if patches is not None:
        px = cm.dense(params["frontend_proj"], patches, "...f,fd->...d", cd)
        x = jnp.concatenate([px, x], axis=1)
    return x


def lm_head(params, cfg, x):
    cd = jnp.dtype(cfg.compute_dtype)
    if cfg.tie_embeddings:
        w = params["embed"]["embedding"].astype(cd)  # (V, d)
        return jnp.einsum("...d,vd->...v", x, w)
    return cm.dense(params["lm_head"], x, "...d,dv->...v", cd)


def softmax_xent(logits, labels, valid=None, z_weight: float = 0.0, mesh=None):
    """Cross-entropy in f32.  logits: (B,S,V); labels: (B,S) int32.

    On a mesh with a 'model' axis the loss runs under shard_map with the
    vocab dim sharded: per-shard masked gold-gather + psum, and a
    pmax/psum-logsumexp — no (B,S,V)-sized intermediate beyond the local
    bf16 logits ever materializes.  (A plain take_along_axis over the
    vocab-sharded dim makes GSPMD gather full f32 logits per chip; a
    one-hot einsum materializes (B,S,V) iota/pred/f32 masks.)"""
    if mesh is not None and "model" in mesh.shape and \
            logits.shape[-1] % mesh.shape["model"] == 0:
        nll, lse = _xent_sharded(logits, labels, mesh)
    else:
        lg = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
        nll = lse - gold
    if valid is None:
        valid = jnp.ones_like(nll)
    else:
        valid = valid.astype(jnp.float32)
    denom = jnp.maximum(valid.sum(), 1.0)
    loss = (nll * valid).sum() / denom
    if z_weight:
        loss = loss + z_weight * ((lse ** 2) * valid).sum() / denom
    return loss


def _xent_sharded(logits, labels, mesh):
    """Vocab-sharded NLL: returns (nll (B,S), lse (B,S)) f32."""
    from repro.parallel.mesh import shard_map
    from jax.sharding import PartitionSpec as P

    V = logits.shape[-1]
    n = mesh.shape["model"]
    v_loc = V // n
    ba = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = 1
    for a in ba:
        dp *= mesh.shape[a]
    bspec = (ba if len(ba) > 1 else ba[0]) if (ba and logits.shape[0] % dp == 0) \
        else None

    def f(lg, lb):  # lg: (Bl, S, v_loc) bf16; lb: (Bl, S)
        lg = lg.astype(jnp.float32)
        off = jax.lax.axis_index("model") * v_loc
        loc = lb - off
        ok = (loc >= 0) & (loc < v_loc)
        gold_l = jnp.take_along_axis(
            lg, jnp.clip(loc, 0, v_loc - 1)[..., None], axis=-1)[..., 0]
        gold = jax.lax.psum(jnp.where(ok, gold_l, 0.0), "model")
        # stabilizer only -> constant under differentiation (pmax has no VJP;
        # stop_gradient BEFORE pmax so AD sees a symbolic-zero tangent)
        m = jax.lax.pmax(jax.lax.stop_gradient(lg.max(axis=-1)), "model")
        sumexp = jax.lax.psum(jnp.exp(lg - m[..., None]).sum(axis=-1), "model")
        lse = m + jnp.log(sumexp)
        return lse - gold, lse

    return shard_map(
        f, mesh=mesh,
        in_specs=(P(bspec, None, "model"), P(bspec, None)),
        out_specs=(P(bspec, None), P(bspec, None)),
    )(logits, labels)


# --------------------------------------------------------------------------- #
# Top-level LM functions                                                       #
# --------------------------------------------------------------------------- #


def lm_train_loss(params, cfg, part, batch, mesh=None, rules=None):
    """batch: {"tokens": (B,S), "labels": (B,S)} (+ "patches" for VLM).
    Returns (loss, metrics)."""
    tokens = batch["tokens"]
    x = embed_tokens(params, cfg, tokens, batch.get("patches"))
    if mesh is not None:
        x = cm.constrain(x, mesh, rules, ("batch", None, None))
    x, _, aux = run_stack_full(
        params["blocks"], cfg, part, x, mesh=mesh, rules=rules)
    x = cm.rmsnorm(params["final_norm"], x, cfg.norm_eps,
                   compute_dtype=jnp.dtype(cfg.compute_dtype))
    logits = lm_head(params, cfg, x)
    labels = batch["labels"]
    if cfg.modality == "vision" and cfg.n_prefix_tokens:
        # patch positions carry no next-token target
        logits = logits[:, cfg.n_prefix_tokens:]
    loss = softmax_xent(logits, labels, batch.get("valid"), mesh=mesh)
    total = loss
    if cfg.moe is not None:
        total = total + cfg.moe.aux_loss_weight * aux["load_balance_loss"] \
            + 1e-3 * aux["router_z_loss"]
    metrics = {"loss": loss, **aux}
    return total, metrics


def lm_prefill(params, cfg, part, tokens, caches, *,
               patches=None, mesh=None, rules=None):
    """Prefill: run the full sequence, writing decode caches.

    Returns (logits_last (B, V), caches)."""
    x = embed_tokens(params, cfg, tokens, patches)
    if mesh is not None:
        # pin batch sharding: without this GSPMD derives a batch-replicated
        # layout from the weight shardings (measured: gemma3 prefill carried
        # full-batch f32 activations on every chip)
        x = cm.constrain(x, mesh, rules, ("batch", None, None))
    x, new_caches, _ = run_stack_full(
        params["blocks"], cfg, part, x, caches=caches, mesh=mesh, rules=rules,
        collect_aux=False)
    x = cm.rmsnorm(params["final_norm"], x, cfg.norm_eps,
                   compute_dtype=jnp.dtype(cfg.compute_dtype))
    logits = lm_head(params, cfg, x[:, -1:])[:, 0]
    return logits, new_caches


def lm_decode_step(params, cfg, part, tokens, positions, caches, *,
                   mesh=None, rules=None):
    """One decode step.  tokens: (B, 1); positions: (B,).
    Returns (logits (B, V), new caches)."""
    x = embed_tokens(params, cfg, tokens)
    x, new_caches = run_stack_decode(
        params["blocks"], cfg, part, x, positions=positions, caches=caches,
        mesh=mesh, rules=rules)
    x = cm.rmsnorm(params["final_norm"], x, cfg.norm_eps,
                   compute_dtype=jnp.dtype(cfg.compute_dtype))
    logits = lm_head(params, cfg, x)[:, 0]
    return logits, new_caches
