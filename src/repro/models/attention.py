"""Attention: chunked (online-softmax) training/prefill attention, sliding
window, GQA, qk-norm, cross-attention, and two decode paths (local
full-cache, and seq-sharded flash-decode via shard_map).

No S×S score matrix is ever materialized: prefill_32k and train_4k run in
O(chunk_q × chunk_kv) score blocks (pure-JAX flash attention), with the
per-KV-block inner step checkpointed so the backward pass recomputes score
blocks instead of saving them.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm

NEG_INF = -1e30


def _softcap(x, cap: float):
    if cap and cap > 0.0:
        return jnp.tanh(x / cap) * cap
    return x


# ---------------------------------------------------------------------------- #
# Chunked attention core (train / prefill)                                      #
# ---------------------------------------------------------------------------- #


def chunked_attention(
    q: jnp.ndarray,  # (B, Sq, H, D)
    k: jnp.ndarray,  # (B, Sk, KV, D)
    v: jnp.ndarray,  # (B, Sk, KV, Dv)
    *,
    causal: bool = True,
    window: int = 0,  # >0 with causal: keys restricted to (q-window, q]
    chunk_q: int = 2048,
    chunk_kv: int = 2048,
    q_offset: int = 0,
    softcap: float = 0.0,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    Dv = v.shape[-1]
    G = H // KV
    scale = scale if scale is not None else D ** -0.5

    def _divisor_chunk(total, want):
        c = min(want, total)
        while total % c:  # shrink to the largest divisor <= want
            c -= 1
        return c

    cq = _divisor_chunk(Sq, chunk_q)
    ck = _divisor_chunk(Sk, chunk_kv)
    nq, nk = Sq // cq, Sk // ck
    # Head-major layout: expand KV heads to H up front so every tensor keeps
    # a plain H dim.  The (B,S,KV,G,D) reshape splits the sharded H axis into
    # two dims GSPMD cannot map onto the mesh -> it replicates the (cq,ck)
    # score blocks.  Post-repeat, scores are (B,H,cq,ck) sharded on H.
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    q4 = q * scale

    banded = window > 0 and causal
    if banded:
        kw = cq + window  # keys possibly visible to one q chunk
        nk_inner = min(-(-kw // ck), nk)
    else:
        nk_inner = nk

    def kv_block_step(carry, inputs):
        acc, m, l, q_blk, qpos = carry
        k_blk, v_blk, kpos = inputs
        s = jnp.einsum("bqhd,bshd->bhqs", q_blk, k_blk)  # (B,H,cq,ck)
        s = _softcap(s, softcap).astype(jnp.float32)
        mask = jnp.ones((q_blk.shape[1], k_blk.shape[1]), dtype=bool)
        if causal:
            mask = mask & (kpos[None, :] <= qpos[:, None])
        if window > 0:
            mask = mask & (kpos[None, :] > qpos[:, None] - window)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqs,bshd->bhqd", p.astype(v_blk.dtype), v_blk
        ).astype(jnp.float32)
        return (acc, m_new, l, q_blk, qpos), None

    kv_block_step = jax.checkpoint(kv_block_step)

    def q_block(args):
        qi, q_blk = args  # q_blk: (B, cq, H, D)
        qpos = q_offset + qi * cq + jnp.arange(cq)
        acc0 = jnp.zeros((B, H, cq, Dv), jnp.float32)
        m0 = jnp.full((B, H, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, cq), jnp.float32)
        if banded:
            width = nk_inner * ck
            start = jnp.clip(qi * cq + q_offset - window + 1, 0, Sk - width)
            k_loc = jax.lax.dynamic_slice_in_dim(k, start, width, axis=1)
            v_loc = jax.lax.dynamic_slice_in_dim(v, start, width, axis=1)
            kpos = start + jnp.arange(width)
        else:
            k_loc, v_loc, kpos = k, v, jnp.arange(Sk)
        nblk = k_loc.shape[1] // ck
        ks = k_loc.reshape(B, nblk, ck, H, D).swapaxes(0, 1)
        vs = v_loc.reshape(B, nblk, ck, H, Dv).swapaxes(0, 1)
        kps = kpos.reshape(nblk, ck)
        (acc, m, l, _, _), _ = jax.lax.scan(
            kv_block_step, (acc0, m0, l0, q_blk, qpos), (ks, vs, kps)
        )
        return acc / jnp.maximum(l[..., None], 1e-30)  # (B, H, cq, Dv)

    if nq == 1:
        outs = q_block((jnp.asarray(0), q4))[None]
    else:
        qs = q4.reshape(B, nq, cq, H, D).swapaxes(0, 1)
        outs = jax.lax.map(q_block, (jnp.arange(nq), qs))
    # outs: (nq, B, H, cq, Dv) -> (B, Sq, H, Dv)
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, Sq, H, Dv)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------- #
# Decode attention                                                              #
# ---------------------------------------------------------------------------- #


def _masked_decode(q1, k_cache, v_cache, lo, hi, softcap):
    """q1: (B,H,D); cache (B,S,KV,*); valid key positions p: lo <= p < hi.

    Head-major (KV repeated to H) so the (B,H,S) score tensor stays sharded
    on H under tensor parallelism — see chunked_attention."""
    B, H, D = q1.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    if G > 1:
        k_cache = jnp.repeat(k_cache, G, axis=2)
        v_cache = jnp.repeat(v_cache, G, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q1 * (D ** -0.5), k_cache)
    s = _softcap(s, softcap).astype(jnp.float32)
    ar = jnp.arange(S)[None, :]
    valid = (ar < hi[:, None]) & (ar >= lo[:, None])
    s = jnp.where(valid[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", p.astype(v_cache.dtype), v_cache)
    return out.astype(q1.dtype)


def flash_decode_sharded(q1, k_cache, v_cache, lo, hi, softcap, mesh, batch_axes):
    """Seq-sharded flash decode: KV cache sharded on its seq dim over the
    'model' mesh axis; each shard computes a partial softmax (o, m, l);
    partials are LSE-merged with an all-gather over 'model'.

    This is what lets a 500k-token cache decode even when kv_heads < 16:
    per-chip KV bytes shrink by the model-axis size regardless of head count.
    """
    from repro.parallel.mesh import shard_map
    from jax.sharding import PartitionSpec as P

    S, KV = k_cache.shape[1], k_cache.shape[2]
    n_shard = mesh.shape["model"]
    if S % n_shard != 0:
        return _masked_decode(q1, k_cache, v_cache, lo, hi, softcap)
    S_loc = S // n_shard
    H, D = q1.shape[1], q1.shape[2]

    def shard_fn(q_loc, k_loc, v_loc, lo_l, hi_l):
        idx = jax.lax.axis_index("model")
        Bl = q_loc.shape[0]
        G = H // KV
        kpos = idx * S_loc + jnp.arange(S_loc)
        valid = (kpos[None, :] < hi_l[:, None]) & (kpos[None, :] >= lo_l[:, None])
        q4 = (q_loc * (D ** -0.5)).reshape(Bl, KV, G, D)
        s = jnp.einsum("bkgd,bskd->bkgs", q4, k_loc)
        s = _softcap(s, softcap).astype(jnp.float32)
        s = jnp.where(valid[:, None, None], s, NEG_INF)
        m = s.max(axis=-1)
        p = jnp.exp(s - m[..., None])
        l = p.sum(axis=-1)
        o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_loc.dtype), v_loc).astype(jnp.float32)
        # LSE merge across the model axis
        om = jax.lax.all_gather(m, "model")
        ol = jax.lax.all_gather(l, "model")
        oo = jax.lax.all_gather(o, "model")
        m_g = om.max(axis=0)
        w = jnp.exp(om - m_g[None])
        l_g = (ol * w).sum(axis=0)
        o_g = (oo * w[..., None]).sum(axis=0)
        out = o_g / jnp.maximum(l_g[..., None], 1e-30)
        return out.reshape(Bl, H, v_loc.shape[-1]).astype(q_loc.dtype)

    ba = tuple(a for a in batch_axes if a in mesh.shape) or None
    if ba is not None:
        dp = 1
        for a in ba:
            dp *= mesh.shape[a]
        if q1.shape[0] % dp != 0:  # e.g. global_batch=1 long-context decode
            ba = None
    q_spec = P(ba, None, None)
    kv_spec = P(ba, "model", None, None)
    s_spec = P(ba)
    return shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, s_spec, s_spec),
        out_specs=q_spec,
    )(q1, k_cache, v_cache, lo, hi)


# ---------------------------------------------------------------------------- #
# Attention module: specs + apply                                               #
# ---------------------------------------------------------------------------- #


def attention_specs(cfg, stack: int) -> Dict[str, Any]:
    d, H, KV = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    p = {
        "wq": cm.dense_spec((d,), (H, hd), ("embed",), ("heads", "head_dim"),
                            stack=stack, bias=cfg.qkv_bias),
        "wk": cm.dense_spec((d,), (KV, hd), ("embed",), ("kv_heads", "head_dim"),
                            stack=stack, bias=cfg.qkv_bias),
        "wv": cm.dense_spec((d,), (KV, hd), ("embed",), ("kv_heads", "head_dim"),
                            stack=stack, bias=cfg.qkv_bias),
        "wo": cm.dense_spec((H, hd), (d,), ("heads", "head_dim"), ("embed",),
                            stack=stack),
    }
    if cfg.qk_norm:
        p["qknorm"] = cm.qknorm_spec(hd, stack)
    return p


def _rope_theta_for(cfg, kind: str) -> float:
    return cfg.rope_local_theta if kind == "attn_local" else cfg.rope_theta


def self_attention(
    params, cfg, part, x, *, kind: str,
    positions: Optional[jnp.ndarray] = None,
    cache: Optional[Dict[str, jnp.ndarray]] = None,
    mesh=None,
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """Full-sequence self-attention (train / prefill / encoder).

    x: (B, S, d).  If ``cache`` is given (prefill), K/V are written into it.
    """
    cd = jnp.dtype(cfg.compute_dtype)
    hd = cfg.resolved_head_dim
    S = x.shape[1]
    q = cm.dense(params["wq"], x, "...d,dhk->...hk", cd)
    k = cm.dense(params["wk"], x, "...d,dhk->...hk", cd)
    v = cm.dense(params["wv"], x, "...d,dhk->...hk", cd)
    if cfg.qk_norm:
        q = cm.headwise_rmsnorm(params["qknorm"]["q_scale"], q, cfg.norm_eps)
        k = cm.headwise_rmsnorm(params["qknorm"]["k_scale"], k, cfg.norm_eps)
    pos = positions if positions is not None else jnp.arange(S)[None, :]
    cos, sin = cm.rope_angles(pos, hd, _rope_theta_for(cfg, kind))
    q = cm.apply_rope(q, cos, sin)
    k = cm.apply_rope(k, cos, sin)
    out = chunked_attention(
        q, k, v,
        causal=(kind != "attn_bidir"),
        window=cfg.window if kind == "attn_local" else 0,
        chunk_q=part.attn_chunk_q, chunk_kv=part.attn_chunk_kv,
        softcap=cfg.logit_softcap,
    )
    y = cm.dense(params["wo"], out, "...hk,hkd->...d", cd)
    new_cache = None
    if cache is not None:
        if "pos" in cache:  # sliding-window ring cache
            new_cache = _ring_from_prefill(cache, k, v)
        else:
            kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
            new_cache = {"k": kc, "v": vc}
    return y, new_cache


def _ring_from_prefill(cache, k, v):
    """Build the sliding-window ring cache after a prefill of S tokens
    starting at position 0.  Ring slot i holds absolute position p ≡ i
    (mod W), p ∈ [S-W, S-1] — the gather indices are static (S, W are
    trace-time Python ints)."""
    import numpy as np

    W = cache["k"].shape[1]
    S = k.shape[1]
    if S >= W:
        base = S - W
        idx = np.array([base + ((i - base) % W) for i in range(W)])
        kc = k[:, idx].astype(cache["k"].dtype)
        vc = v[:, idx].astype(cache["v"].dtype)
        pos = jnp.broadcast_to(jnp.asarray(idx, cache["pos"].dtype), cache["pos"].shape)
    else:
        B = k.shape[0]
        pad = [(0, 0), (0, W - S), (0, 0), (0, 0)]
        kc = jnp.pad(k, pad).astype(cache["k"].dtype)
        vc = jnp.pad(v, pad).astype(cache["v"].dtype)
        pos1 = jnp.concatenate(
            [jnp.arange(S), jnp.full((W - S,), -1)]).astype(cache["pos"].dtype)
        pos = jnp.broadcast_to(pos1, (B, W))
    return {"k": kc, "v": vc, "pos": pos}


def self_attention_decode(
    params, cfg, part, x, *, kind: str,
    positions: jnp.ndarray,  # (B,) absolute position of the new token
    cache: Dict[str, jnp.ndarray],
    mesh=None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Single-token decode: update cache at ``positions``, attend over it."""
    cd = jnp.dtype(cfg.compute_dtype)
    hd = cfg.resolved_head_dim
    q = cm.dense(params["wq"], x, "...d,dhk->...hk", cd)  # (B,1,H,hd)
    k_new = cm.dense(params["wk"], x, "...d,dhk->...hk", cd)
    v_new = cm.dense(params["wv"], x, "...d,dhk->...hk", cd)
    if cfg.qk_norm:
        q = cm.headwise_rmsnorm(params["qknorm"]["q_scale"], q, cfg.norm_eps)
        k_new = cm.headwise_rmsnorm(params["qknorm"]["k_scale"], k_new, cfg.norm_eps)
    cos, sin = cm.rope_angles(positions[:, None], hd, _rope_theta_for(cfg, kind))
    q = cm.apply_rope(q, cos, sin)
    k_new = cm.apply_rope(k_new, cos, sin)
    k_cache = _scatter_cache(cache["k"], k_new, positions)
    v_cache = _scatter_cache(cache["v"], v_new, positions)
    hi = positions + 1
    if kind == "attn_local" and cfg.window > 0:
        lo = jnp.maximum(hi - cfg.window, 0)
    else:
        lo = jnp.zeros_like(hi)
    q1 = q[:, 0]
    if part.flash_decode and mesh is not None and "model" in mesh.shape:
        out = flash_decode_sharded(
            q1, k_cache, v_cache, lo, hi, cfg.logit_softcap, mesh, ("pod", "data"))
    else:
        out = _masked_decode(q1, k_cache, v_cache, lo, hi, cfg.logit_softcap)
    y = cm.dense(params["wo"], out[:, None], "...hk,hkd->...d", cd)
    return y, {"k": k_cache, "v": v_cache}


def cross_attention(
    params, cfg, part, x, *,
    enc_kv: Dict[str, jnp.ndarray],  # precomputed {"k","v"}: (B, S_enc, KV, hd)
    decode: bool = False,
    mesh=None,
) -> jnp.ndarray:
    """Cross-attention against (precomputed) encoder K/V.  No RoPE."""
    cd = jnp.dtype(cfg.compute_dtype)
    q = cm.dense(params["wq"], x, "...d,dhk->...hk", cd)
    if cfg.qk_norm:
        q = cm.headwise_rmsnorm(params["qknorm"]["q_scale"], q, cfg.norm_eps)
    k, v = enc_kv["k"].astype(cd), enc_kv["v"].astype(cd)
    if decode:
        B = x.shape[0]
        S_enc = k.shape[1]
        lo = jnp.zeros((B,), jnp.int32)
        hi = jnp.full((B,), S_enc, jnp.int32)
        out = _masked_decode(q[:, 0], k, v, lo, hi, cfg.logit_softcap)[:, None]
    else:
        out = chunked_attention(
            q, k, v, causal=False,
            chunk_q=part.attn_chunk_q, chunk_kv=part.attn_chunk_kv,
            softcap=cfg.logit_softcap,
        )
    return cm.dense(params["wo"], out, "...hk,hkd->...d", cd)


def cross_kv(params, cfg, enc_out: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Precompute cross-attention K/V from encoder outputs."""
    cd = jnp.dtype(cfg.compute_dtype)
    k = cm.dense(params["wk"], enc_out, "...d,dhk->...hk", cd)
    v = cm.dense(params["wv"], enc_out, "...d,dhk->...hk", cd)
    if cfg.qk_norm:
        k = cm.headwise_rmsnorm(params["qknorm"]["k_scale"], k, cfg.norm_eps)
    return {"k": k, "v": v}


def _scatter_cache(cache, new, pos):
    """Place (B,1,KV,hd) entries at per-batch positions (B,) along axis 1."""
    B = cache.shape[0]
    idx = pos.reshape(B, 1, 1, 1).astype(jnp.int32)
    iota = jnp.arange(cache.shape[1]).reshape(1, -1, 1, 1)
    return jnp.where(iota == idx, new.astype(cache.dtype), cache)
