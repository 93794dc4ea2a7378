"""Pure-jnp oracles for every Pallas kernel (the reference implementations
the kernels are validated against, in kernel-native (state, batch) layout)."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.trellis import ConvCode


def texpand_ref(
    code: ConvCode, pm: jnp.ndarray, bm_table: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle for the one-step fused ACS kernel.

    Kernel-native layout: states/symbols lead, batch is the minor (lane) axis.

    Args:
      pm: (S, B) float32 path metrics.
      bm_table: (M, B) float32 per-step branch-metric table.
    Returns:
      new_pm: (S, B); bp: (S, B) int32 backpointer parity (ties -> 0).
    """
    P0, P1 = code.select_matrices
    OH0, OH1 = code.branch_onehot_pair
    cand0 = jnp.asarray(P0) @ pm + jnp.asarray(OH0) @ bm_table
    cand1 = jnp.asarray(P1) @ pm + jnp.asarray(OH1) @ bm_table
    take1 = cand1 < cand0
    return jnp.where(take1, cand1, cand0), take1.astype(jnp.int32)


def viterbi_scan_ref(
    code: ConvCode, bm_tables: jnp.ndarray, pm0: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle for the full-sequence kernel.

    Args:
      bm_tables: (T, M, B); pm0: (S, B) initial metrics.
    Returns:
      final_pm: (S, B); bps: (T, S, B) int32.
    """

    def step(pm, bm_t):
        new_pm, bp = texpand_ref(code, pm, bm_t)
        return new_pm, bp

    final_pm, bps = jax.lax.scan(step, pm0, bm_tables)
    return final_pm, bps


def minplus_matmul_ref(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Oracle for the (min,+) matmul kernel.  a: (B, I, K), b: (B, K, J)."""
    return jnp.min(a[..., :, :, None] + b[..., None, :, :], axis=-2)


def bcjr_llr_ref(code, feat: jnp.ndarray, terminated: bool = False) -> jnp.ndarray:
    """Oracle for the alpha + beta/LLR BCJR kernel pair (kernels/bcjr.py).

    Same operand matrices, same min-domain algebra, kernel-native layout.

    Args:
      code: an RSCCode (duck-typed: only the cached table properties are
        used, so kernels/ never imports siso/).
      feat: (T, F, B) per-step feature columns (channel LLRs + a-priori).
    Returns:
      llr: (T, B) float32 max-log LLRs (negative -> decide 1).
    """
    from repro.core.trellis import NEG_UNREACHABLE

    T, F, B = feat.shape
    S = code.n_states
    P0, P1 = (jnp.asarray(m) for m in code.select_matrices)
    b0, b1 = (jnp.asarray(m) for m in code.alpha_weights)
    N0, N1 = (jnp.asarray(m) for m in code.beta_matrices)
    c0, c1 = (jnp.asarray(m) for m in code.beta_weights)
    U0, U1 = (jnp.asarray(m) for m in code.llr_matrices)
    w0, w1 = (jnp.asarray(m) for m in code.llr_weights)

    col0 = jnp.where(jnp.arange(S)[:, None] == 0, 0.0, NEG_UNREACHABLE)
    col0 = jnp.broadcast_to(col0, (S, B))

    def fwd(alpha, f_t):
        new = jnp.minimum(P0 @ alpha + b0 @ f_t, P1 @ alpha + b1 @ f_t)
        new = jnp.minimum(new - new.min(axis=0, keepdims=True), NEG_UNREACHABLE)
        return new, alpha  # emit the PRE-update A_t, like the kernel

    _, alphas = jax.lax.scan(fwd, col0, feat)

    def bwd(beta, inputs):
        alpha, f_t = inputs
        cost0 = alpha + w0 @ f_t + U0 @ beta
        cost1 = alpha + w1 @ f_t + U1 @ beta
        llr_t = cost1.min(axis=0) - cost0.min(axis=0)
        new = jnp.minimum(N0 @ beta + c0 @ f_t, N1 @ beta + c1 @ f_t)
        new = jnp.minimum(new - new.min(axis=0, keepdims=True), NEG_UNREACHABLE)
        return new, llr_t

    beta_T = col0 if terminated else jnp.zeros((S, B))
    _, llr = jax.lax.scan(bwd, beta_T, (alphas, feat), reverse=True)
    return llr


_bcjr_llr_ref_jit = jax.jit(bcjr_llr_ref, static_argnums=(0, 2))


def turbo_decode_ref(code, perm, llrs: jnp.ndarray, *, tail: str = "none",
                     iterations: int = 6, early_exit: bool = True,
                     extrinsic_scale: float = 0.7):
    """Oracle for the iterative turbo decoder (siso/turbo.py), written from
    the description of 3GPP TS 36.212 5.1.3.2 and the decoder's schedule,
    in plain float32 ``jax.numpy`` on top of :func:`bcjr_llr_ref`.

    Args:
      code: the constituent RSCCode (duck-typed, as bcjr_llr_ref).
      perm: (K,) the interleaver: interleaved[k] = natural[perm[k]].
      llrs: (B, K, 3) channel LLRs of the streams d0, d1, d2 (``lambda =
        log P(0)/P(1)``), or (B, K + 4, 3) with ``tail="36.212"``.
      tail: "none" (open constituents) or "36.212" (5.1.3.2.2: each
        constituent ends in state 0 after three tail steps; the 12 tail bits
        sit in rows K..K+3 as d0 = x_K, z_K+1, x'_K, z'_K+1; d1 = z_K, x_K+2,
        z'_K, x'_K+2; d2 = x_K+1, z_K+2, x'_K+1, z'_K+2).
    Returns:
      (llr (B, K) float32 a-posteriori LLRs of the last iteration run, in
      natural order; iterations run; (B,) bool streams frozen).

    Departures from the standard, which specifies the encoder and leaves the
    decoder to the receiver: max-log-MAP constituents; extrinsic values
    scaled by ``extrinsic_scale``; no CRC: a stream whose decisions equal the
    previous iteration's is frozen (its extrinsic input held), and with
    ``early_exit`` the loop stops once every stream froze.
    """
    with jax.default_matmul_precision("highest"):
        llrs = jnp.asarray(llrs, jnp.float32)
        B = llrs.shape[0]
        K = len(perm)
        perm = jnp.asarray(perm)
        inv = jnp.argsort(perm)
        x, z1, z2 = llrs[:, :K, 0], llrs[:, :K, 1], llrs[:, :K, 2]
        terminated = tail == "36.212"
        if terminated:
            t = llrs[:, K:K + 4, :].reshape(B, 12)  # flat index 3 * (row - K) + stream
            tails1 = (t[:, jnp.array([0, 2, 4])], t[:, jnp.array([1, 3, 5])])
            tails2 = (t[:, jnp.array([6, 8, 10])], t[:, jnp.array([7, 9, 11])])
        elif tail != "none":
            raise ValueError(f"unknown tail {tail!r}")

        def siso(sys, par, apriori, tails):
            if terminated:
                sys = jnp.concatenate([sys, tails[0]], axis=1)
                par = jnp.concatenate([par, tails[1]], axis=1)
                apriori = jnp.concatenate([apriori, jnp.zeros((B, 3))], axis=1)
            feat = jnp.stack([sys, par, apriori], axis=1).transpose(2, 1, 0)  # (T, 3, B)
            return _bcjr_llr_ref_jit(code, feat, terminated).T[:, :K]

        x2 = x[:, perm]
        le2 = jnp.zeros((B, K), jnp.float32)
        frozen = jnp.zeros((B,), bool)
        prev = llr = None
        n_run = 0
        for _ in range(iterations):
            la1 = le2[:, inv]
            le1 = extrinsic_scale * (siso(x, z1, la1, tails1 if terminated else None)
                                     - x - la1)
            la2 = le1[:, perm]
            l2 = siso(x2, z2, la2, tails2 if terminated else None)
            llr = l2[:, inv]
            bits = llr < 0
            if prev is not None:
                frozen = frozen | jnp.all(bits == prev, axis=1)
            le2 = jnp.where(frozen[:, None], le2, extrinsic_scale * (l2 - x2 - la2))
            prev = bits
            n_run += 1
            if early_exit and bool(frozen.all()):
                break
        return llr, n_run, frozen
