"""Plain Viterbi reference for the benchmark's ``correct`` check.

It imports nothing of the program under test.  A rate-1/n feed-forward
convolutional code is decoded by the textbook add-compare-select recursion
over integer path metrics, so every decision is exact:

* soft symbols arrive on the 1/8 grid, so ``8 * y`` is an integer and the
  correlation metric ``sum_j y_j (2 c_j - 1)`` is kept in units of 1/8;
* the state is the K-1 most recent input bits, newest as the most
  significant bit, and a tie between the two paths into a state goes to the
  lower-numbered predecessor (the rule of the paper the program follows);
* a terminated block is traced back from state 0; a stream is traced back
  from its best state (lowest number among equals) at every chunk boundary,
  and the ``chunk`` positions that lie ``depth`` to ``depth + chunk`` steps
  behind that boundary are committed.

``precision="bfloat16"`` is the control: the same recursion, symbols and
path metrics held in bfloat16.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: A metric no reachable path has: the start constraint (state 0 only).
_UNREACHED = 1 << 28


@dataclasses.dataclass(frozen=True)
class Code:
    """Rate-1/n feed-forward code.  ``polys`` are K-bit generators whose
    most significant bit taps the current input."""

    constraint: int
    polys: Tuple[int, ...]

    @property
    def n_states(self) -> int:
        return 1 << (self.constraint - 1)

    def tables(self):
        """(sign (S, 2, n) of each branch's code bits as +-1, for the
        successor state s' = u * S/2 + v and predecessor 2v + j)."""
        K, S, n = self.constraint, self.n_states, len(self.polys)
        sign = np.zeros((S, 2, n), np.int32)
        for s in range(S):
            u, v = s >> (K - 2), s & (S // 2 - 1)
            for j in (0, 1):
                reg = (u << (K - 1)) | (2 * v + j)
                for k, g in enumerate(self.polys):
                    sign[s, j, k] = 2 * (bin(g & reg).count("1") & 1) - 1
        return sign


def encode(code: Code, bits: np.ndarray, terminate: bool) -> np.ndarray:
    """(B, N) info bits -> (B, N [+ K-1], n) coded bits, starting in state 0."""
    K = code.constraint
    if terminate:
        bits = np.concatenate([bits, np.zeros(bits.shape[:1] + (K - 1,), bits.dtype)], 1)
    T = bits.shape[1]
    pad = np.concatenate([np.zeros(bits.shape[:1] + (K - 1,), bits.dtype), bits], 1)
    out = np.zeros(bits.shape + (len(code.polys),), np.int8)
    for k, g in enumerate(code.polys):
        for d in range(K):  # tap d steps back: bit K-1-d of g
            if (g >> (K - 1 - d)) & 1:
                out[..., k] ^= pad[:, K - 1 - d:K - 1 - d + T].astype(np.int8)
    return out


def _forward(code: Code, y8: np.ndarray, precision: str, boundary: int = 0):
    """ACS over (B, T, n) integer symbols (units of 1/8).  Returns packed
    decisions (T, B, S/8) and, when ``boundary`` > 0, the best state after
    every ``boundary`` steps as (B, T // boundary)."""
    if precision not in ("exact", "bfloat16"):
        raise ValueError(f"unknown precision {precision!r}")
    dec, best = _forward_jit(code.constraint, tuple(code.polys), precision, boundary)(
        jnp.asarray(y8, jnp.int32)
    )
    return np.asarray(dec), np.asarray(best)


@functools.lru_cache(maxsize=None)
def _forward_jit(K: int, polys: Tuple[int, ...], precision: str, boundary: int):
    code = Code(K, polys)
    S = code.n_states
    sign = code.tables()  # (S, 2, n)
    even = np.arange(0, S, 2)
    pred0 = np.concatenate([even, even])  # predecessor 2v of s' = u*S/2 + v
    weights = np.array([128, 64, 32, 16, 8, 4, 2, 1], np.int32)  # np.packbits order
    exact = precision == "exact"
    dtype = jnp.int32 if exact else jnp.bfloat16

    def step(pm, y_t):  # pm (B, S); y_t (B, n)
        bm = jnp.einsum("bn,sjn->bsj", y_t.astype(dtype), jnp.asarray(sign, dtype),
                        preferred_element_type=dtype)
        c0 = pm[:, pred0] + bm[:, :, 0]
        c1 = pm[:, pred0 + 1] + bm[:, :, 1]
        take1 = c1 < c0
        pm = jnp.where(take1, c1, c0)
        packed = (take1.reshape(take1.shape[0], S // 8, 8).astype(jnp.int32) * weights).sum(-1)
        return pm, (packed.astype(jnp.uint8), jnp.argmin(pm, axis=1).astype(jnp.int32))

    @jax.jit
    def run(y8):
        B = y8.shape[0]
        if exact:
            pm0 = jnp.full((B, S), _UNREACHED, jnp.int32).at[:, 0].set(0)
            ys = y8
        else:
            pm0 = jnp.full((B, S), 1e30, dtype).at[:, 0].set(0)
            ys = (y8.astype(jnp.float32) / 8).astype(dtype)
        _, (dec, best) = jax.lax.scan(step, pm0, ys.swapaxes(0, 1))
        if boundary:
            best = best[boundary - 1::boundary].T
        else:
            best = best[:0].T
        return dec, best

    return run


def _walk(code: Code, dec: np.ndarray, state: np.ndarray, t_end: np.ndarray,
          steps: int) -> np.ndarray:
    """Trace back ``steps`` steps from ``state`` (B, m) at exclusive ends
    ``t_end`` (m,).  Returns the decoded bits (B, m, steps), oldest first."""
    K, S = code.constraint, code.n_states
    B = state.shape[0]
    rows = np.arange(B)[:, None]
    state = state.astype(np.int64).copy()
    out = np.zeros(state.shape + (steps,), np.int8)
    for i in range(steps):
        t = t_end - 1 - i  # (m,)
        ok = t >= 0
        byte = dec[np.where(ok, t, 0)[None, :], rows, state >> 3]
        j = (byte >> (7 - (state & 7))) & 1
        out[..., steps - 1 - i] = np.where(ok, state >> (K - 2), 0)
        state = np.where(ok, 2 * (state & (S // 2 - 1)) + j, state)
    return out


def decode_block(code: Code, y: np.ndarray, precision: str = "exact") -> np.ndarray:
    """Terminated blocks: (B, T, n) symbols on the 1/8 grid -> (B, T) bits."""
    y8 = np.rint(np.asarray(y, np.float32) * 8).astype(np.int32)
    dec, _ = _forward(code, y8, precision)
    T = y8.shape[1]
    start = np.zeros((y8.shape[0], 1), np.int64)
    return _walk(code, dec, start, np.array([T]), T)[:, 0]


def decode_windowed(code: Code, y: np.ndarray, *, chunk: int, depth: int,
                    precision: str = "exact") -> np.ndarray:
    """Open streams decoded ``chunk`` steps at a time with a truncated
    traceback of ``depth`` steps: (B, T, n) -> the (B, T - depth) bits
    committed once T steps (a multiple of ``chunk``) have been consumed."""
    y8 = np.rint(np.asarray(y, np.float32) * 8).astype(np.int32)
    B, T, _ = y8.shape
    if T % chunk:
        raise ValueError(f"T={T} is not a multiple of chunk={chunk}")
    dec, best = _forward(code, y8, precision, boundary=chunk)
    ends = np.arange(chunk, T + 1, chunk)  # every boundary
    bits = _walk(code, dec, best, ends, depth + chunk)[..., :chunk]  # (B, m, chunk)
    # the chunk committed at boundary e covers positions [e - depth - chunk, e - depth)
    first = ends - depth - chunk
    keep = first >= 0
    out = bits[:, keep].reshape(B, -1)
    return out[:, : T - depth]
