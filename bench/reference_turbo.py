"""Plain LTE turbo reference for the benchmark's ``correct`` check.

It imports nothing of the program under test.  NumPy, float32:

* ``encode``: the encoder of 3GPP TS 36.212 5.1.3.2.  Each constituent is a
  three-cell shift register with feedback g0 and parity g1 (given in octal,
  most significant bit the D^0 tap: 13 is 1 + D^2 + D^3, 15 is 1 + D + D^3),
  starting at zero; encoder 2 reads the input through the QPP interleaver,
  c'_i = c_pi(i).  Trellis termination (5.1.3.2.2): three tail steps per
  encoder, encoder 1 first, each taking its input from its own feedback, and
  the 12 tail bits multiplexed into rows K..K+3 as
  d0 = x_K, z_K+1, x'_K, z'_K+1;  d1 = z_K, x_K+2, z'_K, x'_K+2;
  d2 = x_K+1, z_K+2, x'_K+1, z'_K+2.
* ``decode``: max-log-MAP on each constituent (the min-sum form of BCJR;
  ``lambda = log P(0)/P(1)``, a branch costs u (lambda_x + lambda_a) +
  z lambda_z), both trellises seeded and ended at state 0 over the K + 3
  steps that hold their tails, a-priori 0 on the tail steps.  Iterations
  exchange extrinsic values scaled by ``scale`` over the first K positions
  only.  A stream whose decisions equal the previous iteration's is frozen:
  its extrinsic input is held from then on.  With ``early_exit`` the loop
  stops once every stream froze.  The standard specifies the encoder only;
  max-log, the scale and the freeze rule are the receiver's choices, the
  ones the configuration states.

``precision="bfloat16"`` is the control: the same decoder with every LLR
and metric rounded to bfloat16 after each operation.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

_INF = np.float32(np.inf)


def _taps(octal: str, memory: int) -> np.ndarray:
    """(memory + 1,) 0/1 coefficients of D^0 .. D^memory."""
    g = int(octal, 8)
    return np.array([(g >> (memory - d)) & 1 for d in range(memory + 1)], np.int64)


class Trellis:
    """An RSC constituent of ``memory`` cells, state = (D1 .. Dm) as bits
    m-1 .. 0 of an integer (D1, the newest cell, most significant)."""

    def __init__(self, feedback_octal: str, parity_octal: str, memory: int = 3):
        self.m = memory
        g0, g1 = _taps(feedback_octal, memory), _taps(parity_octal, memory)
        S = self.n_states = 1 << memory
        self.next = np.zeros((S, 2), np.int64)
        self.parity = np.zeros((S, 2), np.int64)
        self.tail_input = np.zeros(S, np.int64)
        for s in range(S):
            cells = [(s >> (memory - 1 - i)) & 1 for i in range(memory)]  # D1..Dm
            fb = sum(g0[i + 1] * cells[i] for i in range(memory)) & 1
            self.tail_input[s] = fb
            for u in (0, 1):
                a = u ^ fb
                self.parity[s, u] = (a * g1[0]
                                     + sum(g1[i + 1] * cells[i] for i in range(memory))) & 1
                self.next[s, u] = (a << (memory - 1)) | (s >> 1)

    def run(self, bits: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(B, K) input -> (systematic (B, K + m), parity (B, K + m)), the
        last m columns the tail, which ends every row in state 0."""
        B, K = bits.shape
        s = np.zeros(B, np.int64)
        x = np.zeros((B, K + self.m), np.int8)
        z = np.zeros((B, K + self.m), np.int8)
        for k in range(K + self.m):
            u = bits[:, k].astype(np.int64) if k < K else self.tail_input[s]
            x[:, k], z[:, k] = u, self.parity[s, u]
            s = self.next[s, u]
        assert not s.any(), "the tail did not terminate the trellis"
        return x, z


def qpp(k: int, f1: int, f2: int) -> np.ndarray:
    """36.212 5.1.3.2.3: pi(i) = (f1 i + f2 i^2) mod K."""
    i = np.arange(k, dtype=np.int64)
    return (f1 * i + f2 * i * i) % k


def encode(trellis: Trellis, perm: np.ndarray, bits: np.ndarray) -> np.ndarray:
    """(B, K) info bits -> (B, K + 4, 3) coded bits d0, d1, d2."""
    B, K = bits.shape
    x, z = trellis.run(bits)
    x2, z2 = trellis.run(bits[:, perm])
    out = np.zeros((B, K + 4, 3), np.int8)
    out[:, :K, 0], out[:, :K, 1], out[:, :K, 2] = x[:, :K], z[:, :K], z2[:, :K]
    t = [x[:, K], z[:, K], x[:, K + 1], z[:, K + 1], x[:, K + 2], z[:, K + 2],
         x2[:, K], z2[:, K], x2[:, K + 1], z2[:, K + 1], x2[:, K + 2], z2[:, K + 2]]
    for j, col in enumerate(t):  # 5.1.3.2.2's order, row by row
        out[:, K + j // 3, j % 3] = col
    return out


def _rounder(precision: str):
    if precision == "float32":
        return lambda a: a
    if precision == "bfloat16":
        import ml_dtypes

        return lambda a: np.asarray(a, ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"unknown precision {precision!r}")


def siso(trellis: Trellis, sys: np.ndarray, par: np.ndarray, apriori: np.ndarray,
         rnd) -> np.ndarray:
    """Max-log-MAP a-posteriori LLRs (B, T) of a terminated constituent
    over T steps; ``apriori`` is zero on its tail steps.

    Metrics are (S, B).  A branch costs one of four values, u (lambda_x +
    lambda_a) + z lambda_z for u, z in {0, 1}, picked per step by a table.
    A state's successors under new register bit a are a * S/2 + (s >> 1),
    so both recursions are broadcasts over (S/2, 2) views."""
    B, T = sys.shape
    S, h = trellis.n_states, trellis.n_states // 2
    fb = trellis.tail_input  # the feedback: u = a XOR fb(s)
    states = np.arange(S)
    # (a, s) -> index 2u + z of the branch leaving s under register bit a
    label = np.stack([2 * (fb ^ a) + trellis.parity[states, fb ^ a] for a in (0, 1)])
    # into s' = a * h + v from p = 2v + j, as (j, a, v)
    label_in = label.reshape(2, h, 2).transpose(2, 0, 1)
    xa = rnd(sys + apriori).T  # (T, B)
    lz = par.T
    costs = np.stack([np.zeros_like(xa), lz, xa, rnd(xa + lz)], axis=1)  # (T, 4, B)
    alpha = np.empty((T, S, B), np.float32)
    a = np.full((S, B), _INF)
    a[0] = 0
    for t in range(T):
        alpha[t] = a
        g = costs[t][label_in]
        a = np.minimum(rnd(a[None, 0::2] + g[0]), rnd(a[None, 1::2] + g[1])).reshape(S, B)
        a = rnd(a - a.min(axis=0))
    llr = np.empty((B, T), np.float32)
    b = np.full((S, B), _INF)
    b[0] = 0
    is_one = fb.astype(bool)[:, None]  # where a = 0 carries u = 1
    for t in range(T - 1, -1, -1):
        g = costs[t][label].reshape(2, h, 2, B)
        c0 = rnd(g[0] + b[:h, None]).reshape(S, B)  # a = 0: to s >> 1
        c1 = rnd(g[1] + b[h:, None]).reshape(S, B)  # a = 1: to h + (s >> 1)
        t0, t1 = rnd(alpha[t] + c0), rnd(alpha[t] + c1)
        llr[:, t] = rnd(np.where(is_one, t0, t1).min(axis=0)
                        - np.where(is_one, t1, t0).min(axis=0))
        b = np.minimum(c0, c1)
        b = rnd(b - b.min(axis=0))
    return llr


def decode(trellis: Trellis, perm: np.ndarray, y: np.ndarray, *, iterations: int,
           scale: float, early_exit: bool = True,
           precision: str = "float32") -> Tuple[np.ndarray, int, np.ndarray]:
    """(B, K + 4, 3) channel LLRs (the received soft symbols: max-log is
    scale-free) -> (LLRs (B, K) of the last iteration run, iterations run,
    (B,) streams frozen)."""
    rnd = _rounder(precision)
    y = rnd(np.asarray(y, np.float32))
    B, T, _ = y.shape
    K = T - 4
    inv = np.argsort(perm)
    m = trellis.m
    flat = y[:, K:].reshape(B, 12)  # tail LLRs in the order encode() wrote them
    tail_x1, tail_z1 = flat[:, [0, 2, 4]], flat[:, [1, 3, 5]]
    tail_x2, tail_z2 = flat[:, [6, 8, 10]], flat[:, [7, 9, 11]]
    x, z1, z2 = y[:, :K, 0], y[:, :K, 1], y[:, :K, 2]
    x2 = x[:, perm]
    sys1 = np.concatenate([x, tail_x1], 1)
    par1 = np.concatenate([z1, tail_z1], 1)
    sys2 = np.concatenate([x2, tail_x2], 1)
    par2 = np.concatenate([z2, tail_z2], 1)
    zeros = np.zeros((B, m), np.float32)
    scale = np.float32(scale)
    ext2 = np.zeros((B, K), np.float32)  # extrinsic of decoder 2, interleaved order
    frozen = np.zeros(B, bool)
    prev = llr = None
    n_run = 0
    for _ in range(iterations):
        a1 = ext2[:, inv]
        l1 = siso(trellis, sys1, par1, np.concatenate([a1, zeros], 1), rnd)[:, :K]
        e1 = rnd(scale * rnd(rnd(l1 - x) - a1))
        a2 = e1[:, perm]
        l2 = siso(trellis, sys2, par2, np.concatenate([a2, zeros], 1), rnd)[:, :K]
        llr = l2[:, inv]
        bits = llr < 0
        if prev is not None:
            frozen |= (bits == prev).all(axis=1)
        new = rnd(scale * rnd(rnd(l2 - x2) - a2))
        ext2 = np.where(frozen[:, None], ext2, new)
        prev = bits
        n_run += 1
        if early_exit and frozen.all():
            break
    return llr, n_run, frozen


def decode_split(trellis: Trellis, perm: np.ndarray, y: np.ndarray, *,
                 rows_per_worker: int = 64, **kw) -> Tuple[np.ndarray, int, np.ndarray]:
    """``decode`` of groups of ``rows_per_worker`` streams in worker
    processes (spawned: they import NumPy and this module only).  Streams
    are independent and a frozen stream replays its decisions, so the LLRs
    are ``decode``'s; the iterations run are the most any group ran."""
    import multiprocessing
    import os
    from concurrent.futures import ProcessPoolExecutor

    parts = np.array_split(y, max(1, -(-len(y) // rows_per_worker)))
    if len(parts) == 1:
        return decode(trellis, perm, y, **kw)
    workers = min(len(parts), os.cpu_count() or 1)
    with ProcessPoolExecutor(workers, mp_context=multiprocessing.get_context("spawn")) as ex:
        out = list(ex.map(_decode_part, [(trellis, perm, p, kw) for p in parts]))
    llr, n_run, frozen = zip(*out)
    return np.concatenate(llr), max(n_run), np.concatenate(frozen)


def _decode_part(args):
    trellis, perm, y, kw = args
    return decode(trellis, perm, y, **kw)


def compare(got_llr: np.ndarray, got_bits: np.ndarray, want_llr: np.ndarray,
            margin: float) -> Tuple[float, int, int]:
    """(max |L_got - L_want| / (1 + |L_want|), decided bits that differ
    from the reference's where |L_want| >= margin, bits compared), over
    (B, K) LLRs and the program's decided bits (1 where it decides 1)."""
    g, w = np.asarray(got_llr, np.float64), np.asarray(want_llr, np.float64)
    err = float(np.max(np.abs(g - w) / (1 + np.abs(w))))
    wrong = int((np.asarray(got_bits) != (w < 0))[np.abs(w) >= margin].sum())
    return err, wrong, w.size
