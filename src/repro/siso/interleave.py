"""Interleavers as hashable specs.

A turbo code is defined by its constituent RSC code *and* its interleaver,
so the interleaver must be part of the hashable TurboSpec the jit caches
key on.  Both kinds here are frozen dataclasses of ints whose permutation
tables are derived lazily (cached) — the spec itself stays tiny and
hashable, like ConvCode/RSCCode.

Convention: ``interleaved[k] = natural[permutation[k]]`` — i.e.
``interleave(x) = x[perm]`` and ``deinterleave(y) = y[inverse]``.
"""
from __future__ import annotations

import dataclasses
from functools import cached_property
from typing import Dict, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class BlockInterleaver:
    """Classic row-column interleaver: write row-major into a (rows, cols)
    matrix, read column-major."""

    rows: int
    cols: int

    def __post_init__(self):
        if self.rows < 1 or self.cols < 1:
            raise ValueError("rows and cols must be positive")

    @property
    def n(self) -> int:
        return self.rows * self.cols

    @cached_property
    def permutation(self) -> np.ndarray:
        k = np.arange(self.n)
        # k-th read (column-major) hits element (k % rows, k // rows)
        return ((k % self.rows) * self.cols + k // self.rows).astype(np.int32)

    @cached_property
    def inverse(self) -> np.ndarray:
        return np.argsort(self.permutation).astype(np.int32)


@dataclasses.dataclass(frozen=True)
class QPPInterleaver:
    """Quadratic permutation polynomial interleaver (the LTE turbo family):
    ``pi(k) = (f1*k + f2*k^2) mod n``.

    Contention-free and maximally spread for the standardized (n, f1, f2)
    triples; the constructor verifies the polynomial actually permutes
    [0, n) so a bad triple fails loudly at spec-construction time.
    """

    n: int
    f1: int
    f2: int

    def __post_init__(self):
        if self.n < 2:
            raise ValueError("interleaver length must be >= 2")
        perm = self._compute()
        if len(np.unique(perm)) != self.n:
            raise ValueError(
                f"(f1={self.f1}, f2={self.f2}) is not a permutation polynomial "
                f"mod {self.n}"
            )

    def _compute(self) -> np.ndarray:
        k = np.arange(self.n, dtype=np.int64)
        return ((self.f1 * k + self.f2 * k * k) % self.n).astype(np.int32)

    @cached_property
    def permutation(self) -> np.ndarray:
        return self._compute()

    @cached_property
    def inverse(self) -> np.ndarray:
        return np.argsort(self.permutation).astype(np.int32)


#: 3GPP TS 36.212 Table 5.1.3-3, turbo code internal interleaver parameters:
#: code-block size K -> (f1, f2), each with its row index i of the table.
#: Only the rows this repository states are here (no copy of the table is
#: fetched); add a size only with its row.
LTE_QPP: Dict[int, Tuple[int, int]] = {
    40: (3, 10),      # i = 1
    64: (7, 16),      # i = 4
    512: (31, 64),    # i = 60
    6144: (263, 480),  # i = 188
}


def lte_qpp(k: int) -> QPPInterleaver:
    """The 36.212 QPP interleaver of code-block size ``k`` (Table 5.1.3-3)."""
    try:
        f1, f2 = LTE_QPP[k]
    except KeyError:
        raise ValueError(
            f"no 36.212 QPP row for K={k} here (known: {sorted(LTE_QPP)})"
        ) from None
    return QPPInterleaver(k, f1, f2)
