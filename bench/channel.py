"""Seeded traffic data: info bits, the standard's encoder, BPSK over AWGN,
and a 7-bit soft front end (the 1/8 grid clipped at +-4).

Everything is drawn on the device in one jitted call per shape from the
run's ``--seed``; the same seed gives the same symbols.  The encoder is the
benchmark's own (its generators' most significant bit taps the current
input, as IEEE 802.11 clause 17 and EN 300 421 draw them).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Q_STEP, Q_CLIP = 1.0 / 8.0, 4.0


def key(seed: int, *path: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    words = np.random.SeedSequence([int(seed), *map(int, path)]).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(words[0] >> 1)), int(words[1] >> 1))


@functools.lru_cache(maxsize=None)
def _generator(constraint: int, polys: Tuple[int, ...],
               puncture: Optional[Tuple[Tuple[int, ...], ...]], batch: int,
               n_info: int, terminate: bool, sigma: float):
    K, n = constraint, len(polys)

    @jax.jit
    def gen(k):
        kb, kn = jax.random.split(k)
        bits = jax.random.bernoulli(kb, 0.5, (batch, n_info)).astype(jnp.int32)
        u = jnp.pad(bits, ((0, 0), (K - 1, K - 1 if terminate else 0)))
        T = u.shape[1] - (K - 1)
        coded = []
        for g in polys:
            c = jnp.zeros((batch, T), jnp.int32)
            for d in range(K):  # tap d steps back: bit K-1-d of g
                if (g >> (K - 1 - d)) & 1:
                    c = c ^ u[:, K - 1 - d:K - 1 - d + T]
            coded.append(c)
        coded = jnp.stack(coded, -1)  # (B, T, n)
        y = 1.0 - 2.0 * coded + sigma * jax.random.normal(kn, coded.shape)
        y = jnp.clip(jnp.round(y / Q_STEP) * Q_STEP, -Q_CLIP, Q_CLIP)
        if puncture is not None:
            pat = np.asarray(puncture)  # (n, period)
            mask = np.tile(pat.T, (-(-T // pat.shape[1]), 1))[:T]
            y = y * jnp.asarray(mask, jnp.float32)  # erasures read as 0
        return bits.astype(jnp.int8), y.astype(jnp.float32)

    return gen


def sigma(ebn0_db: float, rate: float) -> float:
    """Noise deviation per real dimension for unit-energy BPSK symbols."""
    return float(np.sqrt(1.0 / (2.0 * rate * 10.0 ** (ebn0_db / 10.0))))


def received(code: dict, channel: dict, batch: int, n_info: int, terminate: bool,
             k: jax.Array):
    """(info bits (B, N) int8, received symbols (B, T, n) float32) on the
    device.  ``code`` and ``channel`` are a configuration's groups."""
    polys = tuple(int(p, 8) for p in code["polys_octal"])
    punct = code.get("puncture")
    punct = None if punct is None else tuple(tuple(r) for r in punct)
    rate = 1.0 / len(polys)
    if punct is not None:
        rate = len(punct[0]) / float(np.sum(punct))
    gen = _generator(int(code["constraint"]), polys, punct, batch, n_info, terminate,
                     sigma(float(channel["ebn0_db"]), rate))
    return gen(k)
