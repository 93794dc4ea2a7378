"""Rate-compatible punctured convolutional codes.

The paper's Texpand targets rate-1/2 codes; real systems (GSM/LTE/DVB — the
paper's digital-TV motivation) derive higher rates by *puncturing*: deleting
coded bits by a periodic pattern at the transmitter and treating them as
erasures at the receiver.  Erasure handling costs nothing in our decoder:
punctured positions contribute 0 to every branch metric, so the SAME fused
ACS kernels decode any punctured rate.

Patterns are (n_out, period) 0/1 arrays; e.g. rate-2/3 from rate-1/2:
P = [[1, 1], [1, 0]] — every second bit of the second stream is dropped.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core.trellis import ConvCode

# standard patterns (period aligned per input bit)
PUNCTURE_2_3 = np.array([[1, 1], [1, 0]])
PUNCTURE_3_4 = np.array([[1, 1, 0], [1, 0, 1]])
PUNCTURE_5_6 = np.array([[1, 1, 0, 1, 0], [1, 0, 1, 0, 1]])

#: WIMAX-style turbo puncturing over the [systematic, parity1, parity2]
#: streams: keep every systematic bit, alternate the parities -> rate 1/2
#: from the rate-1/3 mother turbo code.
PUNCTURE_TURBO_1_2 = np.array([[1, 1], [1, 0], [0, 1]])


def puncture(code: ConvCode, coded_bits: jnp.ndarray, pattern: np.ndarray
             ) -> jnp.ndarray:
    """Apply a puncture mask.  coded_bits: (..., T, n_out) -> masked flat
    stream is what a transmitter would send; here we return the (…, T,
    n_out) array with punctured positions REMOVED semantics left to the
    receiver by carrying the mask (see depuncture_metrics)."""
    T = coded_bits.shape[-2]
    mask = pattern_mask(code, T, pattern)
    return coded_bits * mask  # punctured positions zeroed (not transmitted)


def pattern_mask(code, T: int, pattern: np.ndarray) -> jnp.ndarray:
    """(T, n_out) 0/1 mask from a (n_out, period) pattern.

    ``code`` is anything with an ``n_out`` (ConvCode, RSCCode) or a bare int
    stream count — the turbo specs mask 1 + 2*n_parity streams, which belong
    to no single trellis.
    """
    return jnp.asarray(host_pattern_mask(code, T, pattern))


def host_pattern_mask(code, T: int, pattern: np.ndarray) -> np.ndarray:
    """:func:`pattern_mask` as a float32 NumPy array, for inputs still on
    the host."""
    n_out = code if isinstance(code, int) else code.n_out
    n, period = pattern.shape
    assert n == n_out, (n, n_out)
    reps = -(-T // period)
    return np.tile(pattern.T, (reps, 1))[:T].astype(np.float32)  # (T, n_out)


def punctured_hard_metrics(code: ConvCode, received_bits: jnp.ndarray,
                           pattern: np.ndarray) -> jnp.ndarray:
    """Hamming branch metrics with punctured positions as erasures.

    received_bits: (..., T, n_out) where punctured positions are arbitrary.
    Returns (..., T, n_symbols): per-symbol distance counting ONLY
    transmitted positions.
    """
    T = received_bits.shape[-2]
    mask = pattern_mask(code, T, pattern)  # (T, n)
    bits = jnp.asarray(code.symbol_bits)  # (M, n)
    r = received_bits.astype(jnp.float32)[..., None, :]  # (..., T, 1, n)
    diff = jnp.abs(r - bits[None, :, :])  # (..., T, M, n)
    return (diff * mask[:, None, :]).sum(-1)


def effective_rate(code: ConvCode, pattern: np.ndarray) -> float:
    """k/n after puncturing: period input bits -> surviving coded bits."""
    period = pattern.shape[1]
    return period / float(pattern.sum())
