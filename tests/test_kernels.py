"""Per-kernel validation: every Pallas kernel swept over shapes/dtypes and
asserted allclose against the ref.py pure-jnp oracle (interpret mode on CPU,
per the assignment)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import CODE_K3_PAPER, CODE_K3_STD, CODE_K5_GSM, CODE_K7_NASA
from repro.core.trellis import NEG_UNREACHABLE
from repro.kernels import (
    minplus_matmul_op,
    texpand_op,
    unpack_survivors,
    viterbi_decode_packed,
    viterbi_forward_weighted_op,
)
from repro.kernels.common import PACK_BITS
from repro.kernels.ref import minplus_matmul_ref, texpand_ref, viterbi_scan_ref
from repro.kernels.viterbi_scan import table_weights

CODES = {"k3": CODE_K3_STD, "k3p": CODE_K3_PAPER, "k5": CODE_K5_GSM, "k7": CODE_K7_NASA}


# --------------------------------------------------------------------------- #
# texpand (one fused ACS step)                                                 #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("code_id", list(CODES))
@pytest.mark.parametrize("B", [1, 8, 128, 200])  # 200: exercises lane padding
def test_texpand_matches_ref(code_id, B, rng):
    code = CODES[code_id]
    S, M = code.n_states, code.n_symbols
    pm = jax.random.normal(rng, (B, S), jnp.float32) * 10
    bm = jax.random.uniform(jax.random.fold_in(rng, 1), (B, M), jnp.float32, 0, 2)
    new_pm, bp = texpand_op(code, pm, bm)
    ref_pm, ref_bp = texpand_ref(code, pm.T, bm.T)
    np.testing.assert_allclose(np.asarray(new_pm), np.asarray(ref_pm.T), rtol=1e-6)
    assert (np.asarray(bp) == np.asarray(ref_bp.T)).all()


def test_texpand_tie_break(rng):
    """Kernel preserves the paper's lowest-state tie rule (strict <)."""
    code = CODE_K3_STD
    pm = jnp.zeros((8, code.n_states))
    bm = jnp.zeros((8, code.n_symbols))
    _, bp = texpand_op(code, pm, bm)
    assert (bp == 0).all()


# --------------------------------------------------------------------------- #
# viterbi_scan (full-sequence forward, packed survivors)                       #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("code_id", ["k3", "k5", "k7"])
@pytest.mark.parametrize(
    "B,T",  # lane padding (130), partial last words, whole words
    [(1, 4), (8, 31), (130, 16), (1, 7), (8, 64), (130, 33)],
)
def test_viterbi_scan_matches_ref(code_id, B, T, rng):
    """The packed forward scan: final metrics and every survivor bit (once
    unpacked) equal the one-step oracle iterated T times."""
    code = CODES[code_id]
    M, S = code.n_symbols, code.n_states
    bm = jax.random.uniform(rng, (B, T, M), jnp.float32, 0, 2)
    final_pm, packed = viterbi_forward_weighted_op(
        code, None, bm, table_weights(code)
    )
    assert packed.shape == (-(-T // PACK_BITS), B, S)
    pm0 = jnp.full((S, B), NEG_UNREACHABLE, jnp.float32).at[0].set(0.0)
    ref_pm, ref_bps = viterbi_scan_ref(code, bm.transpose(1, 2, 0), pm0)
    ref_pm = jnp.minimum(ref_pm, NEG_UNREACHABLE)
    np.testing.assert_allclose(
        np.asarray(final_pm), np.asarray(ref_pm.T), rtol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(unpack_survivors(packed, T)),
        np.asarray(ref_bps.transpose(0, 2, 1)),
    )


def test_fused_decoder_equals_reference_decoder(rng):
    from repro.core import bsc, encode, hard_branch_metrics, viterbi_decode

    code = CODE_K5_GSM
    bits = jax.random.bernoulli(rng, 0.5, (32, 60)).astype(jnp.int32)
    coded = encode(code, bits, terminate=True)
    rx = bsc(jax.random.fold_in(rng, 1), coded, 0.03)
    bm = hard_branch_metrics(code, rx)
    d_ref, m_ref = viterbi_decode(code, bm)
    d_fused, m_fused = viterbi_decode_packed(code, bm)
    assert jnp.allclose(m_ref, m_fused)
    assert (d_ref == d_fused).all()


# --------------------------------------------------------------------------- #
# minplus matmul                                                               #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("shape", [(1, 4, 4, 4), (2, 8, 16, 8), (3, 130, 64, 70)])
def test_minplus_matches_ref(shape, rng):
    N, I, K, J = shape
    a = jax.random.normal(rng, (N, I, K)) * 5
    b = jax.random.normal(jax.random.fold_in(rng, 1), (N, K, J)) * 5
    out = minplus_matmul_op(a, b)
    ref = minplus_matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(1, 3), i=st.integers(1, 12), k=st.integers(1, 12),
    j=st.integers(1, 12), seed=st.integers(0, 2 ** 16),
)
def test_minplus_property(n, i, k, j, seed):
    key = jax.random.PRNGKey(seed)
    a = jax.random.normal(key, (n, i, k)) * 3
    b = jax.random.normal(jax.random.fold_in(key, 1), (n, k, j)) * 3
    out = minplus_matmul_op(a, b)
    ref = minplus_matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_minplus_associativity(seed):
    """(A⊗B)⊗C == A⊗(B⊗C) in (min,+): the property the block-parallel and
    sequence-parallel decoders rely on."""
    key = jax.random.PRNGKey(seed)
    mats = [jax.random.normal(jax.random.fold_in(key, i), (1, 4, 4)) * 3
            for i in range(3)]
    ab_c = minplus_matmul_op(minplus_matmul_op(mats[0], mats[1]), mats[2])
    a_bc = minplus_matmul_op(mats[0], minplus_matmul_op(mats[1], mats[2]))
    np.testing.assert_allclose(np.asarray(ab_c), np.asarray(a_bc), rtol=1e-4, atol=1e-4)
