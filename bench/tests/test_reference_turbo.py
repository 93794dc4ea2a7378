"""The plain LTE turbo reference against second witnesses: its encoder
against the program's, its decoder against the program's own oracle
(kernels/ref.turbo_decode_ref, written apart from it), and the bfloat16
control against it, at K=40 and K=64 on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference_turbo as rt

TRELLIS = rt.Trellis("13", "15")


def _program_spec(k, f1, f2):
    from repro.siso import QPPInterleaver, RSC_K4_LTE, TurboSpec

    return TurboSpec(RSC_K4_LTE, QPPInterleaver(k, f1, f2), iterations=8, tail="36.212")


def _received(k, f1, f2, batch, seed, ebn0_db=1.0):
    perm = rt.qpp(k, f1, f2)
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2, (batch, k)).astype(np.int8)
    coded = rt.encode(TRELLIS, perm, bits)
    sigma = np.sqrt(1.0 / (2.0 * k / (3.0 * (k + 4)) * 10 ** (ebn0_db / 10)))
    y = 1.0 - 2.0 * coded + sigma * rng.standard_normal(coded.shape)
    return perm, bits, np.clip(np.round(y * 8) / 8, -4, 4).astype(np.float32)


@pytest.mark.parametrize("k, f1, f2", [(40, 3, 10), (64, 7, 16), (6144, 263, 480)])
def test_encoder_matches_the_program_encoder(k, f1, f2):
    perm = rt.qpp(k, f1, f2)
    bits = np.random.default_rng(k).integers(0, 2, (3, k)).astype(np.int8)
    want = rt.encode(TRELLIS, perm, bits)
    spec = _program_spec(k, f1, f2)
    np.testing.assert_array_equal(perm, spec.interleaver.permutation)
    got = np.asarray(spec.encode(jnp.asarray(bits, jnp.int32)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("k, f1, f2, seed", [(40, 3, 10, 1), (64, 7, 16, 2)])
def test_decoder_matches_the_program_oracle(k, f1, f2, seed):
    from repro.kernels.ref import turbo_decode_ref

    perm, bits, y = _received(k, f1, f2, 8, seed, ebn0_db=2.0)
    want, n_want, frozen_want = turbo_decode_ref(
        _program_spec(k, f1, f2).code, perm, jnp.asarray(y), tail="36.212", iterations=8)
    got, n_got, frozen_got = rt.decode(TRELLIS, perm, y, iterations=8, scale=0.7)
    assert n_got == n_want
    np.testing.assert_array_equal(frozen_got, np.asarray(frozen_want))
    err, wrong, n = rt.compare(got, got < 0, np.asarray(want), margin=0.01)
    assert err < 1e-4 and wrong == 0 and n == 8 * k
    assert ((got < 0) != bits).mean() < 0.1  # it decodes, too


def test_split_decode_equals_one_process():
    perm, _, y = _received(40, 3, 10, 6, 3)
    one = rt.decode(TRELLIS, perm, y, iterations=8, scale=0.7)
    split = rt.decode_split(TRELLIS, perm, y, rows_per_worker=2, iterations=8, scale=0.7)
    np.testing.assert_array_equal(one[0], split[0])
    np.testing.assert_array_equal(one[2], split[2])


def test_control_differs_from_the_reference():
    """The bfloat16 control reads LLRs far from the float32 reference's."""
    perm, _, y = _received(64, 7, 16, 8, 4)
    exact, _, _ = rt.decode(TRELLIS, perm, y, iterations=8, scale=0.7)
    control, _, _ = rt.decode(TRELLIS, perm, y, iterations=8, scale=0.7,
                              precision="bfloat16")
    err, _, _ = rt.compare(control, control < 0, exact, margin=0.01)
    assert err > 1e-3
