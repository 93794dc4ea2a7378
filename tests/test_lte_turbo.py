"""The LTE turbo code of 3GPP TS 36.212 5.1.3.2 with its trellis termination:
the encoder's tail layout, and decode() of terminated blocks against the
plain oracle (kernels/ref.turbo_decode_ref), at K=40 and K=64 (Table
5.1.3-3 rows 1 and 4) with seeded bits and noise, all on the CPU."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.decode import decode, plan_decode
from repro.kernels.ref import turbo_decode_ref
from repro.obs import Telemetry
from repro.siso import RSC_K4_LTE, QPPInterleaver, TurboSpec
from repro.siso.interleave import LTE_QPP, lte_qpp

#: max |L - L_ref| / (1 + |L_ref|): float32 rounding of another summation
#: order (one-hot matrix products against plain sums), over 8 iterations
LLR_TOL = 1e-4
#: below this |L_ref| an LLR's sign is rounding, and decisions may differ
MARGIN = 1e-2


def _spec(k, **kw):
    return TurboSpec(RSC_K4_LTE, lte_qpp(k), iterations=8, tail="36.212", **kw)


def _received(spec, batch, seed, ebn0_db):
    """Seeded bits and BPSK over AWGN at ``ebn0_db`` (Eb per information
    bit), soft symbols on the 1/8 grid clipped at +-4."""
    k = spec.block_len
    key = jax.random.PRNGKey(seed)
    bits = jax.random.bernoulli(key, 0.5, (batch, k)).astype(jnp.int32)
    rate = k / (3.0 * (k + 4))
    sigma = np.sqrt(1.0 / (2.0 * rate * 10 ** (ebn0_db / 10)))
    coded = spec.encode(bits)
    y = 1.0 - 2.0 * coded + sigma * jax.random.normal(jax.random.fold_in(key, 1), coded.shape)
    return bits, jnp.clip(jnp.round(y * 8) / 8, -4.0, 4.0)


def _rsc_by_hand(u):
    """36.212 Figure 5.1.3-2's constituent, one bit at a time: g0 = 1 + D^2
    + D^3, g1 = 1 + D + D^3, then three tail steps fed from the feedback.
    Returns the systematic and parity lists, K + 3 long each."""
    d = [0, 0, 0]  # D1, D2, D3
    xs, zs = [], []
    for k in range(len(u) + 3):
        fb = d[1] ^ d[2]
        x = u[k] if k < len(u) else fb  # the tail's switch: input = feedback
        a = x ^ fb
        xs.append(x)
        zs.append(a ^ d[0] ^ d[2])
        d = [a, d[0], d[1]]
    assert d == [0, 0, 0]
    return xs, zs


def test_qpp_rows_and_tail_spec():
    assert LTE_QPP[40] == (3, 10) and LTE_QPP[6144] == (263, 480)
    assert lte_qpp(64) == QPPInterleaver(64, 7, 16)
    with pytest.raises(ValueError, match="no 36.212 QPP row"):
        lte_qpp(41)
    spec = _spec(40)
    assert spec.terminated and spec.n_tail_rows == 4 and spec.n_steps(40) == 44
    assert not TurboSpec().terminated and TurboSpec().n_steps(64) == 64
    with pytest.raises(ValueError, match="tail must be"):
        dataclasses.replace(spec, tail="36.213")
    with pytest.raises(ValueError, match="36.212 tail layout"):
        TurboSpec(interleaver=lte_qpp(64), tail="36.212")  # K=3 constituents


def test_encoder_tail_layout_by_hand():
    u = [int(b) for b in np.random.default_rng(40).integers(0, 2, 40)]
    spec = _spec(40)
    got = np.asarray(spec.encode(jnp.asarray([u])))[0]
    assert got.shape == (44, 3)
    perm = spec.interleaver.permutation
    x, z = _rsc_by_hand(u)
    x2, z2 = _rsc_by_hand([u[p] for p in perm])
    K = 40
    np.testing.assert_array_equal(got[:K, 0], x[:K])
    np.testing.assert_array_equal(got[:K, 1], z[:K])
    np.testing.assert_array_equal(got[:K, 2], z2[:K])
    # 5.1.3.2.2: the tail rows K..K+3 of d0, d1, d2
    np.testing.assert_array_equal(got[K:, 0], [x[K], z[K + 1], x2[K], z2[K + 1]])
    np.testing.assert_array_equal(got[K:, 1], [z[K], x[K + 2], z2[K], x2[K + 2]])
    np.testing.assert_array_equal(got[K:, 2], [x[K + 1], z[K + 2], x2[K + 1], z2[K + 2]])


@pytest.mark.parametrize("k", [40, 64])
def test_noiseless_blocks_decode_exactly(k):
    spec = _spec(k)
    bits = jax.random.bernoulli(jax.random.PRNGKey(k), 0.5, (4, k)).astype(jnp.int32)
    res = decode(spec, 1.0 - 2.0 * spec.encode(bits))
    assert res.plan.backend == "turbo" and res.diagnostics["iterations"] < 8
    np.testing.assert_array_equal(np.asarray(res.info_bits), np.asarray(bits))


@pytest.mark.parametrize("k, seed", [(40, 1), (64, 2)])
def test_terminated_decode_matches_the_oracle(k, seed):
    spec = _spec(k)
    _, rx = _received(spec, 8, seed, ebn0_db=1.0)
    res = decode(spec, rx)
    want, n_run, frozen = turbo_decode_ref(
        spec.code, spec.interleaver.permutation, rx, tail="36.212", iterations=8)
    got, want = np.asarray(res.diagnostics["llr"]), np.asarray(want)
    assert res.diagnostics["iterations"] == n_run
    np.testing.assert_array_equal(np.asarray(res.diagnostics["converged"]), np.asarray(frozen))
    assert np.max(np.abs(got - want) / (1 + np.abs(want))) < LLR_TOL
    sure = np.abs(want) >= MARGIN
    np.testing.assert_array_equal((got < 0)[sure], (want < 0)[sure])
    np.testing.assert_array_equal(np.asarray(res.bits), (got < 0).astype(np.int32))


def test_the_tail_changes_the_decode():
    """The tails carry information: the open-trellis decode of the same
    information rows reads other LLRs at the block's end."""
    spec = _spec(40)
    _, rx = _received(spec, 4, 5, ebn0_db=1.0)
    term, _, _ = turbo_decode_ref(spec.code, spec.interleaver.permutation, rx,
                                  tail="36.212", iterations=8)
    open_, _, _ = turbo_decode_ref(spec.code, spec.interleaver.permutation, rx[:, :40],
                                   tail="none", iterations=8)
    assert float(jnp.max(jnp.abs(term - open_))) > 0.1


def test_early_exit_is_bit_exact_with_fixed_iterations_under_termination():
    spec = _spec(64)
    _, rx = _received(spec, 8, 3, ebn0_db=1.0)
    ee = decode(spec, rx)
    fixed = decode(dataclasses.replace(spec, early_exit=False), rx)
    assert fixed.diagnostics["iterations"] == 8
    assert ee.diagnostics["iterations"] <= 8
    np.testing.assert_array_equal(np.asarray(ee.bits), np.asarray(fixed.bits))
    np.testing.assert_array_equal(np.asarray(ee.diagnostics["llr"]),
                                  np.asarray(fixed.diagnostics["llr"]))


def test_same_bits_with_and_without_a_tracer():
    spec = _spec(40)
    _, rx = _received(spec, 4, 4, ebn0_db=1.0)
    plain = decode(spec, rx)
    tel = Telemetry.enabled(device_counters=False)
    traced = decode(spec, rx, telemetry=tel)
    np.testing.assert_array_equal(np.asarray(plain.bits), np.asarray(traced.bits))
    np.testing.assert_array_equal(np.asarray(plain.diagnostics["llr"]),
                                  np.asarray(traced.diagnostics["llr"]))
    assert tel.tracer.durations_s("turbo.iteration")


def test_planner_takes_the_tail_rows():
    spec = _spec(40)
    assert plan_decode(spec, (4, 44, 3)).backend == "turbo"
    with pytest.raises(ValueError, match="44 rows"):
        plan_decode(spec, (4, 40, 3))
    with pytest.raises(ValueError, match="64 rows"):
        plan_decode(TurboSpec(), (4, 68, 3))
