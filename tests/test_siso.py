"""SISO codec subsystem: RSC trellises, the max-log-MAP BCJR kernel,
interleavers, the iterative turbo decoder, and their registry/planner wiring.

The correctness anchor is the brute-force posterior oracle: in the min
domain, max-log BCJR LLRs are exactly (best cost of any input sequence with
u_t = 1) - (best with u_t = 0), so for short blocks every LLR is checked
against full sequence enumeration — in interpret mode AND under jit.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.puncture import (
    PUNCTURE_2_3,
    PUNCTURE_TURBO_1_2,
    effective_rate,
    pattern_mask,
)
from repro.core.trellis import CODE_K3_STD
from repro.decode import CodecSpec, decode, get_decoder, plan_decode, spec_family
from repro.kernels.ops import bcjr_llr_op
from repro.kernels.ref import bcjr_llr_ref
from repro.obs import MetricsRegistry
from repro.siso import (
    BlockInterleaver,
    QPPInterleaver,
    RSC_K3_75,
    RSC_K4_LTE,
    RSCCode,
    TurboSpec,
    turbo_decode,
)

CODES = {"k3": RSC_K3_75, "k4": RSC_K4_LTE}
#: small spec whose jit caches stay warm across the file
TSPEC = TurboSpec(code=RSC_K3_75, interleaver=QPPInterleaver(64, 7, 16))


def _rand_bits(key, shape):
    return jax.random.bernoulli(key, 0.5, shape).astype(jnp.int32)


# --------------------------------------------------------------------------- #
# RSC codes                                                                    #
# --------------------------------------------------------------------------- #


def test_rsc_encode_is_systematic_and_terminates(rng):
    code = RSC_K4_LTE
    bits = _rand_bits(rng, (4, 10))
    coded = np.asarray(code.encode(bits, terminate=True))
    assert coded.shape == (4, 10 + code.n_flush, code.n_out)
    np.testing.assert_array_equal(coded[:, :10, 0], np.asarray(bits))
    # replay the trellis: every symbol must be consistent and the tail must
    # drive the register back to state 0
    nxt, out = code.next_state, code.out_bits
    for b in range(4):
        s = 0
        for t in range(coded.shape[1]):
            u = int(coded[b, t, 0])
            np.testing.assert_array_equal(out[s, u], coded[b, t])
            s = int(nxt[s, u])
        assert s == 0


def test_rsc_open_encode_appends_nothing(rng):
    bits = _rand_bits(rng, (2, 7))
    assert RSC_K3_75.encode(bits, terminate=False).shape == (2, 7, 2)


def test_rsc_validation():
    with pytest.raises(ValueError):
        RSCCode(3, 0b011, (0b101,))  # feedback not monic
    code = RSC_K3_75
    assert code.n_states == 4 and code.n_out == 2 and code.n_features == 3


# --------------------------------------------------------------------------- #
# interleavers                                                                 #
# --------------------------------------------------------------------------- #


def test_block_interleaver_is_a_permutation_with_inverse():
    il = BlockInterleaver(4, 8)
    assert il.n == 32
    perm, inv = il.permutation, il.inverse
    assert sorted(perm) == list(range(32))
    x = np.arange(32) * 3
    np.testing.assert_array_equal(x[perm][inv], x)


def test_qpp_interleaver_matches_polynomial_and_inverts():
    il = QPPInterleaver(64, 7, 16)
    k = np.arange(64)
    np.testing.assert_array_equal(il.permutation, (7 * k + 16 * k * k) % 64)
    x = np.arange(64) + 100
    np.testing.assert_array_equal(x[il.permutation][il.inverse], x)


def test_qpp_rejects_non_permutation_polynomial():
    with pytest.raises(ValueError, match="not a permutation"):
        QPPInterleaver(64, 2, 4)  # f1 even: 0 and 32 collide
    with pytest.raises(ValueError):
        QPPInterleaver(1, 1, 0)


# --------------------------------------------------------------------------- #
# BCJR vs brute-force posterior oracle                                         #
# --------------------------------------------------------------------------- #


def _brute_llr(code, feat_tb, terminated):
    """(T, F) single-stream features -> (T,) max-log LLRs by enumerating
    every input sequence (cost = coded bits . channel LLRs + u . a-priori)."""
    T, _ = feat_tb.shape
    n = code.n_out
    out, nxt = np.asarray(code.out_bits), np.asarray(code.next_state)
    best0, best1 = np.full(T, np.inf), np.full(T, np.inf)
    for m in range(1 << T):
        s, cost = 0, 0.0
        u_seq = [(m >> t) & 1 for t in range(T)]
        for t, u in enumerate(u_seq):
            cost += float(np.dot(out[s, u], feat_tb[t, :n])) + u * feat_tb[t, n]
            s = nxt[s, u]
        if terminated and s != 0:
            continue
        for t, u in enumerate(u_seq):
            if u == 0:
                best0[t] = min(best0[t], cost)
            else:
                best1[t] = min(best1[t], cost)
    return best1 - best0


@pytest.mark.parametrize("code_name", sorted(CODES))
@pytest.mark.parametrize("terminated", [False, True], ids=["open", "term"])
def test_bcjr_llr_matches_brute_force(code_name, terminated):
    code = CODES[code_name]
    T, B = 8, 3
    feat = np.random.default_rng(17).normal(
        size=(B, T, code.n_features)).astype(np.float32)
    llr_coded = jnp.asarray(feat[..., : code.n_out])
    apriori = jnp.asarray(feat[..., code.n_out])
    brute = np.stack([_brute_llr(code, feat[b], terminated) for b in range(B)])

    # interpret-mode op
    llr_op, metric = bcjr_llr_op(
        code, llr_coded, apriori, terminated=terminated, interpret=True
    )
    np.testing.assert_allclose(np.asarray(llr_op), brute, atol=1e-4)
    assert metric.shape == (B,)
    # lax.scan reference
    ref = bcjr_llr_ref(
        code, jnp.asarray(feat.transpose(1, 2, 0)), terminated=terminated
    ).T
    np.testing.assert_allclose(np.asarray(ref), brute, atol=1e-4)
    # under jit: identical to the eager op
    llr_jit, _ = jax.jit(
        lambda c, a: bcjr_llr_op(code, c, a, terminated=terminated,
                                 interpret=True)
    )(llr_coded, apriori)
    np.testing.assert_array_equal(np.asarray(llr_jit), np.asarray(llr_op))


def test_bcjr_metric_is_best_sequence_cost():
    """The returned per-stream metric equals min over all sequences of the
    total cost — the Viterbi path metric of the same trellis."""
    code = RSC_K3_75
    T, B = 6, 2
    feat = np.random.default_rng(3).normal(
        size=(B, T, code.n_features)).astype(np.float32)
    _, metric = bcjr_llr_op(
        code, jnp.asarray(feat[..., :2]), jnp.asarray(feat[..., 2]),
        terminated=False, interpret=True,
    )
    out, nxt = np.asarray(code.out_bits), np.asarray(code.next_state)
    for b in range(B):
        best = np.inf
        for m in range(1 << T):
            s, cost = 0, 0.0
            for t in range(T):
                u = (m >> t) & 1
                cost += float(np.dot(out[s, u], feat[b, t, :2]))
                cost += u * feat[b, t, 2]
                s = nxt[s, u]
            best = min(best, cost)
        np.testing.assert_allclose(float(metric[b]), best, atol=1e-4)


def test_bcjr_noiseless_decode_is_exact_through_decode_api(rng):
    spec = CodecSpec(code=RSC_K3_75, metric="soft", terminated=True)
    bits = _rand_bits(rng, (4, 32))
    rx = jnp.asarray(1.0 - 2.0 * spec.encode(bits), jnp.float32)  # clean BPSK
    res = decode(spec, rx)
    assert res.diagnostics["backend"] == "bcjr"
    np.testing.assert_array_equal(np.asarray(res.info_bits), np.asarray(bits))
    assert "llr" in res.diagnostics


# --------------------------------------------------------------------------- #
# turbo codec                                                                  #
# --------------------------------------------------------------------------- #


def test_turbo_spec_validation():
    with pytest.raises(ValueError, match="iterations"):
        dataclasses.replace(TSPEC, iterations=0)
    with pytest.raises(ValueError, match="n_streams"):
        dataclasses.replace(TSPEC, puncture=((1, 1), (1, 0)))  # 2 rows, 3 streams
    with pytest.raises(ValueError, match="block length"):
        TSPEC.encode(jnp.zeros((2, 32), jnp.int32))  # spec block is 64
    assert TSPEC.n_streams == 3 and TSPEC.block_len == 64
    assert spec_family(TSPEC) == "turbo" and TSPEC.metric == "soft"
    assert hash(TSPEC) == hash(dataclasses.replace(TSPEC))


def test_turbo_encode_layout(rng):
    bits = _rand_bits(rng, (2, 64))
    coded = np.asarray(TSPEC.encode(bits))
    assert coded.shape == (2, 64, 3)
    np.testing.assert_array_equal(coded[..., 0], np.asarray(bits))  # systematic
    # parity2 is the constituent parity of the interleaved input
    perm = TSPEC.interleaver.permutation
    c2 = np.asarray(TSPEC.code.encode(bits[:, perm], terminate=False))
    np.testing.assert_array_equal(coded[..., 2], c2[..., 1])


def test_turbo_noiseless_decode_converges_and_early_exits(rng):
    bits = _rand_bits(rng, (4, 64))
    llrs = TSPEC.channel_llrs(1.0 - 2.0 * TSPEC.encode(bits))
    res = turbo_decode(TSPEC, llrs, interpret=True)
    np.testing.assert_array_equal(np.asarray(res.bits), np.asarray(bits))
    assert res.iterations_run < TSPEC.iterations  # early exit fired
    assert bool(res.converged.all())
    assert res.agreement[-1] == 1.0


def test_turbo_early_exit_is_bit_exact_with_fixed_iterations(rng):
    """The freeze-at-convergence construction: stopping early must return
    exactly the bits the full iteration budget would have."""
    bits = _rand_bits(rng, (8, 64))
    snr_db = 1.0 + 10 * np.log10(1 / 3)
    rx = TSPEC.channel(jax.random.fold_in(rng, 9), TSPEC.encode(bits),
                       snr_db=snr_db)
    llrs = TSPEC.channel_llrs(rx, snr_db=snr_db)
    ee = turbo_decode(TSPEC, llrs, early_exit=True, interpret=True)
    fixed = turbo_decode(TSPEC, llrs, early_exit=False, interpret=True)
    assert fixed.iterations_run == TSPEC.iterations
    np.testing.assert_array_equal(np.asarray(ee.bits), np.asarray(fixed.bits))


def test_turbo_records_telemetry(rng):
    bits = _rand_bits(rng, (4, 64))
    llrs = TSPEC.channel_llrs(1.0 - 2.0 * TSPEC.encode(bits))
    reg = MetricsRegistry()
    res = turbo_decode(TSPEC, llrs, interpret=True, metrics=reg)
    snap = reg.snapshot()
    assert snap["turbo_iterations_total"] == res.iterations_run
    assert snap["turbo_early_exits_total"] == 1
    assert snap["turbo_converged_streams"] == 4.0
    assert reg.histogram("turbo_llr_agreement").count == res.iterations_run


# --------------------------------------------------------------------------- #
# puncturing across the SISO paths (satellite: WIMAX-style rates)              #
# --------------------------------------------------------------------------- #


def test_effective_rate_and_mask_for_turbo_pattern():
    assert effective_rate(CODE_K3_STD, PUNCTURE_TURBO_1_2) == pytest.approx(1 / 2)
    # pattern_mask takes a bare stream count for turbo's trellis-less streams
    mask = np.asarray(pattern_mask(3, 5, PUNCTURE_TURBO_1_2))
    assert mask.shape == (5, 3)
    np.testing.assert_array_equal(mask[:, 0], 1)  # systematic always kept
    np.testing.assert_array_equal(mask[:2, 1], [1, 0])  # parities alternate
    np.testing.assert_array_equal(mask[:2, 2], [0, 1])


def test_rsc_codec_spec_punctured_noiseless_roundtrip(rng):
    """Rate-2/3 punctured RSC stream decodes exactly without noise through
    the bcjr backend (erasures leave surviving positions decisive)."""
    spec = CodecSpec(code=RSC_K3_75, metric="soft", terminated=True,
                     puncture=PUNCTURE_2_3)
    bits = _rand_bits(rng, (4, 32))
    rx = jnp.asarray(1.0 - 2.0 * spec.encode(bits), jnp.float32)
    res = decode(spec, rx)
    assert res.plan.backend == "bcjr"
    np.testing.assert_array_equal(np.asarray(res.info_bits), np.asarray(bits))


def test_turbo_punctured_noiseless_roundtrip(rng):
    """WIMAX-style rate-1/2 turbo puncturing (alternating parities) still
    decodes a clean block exactly."""
    spec = dataclasses.replace(TSPEC, puncture=PUNCTURE_TURBO_1_2)
    bits = _rand_bits(rng, (4, 64))
    coded = spec.encode(bits)
    # punctured positions really are not transmitted
    mask = np.asarray(pattern_mask(3, 64, spec.puncture_array))
    assert (np.asarray(coded)[..., mask == 0] == 0).all()
    llrs = spec.channel_llrs(1.0 - 2.0 * coded)
    res = turbo_decode(spec, llrs, interpret=True)
    np.testing.assert_array_equal(np.asarray(res.bits), np.asarray(bits))


# --------------------------------------------------------------------------- #
# registry + planner wiring                                                    #
# --------------------------------------------------------------------------- #


def test_planner_routes_turbo_spec_with_family_rule(rng):
    plan = plan_decode(TSPEC, (4, 64))
    assert plan.backend == "turbo"
    assert "family" in plan.reason and "turbo" in plan.reason
    assert "family" in plan.explain()


def test_planner_routes_rsc_spec_to_bcjr():
    spec = CodecSpec(code=RSC_K4_LTE, metric="soft")
    plan = plan_decode(spec, (4, 128))
    assert plan.backend == "bcjr"
    assert "family" in plan.reason


def test_planner_conv_selection_is_unchanged_by_siso_families():
    """Pin: adding the SISO families must not move any Viterbi choice."""
    from repro.decode import LONG_BLOCK_T, DecodeContext

    assert plan_decode(CodecSpec(), (32, 256)).backend == "fused_packed"
    # long blocks without a mesh route to ``tiled`` since the time-parallel
    # backend landed; the SISO families still leave that choice untouched.
    assert plan_decode(CodecSpec(), (4, LONG_BLOCK_T)).backend == "tiled"
    ctx = DecodeContext(streaming=True, stream_depth=15)
    assert plan_decode(CodecSpec(), (1, 4096), ctx=ctx).backend == "streaming"


def test_family_mismatch_is_a_validation_error():
    with pytest.raises(ValueError, match="family"):
        plan_decode(TSPEC, (4, 64), backend="fused_packed")
    with pytest.raises(ValueError, match="family"):
        plan_decode(CodecSpec(), (4, 64), backend="turbo")
    with pytest.raises(ValueError, match="family"):
        plan_decode(CodecSpec(code=RSC_K3_75), (4, 34), backend="sequential")


def test_decode_turbo_end_to_end_from_received(rng):
    """decode(TurboSpec, rx): raw channel output routes through the turbo
    backend's from_received entry; diagnostics carry the iteration count."""
    bits = _rand_bits(rng, (4, 64))
    snr_db = 2.0 + 10 * np.log10(1 / 3)
    rx = TSPEC.channel(jax.random.fold_in(rng, 3), TSPEC.encode(bits),
                       snr_db=snr_db)
    res = decode(TSPEC, rx)
    assert res.plan.backend == "turbo"
    assert res.diagnostics["backend"] == "turbo"
    assert 1 <= res.diagnostics["iterations"] <= TSPEC.iterations
    assert res.info_bits.shape == (4, 64)
    assert float((res.info_bits != bits).mean()) < 0.05
    assert res.path_metric.shape == (4,)


def test_turbo_backend_capabilities():
    turbo = get_decoder("turbo")
    assert turbo.capabilities.family == "turbo"
    assert turbo.capabilities.accepts_received
    bcjr = get_decoder("bcjr")
    assert bcjr.capabilities.family == "rsc"
    assert bcjr.capabilities.accepts_received


def test_turbo_beats_single_pass_at_low_snr(rng):
    """Iteration must actually help: 6 iterations strictly fewer bit errors
    than 1 iteration on a noisy block (the subsystem's raison d'etre)."""
    bits = _rand_bits(rng, (16, 64))
    snr_db = 0.0 + 10 * np.log10(1 / 3)
    rx = TSPEC.channel(jax.random.fold_in(rng, 4), TSPEC.encode(bits),
                       snr_db=snr_db)
    llrs = TSPEC.channel_llrs(rx, snr_db=snr_db)
    one = turbo_decode(TSPEC, llrs, iterations=1, early_exit=False,
                       interpret=True)
    six = turbo_decode(TSPEC, llrs, iterations=6, early_exit=False,
                       interpret=True)
    err1 = int(jnp.sum(one.bits != bits))
    err6 = int(jnp.sum(six.bits != bits))
    assert err6 < err1, (err6, err1)
