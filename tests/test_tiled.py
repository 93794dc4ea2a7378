"""Time-parallel tiled decode (kernels/tiling.py + ops.viterbi_decode_tiled_op).

The gate for the tiled backend, in four layers:

  1. differential fuzz — tiled vs the sequential oracle and the fused_packed
     pipeline over (K3/K7 x hard/soft x punctured x terminated/open x P x
     awkward T), bit-exact in the exact seam regime.  Soft cells quantize the
     channel noise to a 1/64 grid so every float32 metric sum is exactly
     representable — reassociating sums across tile seams is then lossless
     and the bit-exact assert is deterministic, not flaky.
  2. min-plus seam algebra vs a brute-force oracle — per-tile transfer maps
     (the same scan-of-acs_step oracle the seqparallel decoder uses) composed
     with prefix_maps must reproduce the full-length forward metrics at every
     seam, ties included; the tie-break rule is pinned (lowest state index).
  3. windowed-kernel parity — the per-lane validity windows reduce to the
     plain packed scan/traceback when the window covers everything, and to a
     sliced scan when it does not.
  4. truncation regime — overlap >= 5·K is promoted to exact; short warm-ups
     stay approximate with a seeded BER-drift bound.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CODE_K3_STD, CODE_K7_NASA, viterbi_decode
from repro.core.acs import acs_step
from repro.core.puncture import PUNCTURE_2_3
from repro.core.trellis import NEG_UNREACHABLE
from repro.core.viterbi import _traceback
from repro.decode import CodecSpec, DecodeContext, get_decoder
from repro.kernels import (
    compose_maps,
    fused_metric_plan,
    identity_map,
    plan_tiles,
    prefix_maps,
    seam_argmin,
    tile_entry_metrics,
    default_tiles,
    traceback_packed,
    traceback_packed_window,
    truncation_depth,
    viterbi_decode_packed,
    viterbi_decode_tiled_fused,
    viterbi_decode_tiled_op,
)
from repro.kernels.common import lane_block, pad_axis_to
from repro.kernels.viterbi_scan import table_weights, viterbi_scan
from repro.parallel.collectives import _local_transfer_and_bps

try:  # the property layer widens coverage when hypothesis is available
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - env-dependent
    HAVE_HYPOTHESIS = False

CODES = {"k3": CODE_K3_STD, "k7": CODE_K7_NASA}


def _noisy(spec, key, batch, n_info, **chan):
    """bits + channel output + bm tables; soft noise lands on a 1/64 grid so
    float32 metric sums are exact under any association order."""
    bits = jax.random.bernoulli(key, 0.5, (batch, n_info)).astype(jnp.int32)
    coded = spec.encode(bits)
    rx = spec.channel(jax.random.fold_in(key, 1), coded, **chan)
    if spec.soft:
        rx = jnp.round(rx * 64.0) / 64.0
    return bits, rx, spec.branch_metrics(rx)


def _pm_trace(code, bm, clamp=True):
    """Oracle forward pass from state 0 collecting the metrics *entering*
    every step: (T+1, B, S) with row t = metrics after t ACS steps."""
    B = bm.shape[0]
    S = code.n_states
    pm0 = jnp.full((B, S), NEG_UNREACHABLE, jnp.float32).at[:, 0].set(0.0)

    def step(pm, bm_t):
        new_pm, _ = acs_step(code, pm, bm_t)
        return jnp.minimum(new_pm, NEG_UNREACHABLE), pm

    last, trace = jax.lax.scan(step, pm0, bm.swapaxes(0, 1))
    return jnp.concatenate([trace, last[None]], axis=0)


# --------------------------------------------------------------------------- #
# 1. differential fuzz: tiled == sequential == fused_packed (exact regime)     #
# --------------------------------------------------------------------------- #

#: curated awkward cells: T % P != 0, T % 32 != 0, T < span, T < 5·K, P = 1
FUZZ_CELLS = [
    # (code, metric, punctured, terminated, P, n_info)
    ("k3", "hard", False, True, 4, 150),
    ("k3", "hard", False, False, 7, 149),  # open + ragged last tile
    ("k3", "soft", False, True, 4, 101),  # T % 32 != 0
    ("k3", "hard", True, True, 2, 96),
    ("k3", "soft", True, False, 4, 75),
    ("k3", "hard", False, True, 1, 64),  # degenerate tiling
    ("k3", "hard", False, True, 7, 9),  # T=11: more tiles than fit
    ("k7", "hard", False, True, 4, 120),
    ("k7", "soft", False, True, 7, 130),
    ("k7", "hard", True, False, 2, 90),
    ("k7", "hard", False, True, 4, 5),  # T=11 < truncation depth 35
]


@pytest.mark.parametrize(
    "code_id,metric,punctured,terminated,P,n_info",
    FUZZ_CELLS,
    ids=[f"{c}-{m}-{'p' if pu else 'u'}-{'t' if te else 'o'}-P{P}-I{n}"
         for c, m, pu, te, P, n in FUZZ_CELLS],
)
def test_tiled_differential_exact(code_id, metric, punctured, terminated,
                                  P, n_info, rng):
    code = CODES[code_id]
    spec = CodecSpec(
        code=code, metric=metric, terminated=terminated,
        puncture=PUNCTURE_2_3 if punctured else None,
    )
    cell = (code.constraint * 16 + punctured * 8 + (metric == "soft") * 4
            + terminated * 2 + P)
    key = jax.random.fold_in(rng, cell)
    chan = {"snr_db": 3.0} if metric == "soft" else {"flip_prob": 0.04}
    _, rx, bm = _noisy(spec, key, 2, n_info, **chan)

    ref_bits, ref_metric = viterbi_decode(code, bm, terminated=terminated)
    pk_bits, pk_metric = viterbi_decode_packed(
        code, bm, terminated=terminated
    )
    td_bits, td_metric = viterbi_decode_tiled_op(
        code, bm, P, terminated=terminated
    )
    msg = f"tiled P={P} diverged on {spec.describe()} T={bm.shape[1]}"
    np.testing.assert_array_equal(np.asarray(td_bits), np.asarray(ref_bits),
                                  err_msg=msg)
    np.testing.assert_array_equal(np.asarray(td_bits), np.asarray(pk_bits),
                                  err_msg=msg)
    # 1/64-grid inputs keep every sum exact -> metrics match bit-for-bit
    np.testing.assert_array_equal(np.asarray(td_metric), np.asarray(ref_metric),
                                  err_msg=msg)
    assert pk_metric.shape == td_metric.shape

    # the raw-symbol (in-kernel metric) entry decodes identically
    plan = fused_metric_plan(code, metric, spec.puncture_array)
    fd_bits, fd_metric = viterbi_decode_tiled_fused(
        plan, rx, P, terminated=terminated
    )
    np.testing.assert_array_equal(np.asarray(fd_bits), np.asarray(ref_bits),
                                  err_msg=msg + " (fused entry)")
    np.testing.assert_allclose(np.asarray(fd_metric), np.asarray(ref_metric),
                               rtol=1e-5, err_msg=msg + " (fused entry)")


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(
        T=st.integers(2, 96),
        P=st.integers(1, 7),
        seed=st.integers(0, 2 ** 16),
        terminated=st.booleans(),
    )
    def test_tiled_differential_property(T, P, seed, terminated):
        """Arbitrary small-integer metric tables (ties everywhere): exact-mode
        tiling must reproduce the sequential walk, tie-breaks included."""
        code = CODE_K3_STD
        gen = np.random.default_rng(seed)
        bm = jnp.asarray(
            gen.integers(0, 3, size=(2, T, code.n_symbols)).astype(np.float32)
        )
        ref_bits, ref_metric = viterbi_decode(code, bm, terminated=terminated)
        td_bits, td_metric = viterbi_decode_tiled_op(
            code, bm, P, terminated=terminated
        )
        np.testing.assert_array_equal(np.asarray(td_bits), np.asarray(ref_bits))
        np.testing.assert_array_equal(
            np.asarray(td_metric), np.asarray(ref_metric)
        )


# --------------------------------------------------------------------------- #
# 2. min-plus seam algebra vs the brute-force oracle                           #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("code_id", ["k3", "k7"])
@pytest.mark.parametrize("P", [2, 4, 7])
def test_seam_metrics_match_full_forward(code_id, P, rng):
    """Composed per-tile transfer maps must yield, at every seam, exactly the
    path metrics the full-length forward pass has there — the invariant that
    makes the exact regime bit-exact."""
    code = CODES[code_id]
    spec = CodecSpec(code=code, metric="hard")
    key = jax.random.fold_in(rng, code.constraint * 8 + P)
    _, _, bm = _noisy(spec, key, 2, 61, flip_prob=0.05)
    T = bm.shape[1]
    tp = plan_tiles(T, P)

    maps = jnp.stack([
        _local_transfer_and_bps(
            code, bm[:, p * tp.core:(p + 1) * tp.core]
        )
        for p in range(tp.n_tiles)
    ])  # (P, B, S, S) — the seqparallel decoder's own chunk oracle
    excl, total = prefix_maps(maps)
    entry = tile_entry_metrics(excl)  # (P, B, S)

    trace = _pm_trace(code, bm)  # (T+1, B, S)
    for p in range(tp.n_tiles):
        np.testing.assert_array_equal(
            np.asarray(entry[p]), np.asarray(trace[p * tp.core]),
            err_msg=f"seam {p} (step {p * tp.core}) metrics diverged",
        )
    np.testing.assert_array_equal(
        np.asarray(total[:, 0, :]), np.asarray(trace[T]),
        err_msg="composed total != full forward frontier",
    )


def test_seam_argmin_tie_break_is_lowest_state():
    """Pinned rule: seam ties resolve to the LOWEST state index — the same
    first-occurrence convention as jnp.argmin and ops._frontier, so a tiled
    traceback entered through a tied seam picks the same path as the
    sequential walk."""
    m = jnp.asarray([[3.0, 1.0, 1.0, 5.0], [2.0, 2.0, 2.0, 2.0]])
    np.testing.assert_array_equal(np.asarray(seam_argmin(m)), [1, 0])
    assert seam_argmin(m).dtype == jnp.int32


def test_compose_maps_identity_and_associativity():
    """(min,+) maps form a monoid on integer-valued metrics: identity is
    neutral and composition reassociates losslessly — the property prefix_maps
    leans on."""
    S = 4
    gen = np.random.default_rng(7)
    a, b, c = (
        jnp.asarray(gen.integers(0, 9, size=(S, S)).astype(np.float32))
        for _ in range(3)
    )
    eye = identity_map(S)
    np.testing.assert_array_equal(np.asarray(compose_maps(eye, a)), np.asarray(a))
    np.testing.assert_array_equal(np.asarray(compose_maps(a, eye)), np.asarray(a))
    left = compose_maps(compose_maps(a, b), c)
    right = compose_maps(a, compose_maps(b, c))
    np.testing.assert_array_equal(np.asarray(left), np.asarray(right))
    # unreachable entries stay clamped, never overflow past the sentinel
    blocked = jnp.full((S, S), NEG_UNREACHABLE, jnp.float32)
    out = compose_maps(blocked, blocked)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(blocked))


def test_prefix_maps_exclusive_convention():
    """excl[p] composes tiles 0..p-1 (excl[0] = identity); total composes all
    — the exclusive-prefix convention the seam seeding assumes."""
    S = 2
    m0 = jnp.asarray([[1.0, 2.0], [3.0, 4.0]])
    m1 = jnp.asarray([[5.0, 6.0], [7.0, 8.0]])
    excl, total = prefix_maps(jnp.stack([m0, m1]))
    np.testing.assert_array_equal(np.asarray(excl[0]), np.asarray(identity_map(S)))
    np.testing.assert_array_equal(np.asarray(excl[1]), np.asarray(m0))
    np.testing.assert_array_equal(
        np.asarray(total), np.asarray(compose_maps(m0, m1))
    )


# --------------------------------------------------------------------------- #
# 3. windowed kernels reduce to the plain ones                                 #
# --------------------------------------------------------------------------- #


def _packed_fixture(code, key, B, T):
    """Random tables + seed metrics in kernel layout, lane-padded."""
    M = code.n_symbols
    S = code.n_states
    bm = jax.random.randint(key, (T, M, B), 0, 5).astype(jnp.float32)
    pm0 = jnp.full((S, B), NEG_UNREACHABLE, jnp.float32).at[0].set(0.0)
    blk = lane_block(B)
    bm_p, _ = pad_axis_to(bm, 2, blk, 0.0)
    pm0_p, _ = pad_axis_to(pm0, 1, blk, NEG_UNREACHABLE)
    return bm, pm0, bm_p, pm0_p, blk


def test_windowed_scan_full_window_matches_carry_scan(rng):
    code = CODE_K3_STD
    B, T = 3, 45
    bm, pm0, bm_p, pm0_p, blk = _packed_fixture(code, rng, B, T)
    b0, b1, rb = table_weights(code)
    ref_pm, ref_packed = viterbi_scan(code, bm_p, b0, b1, rb, blk, pm0=pm0_p)
    full = jnp.zeros((1, bm_p.shape[2]), jnp.int32)
    win_pm, win_packed = viterbi_scan(
        code, bm_p, b0, b1, rb, blk, pm0=pm0_p, window=(full, full + T)
    )
    np.testing.assert_array_equal(np.asarray(win_pm[:, :B]),
                                  np.asarray(ref_pm[:, :B]))
    np.testing.assert_array_equal(np.asarray(win_packed[:, :, :B]),
                                  np.asarray(ref_packed[:, :, :B]))


def test_windowed_scan_partial_window_matches_sliced_scan(rng):
    """A lane windowed to [lo, hi) must end with exactly the metrics of a
    plain scan over rows lo..hi-1, and emit survivor bit 0 elsewhere."""
    code = CODE_K3_STD
    B, T, lo, hi = 2, 40, 5, 29
    bm, pm0, bm_p, pm0_p, blk = _packed_fixture(code, rng, B, T)
    b0, b1, rb = table_weights(code)
    ones = jnp.ones((1, bm_p.shape[2]), jnp.int32)
    win_pm, win_packed = viterbi_scan(
        code, bm_p, b0, b1, rb, blk, pm0=pm0_p, window=(ones * lo, ones * hi)
    )
    sl_p, _ = pad_axis_to(bm[lo:hi], 2, blk, 0.0)
    ref_pm, ref_packed = viterbi_scan(code, sl_p, b0, b1, rb, blk, pm0=pm0_p)
    np.testing.assert_array_equal(np.asarray(win_pm[:, :B]),
                                  np.asarray(ref_pm[:, :B]))
    # bits inside the window line up step-for-step; outside they are zero
    bits = np.asarray(win_packed[:, :, :B])
    unpacked = np.zeros((T, code.n_states, B), np.int64)
    for t in range(T):
        unpacked[t] = (bits[t // 32] >> (t % 32)) & 1
    assert (unpacked[:lo] == 0).all() and (unpacked[hi:] == 0).all()
    ref_bits = np.asarray(ref_packed[:, :, :B])
    ref_unpacked = np.zeros((hi - lo, code.n_states, B), np.int64)
    for t in range(hi - lo):
        ref_unpacked[t] = (ref_bits[t // 32] >> (t % 32)) & 1
    np.testing.assert_array_equal(unpacked[lo:hi], ref_unpacked)


def test_windowed_traceback_full_window_matches_plain(rng):
    code = CODE_K7_NASA
    S = code.n_states
    B, T = 3, 50
    W = -(-T // 32)
    bps = jax.random.bernoulli(rng, 0.5, (T, B, S)).astype(jnp.int32)
    fs = jax.random.randint(jax.random.fold_in(rng, 1), (B,), 0, S, jnp.int32)
    ref_bits, ref_states = _traceback(code, bps, fs)

    from repro.kernels import pack_survivors

    packed = pack_survivors(bps.transpose(0, 2, 1))  # (W, S, B)
    blk = lane_block(B)
    pk, _ = pad_axis_to(packed, 2, blk, 0)
    st_, _ = pad_axis_to(fs[None, :], 1, blk, 0)
    zeros = jnp.zeros((1, pk.shape[2]), jnp.int32)
    bits, entry = traceback_packed_window(
        code, pk, st_, zeros, zeros + T, blk
    )
    np.testing.assert_array_equal(np.asarray(bits[:T, :B].T),
                                  np.asarray(ref_bits))
    # entry state = the state reached walking all the way back to step 0,
    # i.e. the step the sequential walk's state sequence *entered* on
    plain = traceback_packed(code, pk, st_, T, blk)
    np.testing.assert_array_equal(np.asarray(plain[:T, :B].T),
                                  np.asarray(ref_bits))
    # oracle entry: one more backpointer hop from the earliest kept state
    s1 = np.asarray(ref_states)[:, 0]  # state after step 0
    half = S // 2
    j = np.asarray(bps)[0, np.arange(B), s1]
    s0 = 2 * (s1 & (half - 1)) + j if half > 1 else j
    np.testing.assert_array_equal(np.asarray(entry[0, :B]), s0)


# --------------------------------------------------------------------------- #
# 4. truncation regime: promotion + seeded drift bound                         #
# --------------------------------------------------------------------------- #


def test_overlap_at_depth_promotes_to_exact(rng):
    """overlap >= 5·K always means bit-exact: the op promotes it to the
    exact seam regime rather than running an equal-cost approximation."""
    code = CODE_K3_STD
    spec = CodecSpec(code=code, metric="hard")
    _, _, bm = _noisy(spec, rng, 2, 300, flip_prob=0.06)
    D = truncation_depth(code)
    ref_bits, ref_metric = viterbi_decode(code, bm)
    for ov in (D, D + 7, 10_000):
        bits, metric = viterbi_decode_tiled_op(code, bm, 4, overlap=ov)
        np.testing.assert_array_equal(np.asarray(bits), np.asarray(ref_bits))
        np.testing.assert_array_equal(np.asarray(metric), np.asarray(ref_metric))


def test_truncated_regime_ber_drift_bounded(rng):
    """Short warm-up (overlap < 5·K) is allowed to disagree with the exact
    decode, but at a noisy operating point its end-to-end BER must stay
    within a small absolute drift of exact — the usual truncated-traceback
    argument applied to tile seams.  Seeded, so the bound is deterministic."""
    code = CODE_K3_STD
    spec = CodecSpec(code=code, metric="hard")
    key = jax.random.fold_in(rng, 99)
    bits, _, bm = _noisy(spec, key, 4, 400, flip_prob=0.08)
    sent = np.asarray(bits)

    exact_bits, _ = viterbi_decode_tiled_op(code, bm, 4)
    trunc_bits, _ = viterbi_decode_tiled_op(code, bm, 4, overlap=8)
    assert exact_bits.shape == trunc_bits.shape

    def ber(decoded):
        got = np.asarray(spec.strip_flush(decoded))
        return float((got != sent).mean())

    drift = ber(trunc_bits) - ber(exact_bits)
    assert drift <= 0.02, (
        f"truncated seam warm-up drifted {drift:.4f} BER past exact"
    )


def test_tile_plan_partitions_every_step_once():
    """windows()/gather_index() consistency: the kept cores tile [0, T)
    exactly — no step decoded twice, none dropped — for awkward shapes."""
    for T, P, ov in [(11, 7, 0), (96, 4, 0), (101, 4, 9), (5, 9, 50), (130, 3, 15)]:
        tp = plan_tiles(T, P, ov)
        lo, hi = tp.windows()
        gi = tp.gather_index()
        covered = np.concatenate([
            gi[p, tp.overlap:hi[p]] for p in range(tp.n_tiles)
        ])
        np.testing.assert_array_equal(covered, np.arange(T))
        assert (lo >= 0).all() and (hi <= tp.span).all()
        assert sum(tp.tile_length(p) for p in range(tp.n_tiles)) == T


def test_default_tiles_respects_floors_and_budget():
    assert default_tiles(1, 64, 4) == 1  # shorter than MIN_TILE_CORE
    assert default_tiles(1, 4096, 4) >= 4
    B, T, S = 8, 100_000, 64
    P = default_tiles(B, T, S)
    assert B * P * S <= 512 and P >= 1


# --------------------------------------------------------------------------- #
# decode()-level integration                                                   #
# --------------------------------------------------------------------------- #


def test_tiled_backend_registry_entry(rng):
    spec = CodecSpec(code=CODE_K3_STD, metric="hard")
    _, rx, bm = _noisy(spec, rng, 2, 120, flip_prob=0.03)
    ref_bits, _ = viterbi_decode(spec.code, bm)

    res = get_decoder("tiled")(spec, bm, ctx=DecodeContext(tiles=4))
    assert res.diagnostics["backend"] == "tiled"
    assert res.diagnostics["tiles"] == 4
    assert res.diagnostics["metrics"] == "table"
    np.testing.assert_array_equal(np.asarray(res.bits), np.asarray(ref_bits))

    res2 = get_decoder("tiled").decode_received(
        spec, rx, ctx=DecodeContext(tiles=4)
    )
    assert res2.diagnostics["metrics"] == "in-kernel"
    np.testing.assert_array_equal(np.asarray(res2.bits), np.asarray(ref_bits))


def test_tiled_backend_open_trellis_and_ctx_overlap(rng):
    spec = CodecSpec(code=CODE_K3_STD, metric="hard", terminated=False)
    _, _, bm = _noisy(spec, rng, 2, 140, flip_prob=0.03)
    ref_bits, ref_metric = viterbi_decode(spec.code, bm, terminated=False)
    ctx = DecodeContext(tiles=4, tile_overlap=truncation_depth(spec.code))
    res = get_decoder("tiled")(spec, bm, ctx=ctx)
    np.testing.assert_array_equal(np.asarray(res.bits), np.asarray(ref_bits))
    np.testing.assert_allclose(
        np.asarray(res.path_metric), np.asarray(ref_metric), rtol=1e-6
    )
