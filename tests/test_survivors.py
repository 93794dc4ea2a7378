"""Packed-survivor pipeline: pack/unpack round-trip property, the Pallas
traceback kernel vs the XLA scan-of-gathers oracle, in-kernel branch metrics
vs the table builders, and golden-grid equivalence of the ``fused_packed``
backend (raw-symbol entry) against the sequential oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CODE_K3_STD, CODE_K5_GSM, CODE_K7_NASA, viterbi_decode
from repro.core.puncture import PUNCTURE_2_3
from repro.core.viterbi import _traceback
from repro.decode import CodecSpec, DecodeContext, DecodeRequest, decode, get_decoder
from repro.kernels import (
    fused_metric_plan,
    pack_survivors,
    unpack_survivors,
    viterbi_traceback_op,
)
from repro.kernels.common import PACK_BITS

try:  # the property test widens coverage when hypothesis is available
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - env-dependent
    HAVE_HYPOTHESIS = False

CODES = {"k3": CODE_K3_STD, "k5": CODE_K5_GSM, "k7": CODE_K7_NASA}


def _noisy(spec, key, batch, n_info, **chan):
    bits = jax.random.bernoulli(key, 0.5, (batch, n_info)).astype(jnp.int32)
    coded = spec.encode(bits)
    rx = spec.channel(jax.random.fold_in(key, 1), coded, **chan)
    return bits, rx, spec.branch_metrics(rx)


# --------------------------------------------------------------------------- #
# pack/unpack round-trip (arbitrary T, including partial last words)           #
# --------------------------------------------------------------------------- #


def _assert_roundtrip(T, S, B, seed):
    rng = np.random.default_rng(seed)
    bps = jnp.asarray(rng.integers(0, 2, size=(T, S, B), dtype=np.int32))
    packed = pack_survivors(bps)
    assert packed.shape == (-(-T // PACK_BITS), S, B)
    assert packed.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(unpack_survivors(packed, T)), np.asarray(bps))


@pytest.mark.parametrize(
    "T", [1, 2, 31, 32, 33, 63, 64, 65, 96, 107]  # word edges + tails
)
def test_pack_unpack_roundtrip(T):
    _assert_roundtrip(T, S=4, B=3, seed=T)


if HAVE_HYPOTHESIS:

    @settings(max_examples=30, deadline=None)
    @given(
        T=st.integers(1, 3 * PACK_BITS + 7),  # covers T < 32, T % 32 == 0, tails
        S=st.sampled_from([2, 4, 16]),
        B=st.integers(1, 5),
        seed=st.integers(0, 2 ** 16),
    )
    def test_pack_unpack_roundtrip_property(T, S, B, seed):
        _assert_roundtrip(T, S, B, seed)


def test_pack_tail_bits_are_zero():
    bps = jnp.ones((PACK_BITS + 5, 2, 2), jnp.int32)
    packed = np.asarray(pack_survivors(bps))
    assert packed.shape[0] == 2
    assert (packed[0] == np.uint32(0xFFFFFFFF)).all()
    assert (packed[1] == np.uint32((1 << 5) - 1)).all()  # bits >= T stay 0


# --------------------------------------------------------------------------- #
# Pallas traceback == XLA traceback                                            #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("code_id", sorted(CODES))
@pytest.mark.parametrize("B,T", [(3, 50), (8, 96)])
def test_traceback_kernel_matches_xla_scan(code_id, B, T, rng):
    """Random survivor memory + random start states: the packed walk must
    reproduce the scan-of-gathers traceback exactly."""
    code = CODES[code_id]
    S = code.n_states
    bps = jax.random.bernoulli(rng, 0.5, (T, B, S)).astype(jnp.int32)
    fs = jax.random.randint(jax.random.fold_in(rng, 1), (B,), 0, S, jnp.int32)
    ref_bits, _ = _traceback(code, bps, fs)
    bits = viterbi_traceback_op(code, pack_survivors(bps), fs, T)
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(ref_bits))


# --------------------------------------------------------------------------- #
# in-kernel metric plans == the table builders                                 #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("code_id", ["k3", "k7"])
@pytest.mark.parametrize("metric", ["hard", "soft"])
@pytest.mark.parametrize("punctured", [False, True], ids=["unpunct", "punct23"])
def test_metric_plan_affine_form_matches_tables(code_id, metric, punctured, rng):
    code = CODES[code_id]
    spec = CodecSpec(
        code=code, metric=metric, puncture=PUNCTURE_2_3 if punctured else None
    )
    chan = {"snr_db": 4.0} if metric == "soft" else {"flip_prob": 0.03}
    _, rx, bm = _noisy(spec, rng, 3, 25, **chan)
    plan = fused_metric_plan(code, metric, spec.puncture_array)
    rebuilt = plan.bm_tables(rx)
    np.testing.assert_allclose(np.asarray(rebuilt), np.asarray(bm), rtol=1e-5, atol=1e-5)
    # mid-stream phase: rows [t0:] of the full mask == a chunk built at t0
    t0 = 7
    np.testing.assert_allclose(
        np.asarray(plan.bm_tables(rx[:, t0:], t0=t0)),
        np.asarray(bm[:, t0:]),
        rtol=1e-5, atol=1e-5,
    )


# --------------------------------------------------------------------------- #
# golden grid: fused_packed raw-symbol entry vs the sequential oracle          #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("code_name", ["k3", "k7"])
@pytest.mark.parametrize("metric", ["hard", "soft"])
@pytest.mark.parametrize("terminated", [True, False], ids=["term", "open"])
def test_fused_packed_from_received_golden_grid(code_name, metric, terminated, rng):
    code = CODES[code_name]
    spec = CodecSpec(code=code, metric=metric, terminated=terminated)
    cell = code.constraint * 4 + (metric == "soft") * 2 + terminated
    key = jax.random.fold_in(rng, cell)
    chan = {"snr_db": 4.0} if metric == "soft" else {"flip_prob": 0.03}
    _, rx, bm = _noisy(spec, key, 2, 30, **chan)
    ref_bits, ref_metric = viterbi_decode(code, bm, terminated=terminated)
    res = get_decoder("fused_packed").decode_received(spec, rx, ctx=DecodeContext())
    assert res.diagnostics["metrics"] == "in-kernel"
    np.testing.assert_array_equal(
        np.asarray(res.bits), np.asarray(ref_bits),
        err_msg=f"fused_packed (in-kernel metrics) diverged on {spec.describe()}",
    )
    np.testing.assert_allclose(
        np.asarray(res.path_metric), np.asarray(ref_metric), rtol=1e-5
    )


def test_decode_routes_received_to_in_kernel_metrics(rng):
    """decode() with raw channel output skips the host bm table entirely."""
    spec = CodecSpec()
    _, rx, bm = _noisy(spec, rng, 4, 40, flip_prob=0.02)
    ref_bits, _ = viterbi_decode(spec.code, bm)
    res = decode(DecodeRequest(spec, received=rx))
    assert res.plan.backend == "fused_packed"
    assert res.diagnostics["metrics"] == "in-kernel"
    np.testing.assert_array_equal(np.asarray(res.bits), np.asarray(ref_bits))
    # precomputed tables take the table fallback of the same backend
    res2 = decode(DecodeRequest(spec, bm_tables=bm))
    assert res2.diagnostics["metrics"] == "table"
    np.testing.assert_array_equal(np.asarray(res2.bits), np.asarray(ref_bits))
    # bm_tables precedence (the DecodeRequest contract): custom tables must
    # NOT be recomputed from received when both are given
    custom = jnp.zeros_like(bm).at[..., 0].set(-1.0)  # forces all-zero symbols
    res3 = decode(DecodeRequest(spec, received=rx, bm_tables=custom))
    assert res3.diagnostics["metrics"] == "table"
    ref_custom, _ = viterbi_decode(spec.code, custom)
    np.testing.assert_array_equal(np.asarray(res3.bits), np.asarray(ref_custom))


# --------------------------------------------------------------------------- #
# interpret-mode resolution is pinned per decode                               #
# --------------------------------------------------------------------------- #


def test_interpret_resolution_pinned_per_decode(rng, monkeypatch):
    """``interpret=None`` must resolve exactly ONCE per decode — at the
    ops.py entry point — so the forward scan and the traceback kernel can
    never auto-detect onto different code paths.  Per-kernel resolution
    would consult ``jax.default_backend()`` at each kernel's trace time
    (>= 2 consultations on a fresh shape; 0 on cached executables), so a
    platform-context change between traces could silently split one decode
    across compiled and interpreted kernels."""
    from repro.kernels import common
    from repro.kernels.ops import viterbi_decode_packed

    spec = CodecSpec()
    _, _, bm = _noisy(spec, rng, 3, 37, flip_prob=0.02)  # fresh (B, T) shape
    calls = {"n": 0}
    real = common.jax.default_backend

    def counting():
        calls["n"] += 1
        return real()

    monkeypatch.setattr(common.jax, "default_backend", counting)
    bits, _ = viterbi_decode_packed(spec.code, bm)
    assert calls["n"] == 1, (
        f"interpret auto-detect consulted the platform {calls['n']} times in "
        "one decode; it must be pinned once at the decode entry point"
    )
    ref_bits, _ = viterbi_decode(spec.code, bm)
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(ref_bits))


def test_interpret_resolution_survives_platform_flip(rng, monkeypatch):
    """Forced host platform: even if the platform answer CHANGES mid-decode
    (the mixed-resolution hazard), the pinned decode keeps every kernel on
    the resolution captured at entry."""
    from repro.kernels import common
    from repro.kernels.ops import viterbi_decode_packed

    spec = CodecSpec()
    _, _, bm = _noisy(spec, rng, 3, 41, flip_prob=0.02)  # fresh (B, T) shape
    real = common.jax.default_backend
    first = {"done": False}

    def flipping():
        if not first["done"]:
            first["done"] = True
            return real()  # honest answer for the pinning consultation
        return "tpu"  # later consultations would demand compiled kernels

    monkeypatch.setattr(common.jax, "default_backend", flipping)
    bits, _ = viterbi_decode_packed(spec.code, bm)  # must not try TPU lowering
    ref_bits, _ = viterbi_decode(spec.code, bm)
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(ref_bits))


def test_stream_components_pin_interpret(mesh11):
    """Sessions and schedulers pin the resolution at construction: one
    stream decode spans many kernel dispatches (ticks, tail feeds, flush)
    across which the platform answer must be frozen."""
    from repro.kernels.common import resolve_interpret
    from repro.stream import StreamScheduler, StreamSession

    expected = resolve_interpret(None)
    sess = StreamSession(CODE_K3_STD, chunk=32, backend="fused_packed")
    sched = StreamScheduler(CODE_K3_STD, n_slots=2, chunk=32, backend="fused_packed")
    sharded = StreamScheduler(
        CODE_K3_STD, n_slots=2, chunk=32, backend="fused_packed", mesh=mesh11
    )
    assert sess._interpret is expected
    assert sched._interpret is expected
    assert sharded._interpret is expected
