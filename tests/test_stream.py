"""Streaming subsystem: sliding-window decode, sessions, scheduler."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CODE_K3_STD,
    CODE_K5_GSM,
    bsc,
    encode,
    hard_branch_metrics,
    viterbi_decode,
)
from repro.kernels import unpack_survivors, viterbi_forward_weighted_op
from repro.kernels.viterbi_scan import table_weights
from repro.stream import (
    StreamScheduler,
    StreamSession,
    chunk_forward_scan,
    default_depth,
    init_stream_state,
    viterbi_decode_windowed,
)

CODES = {"k3": CODE_K3_STD, "k5": CODE_K5_GSM}


def _noisy_bm(code, key, batch, info_bits, flip):
    bits = jax.random.bernoulli(key, 0.5, (batch, info_bits)).astype(jnp.int32)
    coded = encode(code, bits, terminate=True)
    rx = bsc(jax.random.fold_in(key, 1), coded, flip)
    return bits, hard_branch_metrics(code, rx)


# --------------------------------------------------------------------------- #
# chunked forward op                                                           #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("code_name", sorted(CODES))
def test_chunked_forward_matches_full_scan(code_name, rng):
    """Composing carried-state chunk scans == one full-block forward pass."""
    code = CODES[code_name]
    w = table_weights(code)
    _, bm = _noisy_bm(code, rng, 4, 61, 0.05)
    T = bm.shape[1]
    full_pm, full_packed = viterbi_forward_weighted_op(code, None, bm, w)

    pm = init_stream_state(code, 4, 1, 1).pm
    bps_parts = []
    C = 16
    for i in range(0, T, C):
        chunk = bm[:, i : i + C]
        if chunk.shape[1] == C:
            pm, packed = viterbi_forward_weighted_op(code, pm, chunk, w)
            bps = unpack_survivors(packed, C)
        else:  # odd tail goes through the scan reference
            pm, bps = chunk_forward_scan(code, pm, chunk)
        bps_parts.append(bps)
    np.testing.assert_allclose(np.asarray(pm), np.asarray(full_pm), rtol=1e-6)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(b) for b in bps_parts]),
        np.asarray(unpack_survivors(full_packed, T)),
    )


def test_chunk_op_matches_scan_reference(rng):
    code = CODE_K3_STD
    _, bm = _noisy_bm(code, rng, 8, 30, 0.1)
    pm0 = init_stream_state(code, 8, 1, 1).pm
    pm_f, packed = viterbi_forward_weighted_op(code, pm0, bm, table_weights(code))
    pm_s, bps_s = chunk_forward_scan(code, pm0, bm)
    np.testing.assert_allclose(np.asarray(pm_f), np.asarray(pm_s), rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(unpack_survivors(packed, bm.shape[1])), np.asarray(bps_s)
    )


# --------------------------------------------------------------------------- #
# (a) windowed == full-block when D >= T                                       #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("code_name", sorted(CODES))
@pytest.mark.parametrize("backend", ["scan", "fused_packed"])
def test_windowed_bit_exact_when_depth_covers_block(code_name, backend, rng):
    code = CODES[code_name]
    _, bm = _noisy_bm(code, rng, 4, 96 - (code.constraint - 1), 0.04)
    ref_bits, ref_metric = viterbi_decode(code, bm)
    T = bm.shape[1]
    bits, metric = viterbi_decode_windowed(
        code, bm, depth=T, chunk=32, backend=backend
    )
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(ref_bits))
    np.testing.assert_allclose(np.asarray(metric), np.asarray(ref_metric), rtol=1e-5)


def test_windowed_handles_odd_tail(rng):
    """T not a multiple of chunk: the remainder flows through finish()."""
    code = CODE_K3_STD
    _, bm = _noisy_bm(code, rng, 2, 83, 0.02)
    ref_bits, _ = viterbi_decode(code, bm)
    bits, _ = viterbi_decode_windowed(code, bm, depth=bm.shape[1], chunk=32)
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(ref_bits))


# --------------------------------------------------------------------------- #
# (b) BER parity at D = 5K on a noisy channel                                  #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("code_name", sorted(CODES))
def test_windowed_ber_parity_at_truncation_depth(code_name, rng):
    code = CODES[code_name]
    info, bm = _noisy_bm(code, rng, 8, 512, 0.02)
    ref_bits, _ = viterbi_decode(code, bm)
    bits, _ = viterbi_decode_windowed(
        code, bm, depth=default_depth(code), chunk=64, backend="scan"
    )
    n = info.shape[1]
    ber_ref = float((np.asarray(ref_bits)[:, :n] != np.asarray(info)).mean())
    ber_win = float((np.asarray(bits)[:, :n] != np.asarray(info)).mean())
    assert abs(ber_win - ber_ref) <= 1e-3


# --------------------------------------------------------------------------- #
# (c) session chunk-boundary invariance                                        #
# --------------------------------------------------------------------------- #


def test_session_chunk_boundary_invariance(rng):
    """One 4096-step stream decoded in 64-step chunks == one-shot decode."""
    code = CODE_K3_STD
    T = 4096
    info, bm = _noisy_bm(code, rng, 1, T - (code.constraint - 1), 0.01)
    ref_bits, ref_metric = viterbi_decode(code, bm)

    sess = StreamSession(code, batch=1, chunk=64, depth=40, backend="scan")
    parts = []
    for i in range(T // 64):
        parts.append(np.asarray(sess.push(bm[:, i * 64 : (i + 1) * 64])))
    rest, metric = sess.finish(terminated=True)
    parts.append(np.asarray(rest))
    bits = np.concatenate(parts, axis=1)
    assert bits.shape == ref_bits.shape
    np.testing.assert_array_equal(bits, np.asarray(ref_bits))
    np.testing.assert_allclose(np.asarray(metric), np.asarray(ref_metric), rtol=1e-5)


def test_session_emission_bookkeeping(rng):
    """Commit lag: nothing before depth steps, chunk bits at steady state,
    the final `lag` bits on finish."""
    code = CODE_K3_STD
    sess = StreamSession(code, batch=2, chunk=16, depth=24, backend="scan")
    _, bm = _noisy_bm(code, rng, 2, 62, 0.0)
    counts = []
    for i in range(4):
        counts.append(sess.push(bm[:, i * 16 : (i + 1) * 16]).shape[1])
    assert counts == [0, 8, 16, 16]  # t=16,32,48,64 vs depth 24
    assert sess.lag == 24
    rest, _ = sess.finish(terminated=True)
    assert rest.shape[1] == 24
    with pytest.raises(RuntimeError):
        sess.push(bm[:, :16])


def test_session_normalization_keeps_metrics_bounded(rng):
    """A long stream with per-chunk renorm: path metrics stay O(chunk) while
    the reconstructed absolute metric still matches the block decoder."""
    code = CODE_K3_STD
    _, bm = _noisy_bm(code, rng, 1, 1022, 0.05)
    ref_bits, ref_metric = viterbi_decode(code, bm)
    sess = StreamSession(code, batch=1, chunk=64, depth=1024, backend="scan")
    bits, metric = sess.decode_all(bm)
    assert float(sess.state.pm.min()) == 0.0  # renormalized every chunk
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(ref_bits))
    np.testing.assert_allclose(np.asarray(metric), np.asarray(ref_metric), rtol=1e-5)


# --------------------------------------------------------------------------- #
# (d) scheduler: continuous batching + slot reuse                              #
# --------------------------------------------------------------------------- #


def test_scheduler_slot_reuse_across_completions(rng):
    """More streams than slots, staggered lengths: every stream decodes
    exactly, and slots turn over (claims > n_slots)."""
    code = CODE_K3_STD
    sched = StreamScheduler(code, n_slots=4, chunk=16, depth=30, backend="scan")
    refs = {}
    for i in range(10):
        k = jax.random.fold_in(rng, i)
        T = (96, 130, 64, 200)[i % 4]
        _, bm = _noisy_bm(code, k, 1, T, 0.01)
        rb, rm = viterbi_decode(code, bm)
        refs[f"s{i}"] = (np.asarray(rb[0]), float(rm[0]))
        sched.submit(f"s{i}", bm[0])
    out = sched.run()
    assert sched.stats.streams_finished == 10
    assert sched.stats.slot_claims == 10 > sched.n_slots  # slots were recycled
    assert sched.utilization() == 0.0
    for sid, (rb, rm) in refs.items():
        bits, metric = out[sid]
        np.testing.assert_array_equal(bits, rb)
        assert abs(metric - rm) < 1e-3 * max(1.0, abs(rm))


def test_scheduler_single_jitted_call_per_tick(rng):
    """The hot loop traces once: many ticks with many live streams reuse one
    compiled stream_step."""
    code = CODE_K3_STD
    sched = StreamScheduler(code, n_slots=8, chunk=16, depth=15, backend="scan")
    traces = {"n": 0}
    orig = sched._step_fn

    def counting(state, bm, weights=None, active=None):
        traces["n"] += 1
        return orig(state, bm, weights, active)

    sched._step_fn = counting
    for i in range(8):
        _, bm = _noisy_bm(code, jax.random.fold_in(rng, i), 1, 94, 0.0)
        sched.submit(f"s{i}", bm[0])
    sched.run()
    assert traces["n"] == sched.stats.ticks  # one batched dispatch per tick


def test_scheduler_short_stream_admitted_mid_run(rng):
    """A stream shorter than one chunk that queues behind a full slot must
    retire cleanly when admitted mid-run (regression: it used to crash the
    packing loop)."""
    code = CODE_K3_STD
    sched = StreamScheduler(code, n_slots=1, chunk=32, depth=15, backend="scan")
    _, bm_long = _noisy_bm(code, rng, 1, 126, 0.0)
    _, bm_short = _noisy_bm(code, jax.random.fold_in(rng, 1), 1, 10, 0.0)
    ref_short, _ = viterbi_decode(code, bm_short)
    sched.submit("long", bm_long[0])
    sched.submit("short", bm_short[0])  # queues: T=12 < chunk
    out = sched.run()
    assert set(out) == {"long", "short"}
    np.testing.assert_array_equal(out["short"][0], np.asarray(ref_short[0]))


def test_scheduler_slot_state_reset_after_idle_ticks(rng):
    """A slot that sat free (and was advanced with zero branch metrics for
    several ticks) must be re-initialized when a later stream claims it
    (regression: drifted path metrics erased the start-in-state-0
    constraint)."""
    code = CODE_K3_STD
    sched = StreamScheduler(code, n_slots=2, chunk=16, depth=30, backend="scan")
    _, bm_a = _noisy_bm(code, rng, 1, 158, 0.01)
    sched.submit("a", bm_a[0])
    for _ in range(4):  # slot 1 idles through real ticks
        sched.step()
    # noisy enough that an un-reset (drifted, all-zero) initial pm would
    # decode different bits and understate the metric
    _, bm_b = _noisy_bm(code, jax.random.fold_in(rng, 7), 1, 94, 0.12)
    ref_b, ref_mb = viterbi_decode(code, bm_b)
    sched.submit("b", bm_b[0])
    out = sched.run()
    bits_b, metric_b = out["b"]
    np.testing.assert_array_equal(bits_b, np.asarray(ref_b[0]))
    assert abs(metric_b - float(ref_mb[0])) < 1e-3


def test_scheduler_batched_slot_flush(rng, monkeypatch):
    """All slots retiring in the same tick flush through ONE batched
    traceback call (grouped tail-feeds), not one dispatch per slot — and the
    batched path stays bit-exact, including distinct odd tail lengths."""
    from repro.stream import window as _w

    code = CODE_K3_STD
    sched = StreamScheduler(code, n_slots=4, chunk=16, depth=90, backend="scan")
    flush_factory = _w.jitted_stream_flush
    calls = {"n": 0}

    def counting_flush(code_, terminated=True, interpret=None):
        calls["n"] += 1
        return flush_factory(code_, terminated=terminated, interpret=interpret)

    monkeypatch.setattr(_w, "jitted_stream_flush", counting_flush)
    refs = {}
    for i, T in enumerate((80, 83, 87, 83)):  # same tick out, 3 tail lengths
        _, bm = _noisy_bm(code, jax.random.fold_in(rng, i), 1, T, 0.02)
        rb, rm = viterbi_decode(code, bm)
        refs[f"s{i}"] = (np.asarray(rb[0]), float(rm[0]))
        sched.submit(f"s{i}", bm[0])
    out = sched.run()
    assert sched.stats.streams_finished == 4
    assert calls["n"] == 1  # one flush for the whole retiring cohort
    for sid, (rb, rm) in refs.items():
        bits, metric = out[sid]
        np.testing.assert_array_equal(bits, rb)
        assert abs(metric - rm) < 1e-3 * max(1.0, abs(rm))


def test_scheduler_accepts_codec_spec(rng):
    """The scheduler consumes a CodecSpec; submit() inherits its terminated
    flag (here: open trellis -> traceback from the best frontier state)."""
    from repro.decode import CodecSpec

    code = CODE_K3_STD
    spec = CodecSpec(code=code, terminated=False)
    sched = StreamScheduler(spec, n_slots=2, chunk=16, depth=200, backend="scan")
    bits = jax.random.bernoulli(rng, 0.5, (1, 90)).astype(jnp.int32)
    bm = spec.branch_metrics(
        bsc(jax.random.fold_in(rng, 1), spec.encode(bits), 0.01)
    )
    ref, _ = viterbi_decode(code, bm, terminated=False)
    sched.submit("open-stream", bm[0])  # terminated defaults from the spec
    out = sched.run()
    np.testing.assert_array_equal(out["open-stream"][0], np.asarray(ref[0]))


def test_scheduler_evict(rng):
    code = CODE_K3_STD
    sched = StreamScheduler(code, n_slots=2, chunk=16, depth=15, backend="scan")
    for i in range(3):
        _, bm = _noisy_bm(code, jax.random.fold_in(rng, i), 1, 158, 0.0)
        sched.submit(f"s{i}", bm[0])
    sched.step()
    assert sched.evict("s2") is None  # still pending
    partial = sched.evict("s0")  # active: returns committed prefix
    assert partial is not None
    out = sched.run()
    assert set(out) == {"s1"}
    with pytest.raises(KeyError):
        sched.evict("nope")


# --------------------------------------------------------------------------- #
# (e) packed-survivor streaming (fused_packed backend)                         #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("code_name", sorted(CODES))
def test_packed_windowed_bit_exact_when_depth_covers_block(code_name, rng):
    """fused_packed streaming (packed ring + Pallas traceback) stays bit-
    identical to the block decoder in the exactness regime."""
    code = CODES[code_name]
    _, bm = _noisy_bm(code, rng, 4, 96 - (code.constraint - 1), 0.04)
    ref_bits, ref_metric = viterbi_decode(code, bm)
    bits, metric = viterbi_decode_windowed(
        code, bm, depth=bm.shape[1], chunk=32, backend="fused_packed"
    )
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(ref_bits))
    np.testing.assert_allclose(np.asarray(metric), np.asarray(ref_metric), rtol=1e-5)


def test_packed_truncated_window_matches_scan_backend(rng):
    """Away from the exactness regime the packed and unpacked windows must
    still commit identical bits (same truncation, different survivor
    format); the packed depth rounds up to a word multiple."""
    code = CODE_K3_STD
    _, bm = _noisy_bm(code, rng, 4, 254, 0.03)
    b_packed, _ = viterbi_decode_windowed(
        code, bm, depth=32, chunk=32, backend="fused_packed"
    )
    b_scan, _ = viterbi_decode_windowed(code, bm, depth=32, chunk=32, backend="scan")
    np.testing.assert_array_equal(np.asarray(b_packed), np.asarray(b_scan))


def test_packed_session_rounds_depth_and_handles_odd_tail(rng):
    code = CODE_K3_STD
    _, bm = _noisy_bm(code, rng, 2, 81, 0.02)  # T = 83: odd tail of 19
    ref_bits, ref_metric = viterbi_decode(code, bm)
    sess = StreamSession(code, batch=2, chunk=32, depth=bm.shape[1],
                         backend="fused_packed")
    assert sess.depth % 32 == 0 and sess.depth >= bm.shape[1]
    bits, metric = sess.decode_all(bm)
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(ref_bits))
    np.testing.assert_allclose(np.asarray(metric), np.asarray(ref_metric), rtol=1e-5)
    with pytest.raises(ValueError, match="chunk"):
        StreamSession(code, chunk=20, backend="fused_packed")
    with pytest.raises(ValueError, match="chunk"):
        StreamSession(code, chunk=20)  # the default backend is the packed one
    with pytest.raises(ValueError, match="stream backend"):
        StreamSession(code, chunk=32, backend="fused")  # no unpacked Pallas ring


def test_packed_session_from_received_in_kernel_metrics(rng):
    """inputs='received': the session feeds raw symbols and the kernel
    computes the branch metrics — bit-exact vs the table-fed block decode."""
    code = CODE_K3_STD
    bits = jax.random.bernoulli(rng, 0.5, (4, 126)).astype(jnp.int32)
    coded = encode(code, bits, terminate=True)
    rx = bsc(jax.random.fold_in(rng, 1), coded, 0.03)
    bm = hard_branch_metrics(code, rx)
    ref_bits, ref_metric = viterbi_decode(code, bm)
    sess = StreamSession(code, batch=4, chunk=32, depth=bm.shape[1],
                         backend="fused_packed", inputs="received")
    out, metric = sess.decode_all(rx)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_bits))
    np.testing.assert_allclose(np.asarray(metric), np.asarray(ref_metric), rtol=1e-5)


def test_packed_scheduler_slot_reuse_bit_exact(rng):
    """Packed hot loop end-to-end through the scheduler: staggered lengths,
    slot turnover, odd tails — every stream decodes exactly."""
    code = CODE_K3_STD
    sched = StreamScheduler(code, n_slots=3, chunk=32, depth=250,
                            backend="fused_packed")
    refs = {}
    for i in range(8):
        k = jax.random.fold_in(rng, i)
        T = (96, 130, 64, 200)[i % 4]
        _, bm = _noisy_bm(code, k, 1, T, 0.01)
        rb, rm = viterbi_decode(code, bm)
        refs[f"s{i}"] = (np.asarray(rb[0]), float(rm[0]))
        sched.submit(f"s{i}", bm[0])
    out = sched.run()
    assert sched.stats.streams_finished == 8
    assert sched.stats.slot_claims == 8 > sched.n_slots
    for sid, (rb, rm) in refs.items():
        bits, metric = out[sid]
        np.testing.assert_array_equal(bits, rb)
        assert abs(metric - rm) < 1e-3 * max(1.0, abs(rm))


# --------------------------------------------------------------------------- #
# (f) device-resident scheduler input arena                                    #
# --------------------------------------------------------------------------- #


def test_scheduler_hot_loop_packs_on_device(rng, monkeypatch):
    """The per-tick (n_slots, chunk, M) block is gathered from the device
    arena by slot offset — no host numpy packing in step()."""
    code = CODE_K3_STD
    sched = StreamScheduler(code, n_slots=4, chunk=16, depth=30, backend="scan")
    gathers = {"n": 0}
    orig = sched._gather

    def counting(arena, offs):
        gathers["n"] += 1
        return orig(arena, offs)

    monkeypatch.setattr(sched, "_gather", counting)
    refs = {}
    for i in range(6):
        _, bm = _noisy_bm(code, jax.random.fold_in(rng, i), 1, (60, 94)[i % 2], 0.01)
        rb, _ = viterbi_decode(code, bm)
        refs[f"s{i}"] = np.asarray(rb[0])
        sched.submit(f"s{i}", bm[0])
    out = sched.run()
    assert gathers["n"] == sched.stats.ticks  # one device gather per tick
    for sid, rb in refs.items():
        np.testing.assert_array_equal(out[sid][0], rb)


def test_scheduler_arena_compaction_preserves_streams(rng):
    """Retired segments eventually dominate the arena; compaction rebuilds
    it around the live streams without disturbing in-flight decodes."""
    code = CODE_K3_STD
    sched = StreamScheduler(code, n_slots=2, chunk=16, depth=15, backend="scan")
    sched._compact_floor = 0  # exercise compaction at toy sizes
    sched._compact_ratio = 2
    refs = {}
    for i in range(10):
        _, bm = _noisy_bm(code, jax.random.fold_in(rng, i), 1, 62, 0.01)
        rb, _ = viterbi_decode(code, bm)
        refs[f"s{i}"] = np.asarray(rb[0])
        sched.submit(f"s{i}", bm[0])
    out = sched.run()
    assert sched.stats.arena_compactions > 0
    for sid, rb in refs.items():
        np.testing.assert_array_equal(out[sid][0], rb)


# --------------------------------------------------------------------------- #
# (g) scheduler lifecycle edge cases                                           #
# --------------------------------------------------------------------------- #


def test_scheduler_evict_while_draining(rng):
    """Evicting a stream whose remainder is already below one chunk (it
    would retire next tick) must return the committed prefix and free the
    slot without corrupting the streams still in flight."""
    code = CODE_K3_STD
    sched = StreamScheduler(code, n_slots=2, chunk=16, depth=15, backend="scan")
    _, bm_a = _noisy_bm(code, rng, 1, 158, 0.01)
    _, bm_b = _noisy_bm(code, jax.random.fold_in(rng, 1), 1, 40, 0.01)
    ref_a, _ = viterbi_decode(code, bm_a)
    sched.submit("a", bm_a[0])
    sched.submit("b", bm_b[0])
    for _ in range(8):
        sched.step()
        st_b = next((s for s in sched.active.values() if s.stream_id == "b"), None)
        if st_b is not None and 0 < st_b.available < sched.chunk:
            break
    else:
        pytest.fail("stream 'b' never reached the draining window")
    partial = sched.evict("b")  # draining: remainder < chunk
    assert partial is not None and partial.dtype == np.int32
    out = sched.run()
    assert set(out) == {"a"}
    np.testing.assert_array_equal(out["a"][0], np.asarray(ref_a[0]))
    with pytest.raises(KeyError):
        sched.evict("b")  # already gone


def test_scheduler_submit_after_all_slots_retired(rng):
    """A drained scheduler (every slot retired, results collected) must
    accept and decode a fresh wave of streams."""
    code = CODE_K3_STD
    sched = StreamScheduler(code, n_slots=2, chunk=16, depth=30, backend="scan")
    for i in range(3):
        _, bm = _noisy_bm(code, jax.random.fold_in(rng, i), 1, 62, 0.01)
        sched.submit(f"wave1-{i}", bm[0])
    sched.run()
    assert not sched.pending_work() and sched.utilization() == 0.0
    _, bm = _noisy_bm(code, jax.random.fold_in(rng, 99), 1, 94, 0.05)
    ref, ref_m = viterbi_decode(code, bm)
    sched.submit("wave2", bm[0])
    out = sched.run()
    np.testing.assert_array_equal(out["wave2"][0], np.asarray(ref[0]))
    assert abs(out["wave2"][1] - float(ref_m[0])) < 1e-3
    assert sched.stats.streams_finished == 4


def test_scheduler_zero_length_stream(rng):
    """A zero-step stream must retire cleanly with empty bits (and must not
    wedge the tick loop or the batched flush)."""
    code = CODE_K3_STD
    sched = StreamScheduler(code, n_slots=2, chunk=16, depth=15, backend="scan")
    _, bm_real = _noisy_bm(code, rng, 1, 62, 0.01)
    ref, _ = viterbi_decode(code, bm_real)
    sched.submit("empty", np.zeros((0, code.n_symbols), np.float32))
    sched.submit("real", bm_real[0])
    out = sched.run()
    assert out["empty"][0].shape == (0,)
    np.testing.assert_array_equal(out["real"][0], np.asarray(ref[0]))
    assert sched.stats.streams_finished == 2


def test_scheduler_compaction_mid_tick_with_live_slots(rng):
    """Compaction triggered while streams are mid-flight (the tick compacts
    before its gather): live segments must be relocated coherently so the
    in-flight decode continues bit-exact."""
    code = CODE_K3_STD
    sched = StreamScheduler(code, n_slots=2, chunk=16, depth=15, backend="scan")
    sched._compact_floor = 0
    sched._compact_ratio = 1  # compact aggressively, incl. with live slots
    refs = {}
    long_ids = []
    for i in range(2):  # long residents: stay live across compactions
        _, bm = _noisy_bm(code, jax.random.fold_in(rng, i), 1, 190, 0.02)
        rb, _ = viterbi_decode(code, bm)
        refs[f"long{i}"] = np.asarray(rb[0])
        long_ids.append(f"long{i}")
        sched.submit(f"long{i}", bm[0])
    sched.step()  # both residents mid-stream
    for i in range(6):  # churn short streams through the queue
        _, bm = _noisy_bm(code, jax.random.fold_in(rng, 100 + i), 1, 46, 0.02)
        rb, _ = viterbi_decode(code, bm)
        refs[f"short{i}"] = np.asarray(rb[0])
        sched.submit(f"short{i}", bm[0])
    out = sched.run()
    assert sched.stats.arena_compactions > 0
    for sid, rb in refs.items():
        np.testing.assert_array_equal(out[sid][0], rb)


# --------------------------------------------------------------------------- #
# (h) mesh-sharded scheduler, single-device degenerate mesh                    #
# --------------------------------------------------------------------------- #


def test_sharded_scheduler_on_unit_mesh_matches_unsharded(mesh11, rng):
    """mesh with data=1: the sharded code path (shard_map tick, per-shard
    arena, collective load report) runs on the main suite's single device
    and stays bit-exact with the plain scheduler."""
    code = CODE_K3_STD
    plain = StreamScheduler(code, n_slots=4, chunk=16, depth=30, backend="scan")
    shard = StreamScheduler(code, n_slots=4, chunk=16, depth=30, backend="scan",
                            mesh=mesh11, mesh_axis="data")
    assert shard.n_shards == 1 and shard._sharded_step is not None
    for i in range(6):
        _, bm = _noisy_bm(code, jax.random.fold_in(rng, i), 1, (94, 62)[i % 2], 0.02)
        plain.submit(f"s{i}", bm[0])
        shard.submit(f"s{i}", bm[0])
    out_p, out_s = plain.run(), shard.run()
    for sid in out_p:
        np.testing.assert_array_equal(out_s[sid][0], out_p[sid][0])
        assert abs(out_s[sid][1] - out_p[sid][1]) < 1e-4
    report = shard.load_report()
    assert report["n_shards"] == 1 and report["active_total"] == 0


def test_sharded_scheduler_validates_mesh(mesh11):
    code = CODE_K3_STD
    with pytest.raises(ValueError, match="no 'nope' axis"):
        StreamScheduler(code, n_slots=4, mesh=mesh11, mesh_axis="nope")


# --------------------------------------------------------------------------- #
# decode-API streaming integration                                             #
# --------------------------------------------------------------------------- #


def test_decode_api_streaming_backend(rng):
    from repro.decode import CodecSpec, DecodeContext, decode

    spec = CodecSpec()
    bits = jax.random.bernoulli(rng, 0.5, (4, 94)).astype(jnp.int32)
    rx = spec.channel(jax.random.fold_in(rng, 1), spec.encode(bits),
                      flip_prob=0.01)
    res = decode(spec, rx, backend="streaming", ctx=DecodeContext(chunk=32))
    assert res.info_bits.shape == bits.shape
    assert float((res.info_bits != bits).mean()) < 0.05


# --------------------------------------------------------------------------- #
# (i) packed odd-tail hardening: T % 32 != 0 in the truncation regime          #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("T", [33, 65, 97, 255])
@pytest.mark.parametrize("depth", [32, 40, 64])
def test_packed_odd_tail_truncation_open_trellis_session(T, depth, rng):
    """Regression (odd-tail audit): T % 32 != 0 with terminated=False — the
    truncation regime — through the packed session's unpack-at-flush path.
    The final segment is smaller than one packed word and the requested
    depth need not be word-aligned (the session rounds it up); committed
    bits and metric must match the unpacked scan backend bit-for-bit at the
    session's EFFECTIVE (rounded) depth."""
    code = CODE_K3_STD
    bits = jax.random.bernoulli(jax.random.fold_in(rng, T * 100 + depth), 0.5,
                                (4, T)).astype(jnp.int32)
    from repro.core import bsc as _bsc
    coded = encode(code, bits, terminate=False)
    rx = _bsc(jax.random.fold_in(rng, T), coded, 0.03)
    bm = hard_branch_metrics(code, rx)
    assert bm.shape[1] % 32 != 0
    sess_p = StreamSession(code, batch=4, chunk=32, depth=depth,
                           backend="fused_packed")
    b_packed, m_packed = sess_p.decode_all(bm, terminated=False)
    # compare at the packed session's effective depth (rounded to a word)
    sess_s = StreamSession(code, batch=4, chunk=32, depth=sess_p.depth,
                           backend="scan")
    b_scan, m_scan = sess_s.decode_all(bm, terminated=False)
    np.testing.assert_array_equal(np.asarray(b_packed), np.asarray(b_scan))
    np.testing.assert_allclose(np.asarray(m_packed), np.asarray(m_scan),
                               rtol=1e-5)


def test_packed_odd_tail_open_trellis_exact_regime(rng):
    """Same odd-tail path in the exactness regime (depth >= T): bit-identical
    to the full-block open-trellis decode, metric included."""
    code = CODE_K3_STD
    for T in (33, 94, 127):
        bits = jax.random.bernoulli(jax.random.fold_in(rng, T), 0.5,
                                    (2, T)).astype(jnp.int32)
        from repro.core import bsc as _bsc
        coded = encode(code, bits, terminate=False)
        rx = _bsc(jax.random.fold_in(rng, T + 1), coded, 0.05)
        bm = hard_branch_metrics(code, rx)
        ref_bits, ref_metric = viterbi_decode(code, bm, terminated=False)
        b, m = viterbi_decode_windowed(code, bm, depth=T, chunk=32,
                                       backend="fused_packed", terminated=False)
        np.testing.assert_array_equal(np.asarray(b), np.asarray(ref_bits))
        np.testing.assert_allclose(np.asarray(m), np.asarray(ref_metric),
                                   rtol=1e-5)


def test_packed_scheduler_odd_tails_truncation_open_trellis(rng):
    """Scheduler flush hardening: packed hot loop, depth < T, odd tails of
    several lengths retiring in mixed cohorts, open trellises — identical to
    the scan-backend scheduler at the same (word-aligned) depth."""
    code = CODE_K3_STD
    sp = StreamScheduler(code, n_slots=3, chunk=32, depth=64,
                         backend="fused_packed")
    ss = StreamScheduler(code, n_slots=3, chunk=32, depth=64, backend="scan")
    for i, T in enumerate((97, 130, 65, 201, 99, 33)):
        _, bm = _noisy_bm(code, jax.random.fold_in(rng, i), 1, T, 0.03)
        sp.submit(f"s{i}", bm[0], terminated=False)
        ss.submit(f"s{i}", bm[0], terminated=False)
    op, os_ = sp.run(), ss.run()
    for sid in op:
        np.testing.assert_array_equal(op[sid][0], os_[sid][0])
        assert abs(op[sid][1] - os_[sid][1]) < 1e-3 * max(1.0, abs(os_[sid][1]))


# --------------------------------------------------------------------------- #
# (j) drain-before-gather: sub-chunk admissions, compaction, arena integrity   #
# --------------------------------------------------------------------------- #


def _assert_arena_integrity(sched):
    """Every live slot's row map must point inside its shard's used prefix,
    cover exactly its unconsumed steps, and never alias another stream."""
    by_shard = {}
    for st in sched.active.values():
        assert len(st.rows) == st.available, st.stream_id
        if len(st.rows):
            assert st.rows.min() >= sched.chunk  # zero prefix is reserved
            assert st.rows.max() < sched._arena_len[st.shard], (
                f"{st.stream_id} points past the used prefix "
                f"(stale _arena_len or compacted rows)"
            )
        by_shard.setdefault(st.shard, []).append(st)
    for streams in by_shard.values():
        all_rows = np.concatenate([st.rows for st in streams]) if streams else []
        assert len(all_rows) == len(set(all_rows.tolist())), "row aliasing"


def test_scheduler_subchunk_streams_retired_same_tick_arena_integrity(rng):
    """Regression (drain-before-gather): zero- and sub-chunk-length streams
    submitted and retired in the same tick, interleaved with compaction
    while long streams stay live — no stale _arena_len entries and no live
    slot left pointing at compacted rows, checked after every tick."""
    code = CODE_K3_STD
    sched = StreamScheduler(code, n_slots=3, chunk=16, depth=15, backend="scan")
    sched._compact_floor = 0
    sched._compact_ratio = 1  # compact as aggressively as possible
    refs = {}
    _, bm_long = _noisy_bm(code, rng, 2, 190, 0.02)
    for j in range(2):
        rb, _ = viterbi_decode(code, bm_long[j : j + 1])
        refs[f"long{j}"] = np.asarray(rb[0])
        sched.submit(f"long{j}", bm_long[j])
    sched.step()
    _assert_arena_integrity(sched)
    for i in range(8):  # churn sub-chunk and zero-length streams
        T = (10, 0, 3, 14)[i % 4]
        if T:
            _, bm = _noisy_bm(code, jax.random.fold_in(rng, 50 + i), 1, T, 0.02)
            rb, _ = viterbi_decode(code, bm)
            refs[f"tiny{i}"] = np.asarray(rb[0])
            sched.submit(f"tiny{i}", bm[0])
        else:
            refs[f"tiny{i}"] = np.zeros((0,), np.int32)
            sched.submit(f"tiny{i}", np.zeros((0, code.n_symbols), np.float32))
        sched.step()  # the tiny stream admits AND retires inside this tick
        _assert_arena_integrity(sched)
    out = sched.run()
    _assert_arena_integrity(sched)
    assert sched.stats.arena_compactions > 0
    for sid, rb in refs.items():
        np.testing.assert_array_equal(out[sid][0], rb)


def test_scheduler_chunk_fed_submit_tick_compact_interleaving(rng):
    """The same interleaving through the CHUNK-FED path: partial feeds land
    between ticks and compactions relocate live, partially-consumed row
    maps; decode stays bit-exact and the arena stays coherent throughout."""
    from repro.stream import StreamBusy

    code = CODE_K3_STD
    sched = StreamScheduler(code, n_slots=2, chunk=16, depth=15, backend="scan")
    sched._compact_floor = 0
    sched._compact_ratio = 1
    refs, feeds = {}, {}
    for i in range(4):
        _, bm = _noisy_bm(code, jax.random.fold_in(rng, i), 1, (90, 61, 170, 44)[i], 0.02)
        rb, _ = viterbi_decode(code, bm)
        refs[f"s{i}"] = np.asarray(rb[0])
        sched.open_stream(f"s{i}")
        t = np.asarray(bm[0])
        feeds[f"s{i}"] = [t[k : k + 23] for k in range(0, len(t), 23)]
    while sched.pending_work():
        for sid, chunks in feeds.items():
            if chunks:
                try:
                    sched.submit_chunk(sid, chunks[0])
                except StreamBusy:
                    continue
                chunks.pop(0)
                if not chunks:
                    sched.close(sid)
        sched.step()
        _assert_arena_integrity(sched)
    assert sched.stats.arena_compactions > 0
    for sid, rb in refs.items():
        np.testing.assert_array_equal(sched.results[sid][0], rb)


# --------------------------------------------------------------------------- #
# (k) host-staged arena: one device write per tick                             #
# --------------------------------------------------------------------------- #


class _EagerAppendScheduler(StreamScheduler):
    """The reference for the staged arena: every accepted chunk's features
    built on the device and written with its own eager dynamic_update_slice,
    the arena grown and re-placed at that append, nothing staged."""

    def _append_stream_rows(self, st, rows):
        data = jnp.asarray(rows)
        if self.inputs == "received":
            data = self._plan.features(data, t0=st.fed)
        start = self._append_rows(st.shard, data)
        st.rows = np.concatenate(
            [st.rows, np.arange(start, start + rows.shape[0], dtype=np.int32)]
        )
        st.fed += rows.shape[0]

    def _append_rows(self, shard, rows):
        start = self._arena_len[shard]
        need = start + rows.shape[0]
        cap = self._arena.shape[1]
        if need > cap:
            grow = jnp.zeros(
                (self.n_shards, max(2 * cap, need) - cap, self._width), jnp.float32
            )
            self._arena = jnp.concatenate([self._arena, grow], axis=1)
        self._arena = jax.lax.dynamic_update_slice(
            self._arena, jnp.asarray(rows, jnp.float32)[None], (shard, start, 0)
        )
        self._arena_len[shard] = need
        self.stats.arena_appends += 1
        self._pin_arena()
        return start


def _drive(sched, tables, sizes, restore_at=None):
    """Feed every stream its table in pieces of ``sizes`` rows (cycling,
    round robin, one piece per stream between ticks), closing it with its
    last piece; ``restore_at``: snapshot the scheduler before that tick,
    with rows staged, and go on from the restored one.  Returns the
    scheduler that finished and its results."""
    from repro.stream import StreamBusy

    cursor = dict.fromkeys(tables, 0)
    for sid in tables:
        sched.open_stream(sid)
    tick = 0
    while any(cursor[sid] < len(t) for sid, t in tables.items()):
        for i, (sid, table) in enumerate(tables.items()):
            c = cursor[sid]
            if c >= len(table):
                continue
            piece = table[c : c + sizes[(tick + i) % len(sizes)]]
            try:
                sched.submit_chunk(sid, piece, close=c + len(piece) == len(table))
            except StreamBusy:
                continue
            cursor[sid] = c + len(piece)
        if tick == restore_at:
            assert any(sched._staged)
            sched = StreamScheduler.restore(sched.snapshot())
        sched.step()
        tick += 1
    return sched, sched.run()


_STAGING_CASES = {
    # pieces that do not divide the chunk, streams admitted at once
    "ragged": dict(n_slots=3, streams=3, inputs="bm"),
    # raw symbols of a rate-2/3 code: the host features' puncture phase
    # follows each piece's first step
    "punctured": dict(n_slots=3, streams=3, inputs="received"),
    # one slot: the other streams' pieces queue on the host and land as
    # one backlog when a slot frees
    "backlog": dict(n_slots=1, streams=3, inputs="bm"),
    # snapshot with rows staged, restored and driven to the end
    "restore": dict(n_slots=2, streams=3, inputs="bm", restore_at=2),
}


@pytest.mark.parametrize("case", sorted(_STAGING_CASES))
def test_staged_arena_matches_eager_appends(case, rng):
    """Host staging plus one write per tick leaves every arena row and every
    decoded bit as the per-chunk eager device append did, bit for bit."""
    from repro.core.puncture import PUNCTURE_2_3
    from repro.decode import CodecSpec

    cfg = _STAGING_CASES[case]
    code = CODE_K3_STD
    keys = [jax.random.fold_in(rng, 300 + i) for i in range(cfg["streams"])]
    lengths = (118, 77, 150)
    if cfg["inputs"] == "received":
        spec = CodecSpec(code=code, puncture=PUNCTURE_2_3)
        kw = dict(backend="fused_packed", inputs="received", chunk=32, depth=64)
        tables = {}
        for i, key in enumerate(keys):
            bits = jax.random.bernoulli(key, 0.5, (1, lengths[i])).astype(jnp.int32)
            rx = spec.channel(jax.random.fold_in(key, 1), spec.encode(bits),
                              flip_prob=0.02)
            tables[f"s{i}"] = np.asarray(rx[0], np.float32)
    else:
        spec = code
        kw = dict(backend="scan", chunk=16, depth=30)
        tables = {
            f"s{i}": np.asarray(_noisy_bm(code, key, 1, lengths[i], 0.02)[1][0])
            for i, key in enumerate(keys)
        }
    sizes = (23, 7, 41, 5)
    staged, got = _drive(StreamScheduler(spec, n_slots=cfg["n_slots"], **kw),
                         tables, sizes, restore_at=cfg.get("restore_at"))
    eager, want = _drive(_EagerAppendScheduler(spec, n_slots=cfg["n_slots"], **kw),
                         tables, sizes)
    assert set(got) == set(want) == set(tables)
    for sid in tables:
        np.testing.assert_array_equal(got[sid][0], want[sid][0])
        assert got[sid][1] == want[sid][1]
    if case != "restore":  # a restored arena is laid out anew
        assert staged._arena_len == eager._arena_len
        for shard, n in enumerate(staged._arena_len):
            a = np.asarray(staged._read_arena()[shard, :n])
            b = np.asarray(eager._arena[shard, :n])
            np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))
        assert staged.stats.arena_appends == eager.stats.arena_appends
        assert 0 < staged.stats.arena_writes <= staged.stats.arena_appends


@pytest.mark.parametrize("metric", ["hard", "soft"])
@pytest.mark.parametrize("pattern", ["2_3", "3_4"])
def test_host_features_match_device_features_at_every_phase(metric, pattern, rng):
    """The NumPy features the scheduler stages equal FusedMetricPlan.features
    bit for bit, for every start step in the puncture period and lengths
    that cross it, negative zeros included."""
    from repro.core import puncture
    from repro.kernels.metrics import fused_metric_plan

    pat = getattr(puncture, f"PUNCTURE_{pattern}")
    plan = fused_metric_plan(CODE_K3_STD, metric, pat)
    rx = np.array(jax.random.normal(rng, (41, 2)), np.float32)
    rx[3] = -0.0
    for t0 in range(2 * pat.shape[1]):
        for T in (1, 5, 41):
            want = np.asarray(plan.features(jnp.asarray(rx[:T]), t0=t0))
            got = plan.host_features(rx[:T], t0=t0)
            assert got.dtype == np.float32 and got.shape == want.shape
            np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


@pytest.mark.parametrize("sharded", [False, True])
def test_padded_arena_writes_keep_the_zero_prefix(request, sharded, rng):
    """Pieces that never fill a power-of-two block pad every write; the pad
    entries are dropped, so rows [0, chunk) of each shard stay zero (the
    read target of starved slots) and no row past the used prefix is
    touched."""
    mesh = request.getfixturevalue("mesh11") if sharded else None
    code = CODE_K3_STD
    sched = StreamScheduler(code, n_slots=2, chunk=16, depth=30, backend="scan",
                            mesh=mesh)
    tables = {
        f"s{i}": np.asarray(_noisy_bm(code, jax.random.fold_in(rng, 400 + i), 1,
                                      (61, 93)[i], 0.02)[1][0]) + 1.0
        for i in range(2)
    }
    sched, _ = _drive(sched, tables, (3, 11, 17))
    assert sched.stats.arena_writes > 0
    arena = np.asarray(sched._read_arena())
    for shard, n in enumerate(sched._arena_len):
        assert not arena[shard, : sched.chunk].any()
        assert not arena[shard, n:].any()
        assert (arena[shard, sched.chunk : n] != 0).any()


def test_submit_chunk_copies_the_callers_rows(rng):
    """Rows wait on the host until the tick writes them: a caller that
    reuses its buffer right after submit_chunk returns changes nothing."""
    code = CODE_K3_STD
    _, bm = _noisy_bm(code, rng, 1, 94, 0.02)
    table = np.asarray(bm[0])
    ref, _ = viterbi_decode(code, bm)
    sched = StreamScheduler(code, n_slots=1, chunk=16, depth=30, backend="scan")
    sched.open_stream("s")
    buf = np.empty((16, table.shape[1]), np.float32)
    for k in range(0, len(table), 16):
        piece = table[k : k + 16]
        buf[: len(piece)] = piece
        sched.submit_chunk("s", buf[: len(piece)], close=k + 16 >= len(table))
        buf[:] = np.nan  # the next receive overwrites the buffer
        sched.step()
    np.testing.assert_array_equal(sched.run()["s"][0], np.asarray(ref[0]))


# --------------------------------------------------------------------------- #
# (l) jitted arena compaction                                                  #
# --------------------------------------------------------------------------- #


def _reference_compaction(arena, sched):
    """The construction the jitted compaction replaced, in NumPy: per shard
    the zero prefix, each admitted stream's unconsumed rows in stream order,
    zeros to the capacity.  Returns the arena, each stream's new row map and
    each shard's used rows."""
    want = np.zeros_like(arena)
    cursor = [sched.chunk] * arena.shape[0]
    rows = {}
    for st in sched.active.values():
        c, n = cursor[st.shard], st.available
        want[st.shard, c : c + n] = arena[st.shard, st.rows]
        rows[st.stream_id] = np.arange(c, c + n)
        cursor[st.shard] = c + n
    return want, rows, cursor


def _random_compaction_run(seed, whole_chunks, check):
    """Staggered streams (more than slots, so some are admitted mid-run) fed
    random pieces, ragged or whole chunks, through a scheduler and, in
    lockstep, through one that never compacts; ``check(sched)`` runs before
    a random half of the ticks.  Returns the scheduler that compacted, after
    both drained to the same bits."""
    from repro.stream import StreamBusy

    rs = np.random.default_rng(seed)
    code, chunk = CODE_K3_STD, 16
    n_slots = int(rs.integers(2, 5))
    scheds = [StreamScheduler(code, n_slots=n_slots, chunk=chunk, depth=30,
                              backend="scan") for _ in range(2)]
    sched, plain = scheds
    sched._compact_floor = plain._compact_floor = 1 << 30  # check() compacts
    tables = {}
    for i in range(n_slots + int(rs.integers(1, 4))):
        steps = int(rs.integers(3, 12)) * chunk
        _, bm = _noisy_bm(code, jax.random.PRNGKey(1000 * seed + i), 1, steps - 2, 0.02)
        tables[f"s{i}"] = np.asarray(bm[0])
    cursor = dict.fromkeys(tables, 0)
    opened = []
    tick = 0
    while any(s.pending_work() for s in scheds) or len(opened) < len(tables):
        if len(opened) < len(tables) and rs.random() < 0.6:
            sid = f"s{len(opened)}"
            opened.append(sid)
            for s in scheds:
                s.open_stream(sid)
        for sid in opened:
            table, c = tables[sid], cursor[sid]
            if c >= len(table) or rs.random() < 0.3:
                continue
            size = chunk * int(rs.integers(1, 3)) if whole_chunks else int(rs.integers(1, 40))
            piece = table[c : c + min(size, sched.credit(sid))]
            if not len(piece):
                continue
            close = c + len(piece) == len(table)
            try:
                for s in scheds:
                    s.submit_chunk(sid, piece, close=close)
            except StreamBusy:
                pytest.fail("both schedulers grant the same credit")
            cursor[sid] = c + len(piece)
        if rs.random() < 0.5:
            check(sched)
        for s in scheds:
            s.step()
        tick += 1
        assert tick < 500
    assert set(sched.results) == set(plain.results) == set(tables)
    for sid in tables:
        np.testing.assert_array_equal(sched.results[sid][0], plain.results[sid][0])
        assert sched.results[sid][1] == plain.results[sid][1]
    return sched


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("layout", ["ragged", "whole_chunks"])
def test_jitted_compaction_matches_reference_construction(layout, seed):
    """Across random layouts (stream counts, ragged or whole-chunk pieces,
    streams admitted mid-run), the jitted compaction leaves the arena bit
    for bit as the per-stream construction did, moves every row map to its
    new rows and sets each shard's used rows; decodes are unchanged."""
    checked = []

    def check(sched):
        arena = np.asarray(sched._read_arena())
        want, rows, cursor = _reference_compaction(arena, sched)
        sched._compact()
        got = np.asarray(sched._arena)
        np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
        for st in sched.active.values():
            np.testing.assert_array_equal(st.rows, rows[st.stream_id])
        assert sched._arena_len == cursor
        _assert_arena_integrity(sched)
        checked.append(sum(st.available for st in sched.active.values()))

    sched = _random_compaction_run(seed, layout == "whole_chunks", check)
    assert sched.stats.arena_compactions == len(checked) > 2
    assert len(set(checked)) > 1  # the live rows differ between compactions


def test_compaction_compiles_once_per_capacity():
    """Compactions that find different live-row counts at one capacity run
    one compiled program: the jitted callable's cache holds one entry per
    capacity, however many compactions ran."""
    from repro.stream.scheduler import _arena_compactor

    _arena_compactor.cache_clear()  # a fresh callable, with an empty cache
    live_by_cap = {}
    compactors = []

    def check(sched):
        if not compactors:
            compactors.append(sched._compact_arena)
        live_by_cap.setdefault(sched._read_arena().shape[1], set()).add(
            sum(st.available for st in sched.active.values())
        )
        sched._compact()

    _random_compaction_run(7, False, check)
    assert max(len(v) for v in live_by_cap.values()) > 1
    assert compactors[0]._cache_size() == len(live_by_cap)
