"""Online ingestion: chunk-fed scheduler, producers, backpressure.

The invariant everything here leans on: ARRIVAL SCHEDULE NEVER CHANGES THE
DECODE.  However a stream's rows trickle in — bursty generator, drip-fed
submit_chunk, starvation gaps, early close mid-chunk — the committed bits
and final metric must be bit-identical to the one-shot ``submit`` of the
concatenated table (and, at depth >= T, to the offline block decoder).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CODE_K3_STD,
    bsc,
    encode,
    hard_branch_metrics,
    viterbi_decode,
)
from repro.obs import Telemetry
from repro.stream import (
    CallableProducer,
    GeneratorProducer,
    PushProducer,
    RateLimitedProducer,
    StreamBusy,
    StreamScheduler,
    as_producer,
)

CODE = CODE_K3_STD


def _noisy_bm(key, info_bits, flip=0.02, batch=1):
    bits = jax.random.bernoulli(key, 0.5, (batch, info_bits)).astype(jnp.int32)
    coded = encode(CODE, bits, terminate=True)
    rx = bsc(jax.random.fold_in(key, 1), coded, flip)
    return bits, np.asarray(hard_branch_metrics(CODE, rx))


def _chunks_of(table, sizes):
    """Split a (T, M) table into arrival chunks of the given sizes (the last
    chunk absorbs any remainder)."""
    out, i = [], 0
    for sz in sizes:
        out.append(table[i : i + sz])
        i += sz
        if i >= len(table):
            break
    if i < len(table):
        out.append(table[i:])
    return [c for c in out if len(c)]


# --------------------------------------------------------------------------- #
# producer adapters                                                            #
# --------------------------------------------------------------------------- #


def test_generator_producer_splits_and_fills_credit():
    rows = np.arange(20, dtype=np.float32).reshape(10, 2)
    prod = GeneratorProducer(iter([rows[:7], rows[7:]]))
    got = prod.poll(3)  # 7-row burst split against credit 3
    np.testing.assert_array_equal(got, rows[:3])
    np.testing.assert_array_equal(prod.poll(4), rows[3:7])
    assert not prod.exhausted
    # a poll keeps pulling source chunks until the credit is filled or the
    # source ends — never capped at one yielded chunk per poll
    np.testing.assert_array_equal(prod.poll(100), rows[7:])
    assert prod.poll(5) is None and prod.exhausted
    assert GeneratorProducer(iter([rows])).poll(0) is None  # zero credit


def test_generator_producer_fills_credit_from_tiny_yields():
    """Many small source chunks assemble into ONE poll up to the credit —
    a 1-row generator must not throttle ingest to one row per tick."""
    rows = np.arange(24, dtype=np.float32).reshape(12, 2)
    prod = GeneratorProducer(rows[i : i + 1] for i in range(12))
    got = prod.poll(8)
    np.testing.assert_array_equal(got, rows[:8])
    np.testing.assert_array_equal(prod.poll(8), rows[8:])
    assert prod.exhausted


def test_callable_producer_none_means_not_ready():
    state = {"n": 0}

    def fn(max_rows):
        state["n"] += 1
        if state["n"] == 1:
            return None  # nothing ready yet
        if state["n"] == 2:
            return np.ones((4, 2), np.float32)
        raise StopIteration

    prod = CallableProducer(fn)
    assert prod.poll(8) is None and not prod.exhausted
    assert prod.poll(8).shape == (4, 2)
    assert prod.poll(8) is None and prod.exhausted


def test_push_producer_feed_poll_and_bound():
    prod = PushProducer(max_rows=8)
    prod.feed(np.zeros((5, 2), np.float32))
    with pytest.raises(StreamBusy):
        prod.feed(np.zeros((4, 2), np.float32), block=False)  # 5 + 4 > 8
    got = prod.poll(3)
    assert got.shape == (3, 2)
    prod.feed(np.zeros((4, 2), np.float32), block=False)  # drained below bound
    prod.close()
    assert not prod.exhausted  # rows still buffered
    assert prod.poll(100).shape == (6, 2)
    assert prod.exhausted
    with pytest.raises(RuntimeError):
        prod.feed(np.zeros((1, 2), np.float32))


def test_as_producer_coercion():
    assert isinstance(as_producer(iter([])), GeneratorProducer)
    assert isinstance(as_producer([np.zeros((1, 2))]), GeneratorProducer)
    assert isinstance(as_producer(lambda n: None), CallableProducer)
    p = PushProducer()
    assert as_producer(p) is p


def test_rate_limited_producer_respects_clock():
    table = np.arange(40, dtype=np.float32).reshape(20, 2)
    now = {"t": 0.0}
    prod = RateLimitedProducer(table, rows_per_s=10.0, clock=lambda: now["t"])
    assert prod.poll(100) is None  # no time elapsed, nothing released
    now["t"] = 0.5  # 5 rows released
    np.testing.assert_array_equal(prod.poll(100), table[:5])
    now["t"] = 10.0
    np.testing.assert_array_equal(prod.poll(4), table[5:9])  # capped by credit
    np.testing.assert_array_equal(prod.poll(100), table[9:])
    assert prod.exhausted


# --------------------------------------------------------------------------- #
# chunk-fed decode == one-shot submit == offline block decode                  #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("backend,chunk", [("scan", 16), ("fused_packed", 32)])
def test_chunk_fed_bit_exact_vs_offline(backend, chunk, rng):
    """Drip-fed arrival (sizes unrelated to the decode chunk, early-close
    mid-chunk tail) decodes bit-identically to the offline block decoder."""
    sizes = (5, 31, 2, 64, 17, 9, 50)
    sched = StreamScheduler(CODE, n_slots=2, chunk=chunk, depth=400, backend=backend)
    refs = {}
    feeds = {}
    for i in range(4):
        _, bm = _noisy_bm(jax.random.fold_in(rng, i), (91, 130, 64, 175)[i % 4])
        refs[f"s{i}"] = viterbi_decode(CODE, bm)
        feeds[f"s{i}"] = _chunks_of(bm[0], sizes)
        sched.open_stream(f"s{i}")
    while sched.pending_work():
        for sid, chunks in feeds.items():
            if chunks:
                try:
                    sched.submit_chunk(sid, chunks[0])
                except StreamBusy:
                    continue  # retry next tick — backpressure in action
                chunks.pop(0)
                if not chunks:
                    sched.close(sid)
        sched.step()
    for sid, (rb, rm) in refs.items():
        bits, metric = sched.results[sid]
        np.testing.assert_array_equal(bits, np.asarray(rb[0]))
        assert abs(metric - float(rm[0])) < 1e-3 * max(1.0, abs(float(rm[0])))


@pytest.mark.parametrize("backend,chunk", [("scan", 16), ("fused_packed", 32)])
def test_starved_slot_idles_without_corruption(backend, chunk, rng):
    """A stream fed in bursts with long gaps starves its slot for several
    ticks while a neighbor keeps decoding: the starved slot's carried state
    must be untouched by the masked ticks (bit-exact decode, no eviction)."""
    _, bm_a = _noisy_bm(rng, 8 * chunk - 2, 0.05)
    _, bm_b = _noisy_bm(jax.random.fold_in(rng, 1), 6 * chunk - 2, 0.05)
    ref_a, _ = viterbi_decode(CODE, bm_a)
    ref_b, _ = viterbi_decode(CODE, bm_b)
    sched = StreamScheduler(
        CODE, n_slots=2, chunk=chunk, depth=16 * chunk, backend=backend
    )
    sched.submit("a", bm_a[0])  # fully buffered: never starves
    sched.open_stream("b")
    fed = 0
    table_b = bm_b[0]
    burst = 0
    while sched.pending_work():
        # feed b one chunk every third tick only
        if fed < len(table_b) and burst % 3 == 0:
            n = min(chunk, len(table_b) - fed)
            sched.submit_chunk("b", table_b[fed : fed + n])
            fed += n
            if fed == len(table_b):
                sched.close("b")
        burst += 1
        sched.step()
        assert "b" in {st.stream_id for st in sched.active.values()} or (
            "b" in sched.results
        )  # starvation never evicts
    assert sched.stats.starved_slot_ticks > 0
    np.testing.assert_array_equal(sched.results["a"][0], np.asarray(ref_a[0]))
    np.testing.assert_array_equal(sched.results["b"][0], np.asarray(ref_b[0]))


def test_submit_is_adapter_over_chunk_path(rng, monkeypatch):
    """submit() routes through open_stream + submit_chunk + close — there is
    no second ingestion path left in the scheduler."""
    sched = StreamScheduler(CODE, n_slots=2, chunk=16, depth=30, backend="scan")
    calls = {"open": 0, "chunk": 0}
    orig_open, orig_chunk = sched.open_stream, sched.submit_chunk

    def open_spy(*a, **k):
        calls["open"] += 1
        return orig_open(*a, **k)

    def chunk_spy(*a, **k):
        calls["chunk"] += 1
        return orig_chunk(*a, **k)

    monkeypatch.setattr(sched, "open_stream", open_spy)
    monkeypatch.setattr(sched, "submit_chunk", chunk_spy)
    _, bm = _noisy_bm(rng, 62)
    ref, _ = viterbi_decode(CODE, bm)
    sched.submit("s", bm[0])
    st = next(iter(sched.active.values()))
    assert st.closed  # the adapter closed it
    out = sched.run()
    assert calls == {"open": 1, "chunk": 1}
    np.testing.assert_array_equal(out["s"][0], np.asarray(ref[0]))


# --------------------------------------------------------------------------- #
# backpressure                                                                 #
# --------------------------------------------------------------------------- #


def test_submit_chunk_credit_and_stream_busy(rng):
    sched = StreamScheduler(
        CODE, n_slots=1, chunk=16, depth=30, backend="scan", max_buffered=32
    )
    _, bm = _noisy_bm(rng, 126)
    table = bm[0]
    sched.open_stream("s")
    assert sched.credit("s") == 32
    credit = sched.submit_chunk("s", table[:20])
    assert credit == 12 == sched.credit("s")
    with pytest.raises(StreamBusy) as exc:
        sched.submit_chunk("s", table[20:40])  # 20 > 12
    assert exc.value.credit == 12 and exc.value.offered == 20
    assert sched.stats.busy_rejections == 1
    assert sched.credit("s") == 12  # rejected chunk took nothing
    sched.step()  # consumes one decode chunk -> credit recovers
    assert sched.credit("s") == 28
    sched.submit_chunk("s", table[20:40])
    fed = 40  # feed the rest within credit, ticking to drain the queue
    while fed < len(table):
        n = min(sched.credit("s"), len(table) - fed)
        if n:
            sched.submit_chunk("s", table[fed : fed + n])
            fed += n
        sched.step()
    sched.close("s")
    out = sched.run()
    ref, _ = viterbi_decode(CODE, bm)
    np.testing.assert_array_equal(out["s"][0], np.asarray(ref[0]))


def test_backpressure_bounds_queue_depth(rng):
    """A producer can never push a stream's unconsumed rows past its bound,
    no matter how fast it generates."""
    _, bm = _noisy_bm(rng, 510)
    sched = StreamScheduler(
        CODE, n_slots=1, chunk=16, depth=30, backend="scan", max_buffered=48
    )
    sched.open_stream("s", producer=iter([bm[0]]))  # one 512-row burst
    depths = []
    while sched.pending_work():
        sched.step()
        depths.append(sched.load_report()["queued_rows_total"])
    assert max(depths) <= 48
    ref, _ = viterbi_decode(CODE, bm)
    np.testing.assert_array_equal(sched.results["s"][0], np.asarray(ref[0]))


def test_producer_fed_run_drains_everything(rng):
    """run() busy-polls producer-fed streams to completion; generator sizes
    are decoupled from chunk and credit."""
    sched = StreamScheduler(
        CODE, n_slots=2, chunk=16, depth=300, backend="scan", max_buffered=40
    )
    refs = {}
    for i in range(5):
        _, bm = _noisy_bm(jax.random.fold_in(rng, i), (80, 130, 62)[i % 3])
        refs[f"s{i}"] = viterbi_decode(CODE, bm)
        sched.open_stream(
            f"s{i}", producer=_chunks_of(bm[0], (9, 33, 5, 70, 21, 48))
        )
    out = sched.run()
    for sid, (rb, rm) in refs.items():
        np.testing.assert_array_equal(out[sid][0], np.asarray(rb[0]))
        assert abs(out[sid][1] - float(rm[0])) < 1e-3 * max(1.0, abs(float(rm[0])))


def test_run_raises_on_starved_stream_without_producer(rng):
    sched = StreamScheduler(CODE, n_slots=1, chunk=16, depth=30, backend="scan")
    sched.open_stream("stuck")
    sched.submit_chunk("stuck", _noisy_bm(rng, 6)[1][0])  # < one chunk, no close
    with pytest.raises(RuntimeError, match="starved with no producer"):
        sched.run()
    sched.close("stuck")  # now it can retire
    out = sched.run()
    assert "stuck" in out


# --------------------------------------------------------------------------- #
# lifecycle edges of the chunk path                                            #
# --------------------------------------------------------------------------- #


def test_open_close_zero_rows(rng):
    """open + close with no rows at all: retires with empty bits, slot
    recycled, later streams unaffected."""
    sched = StreamScheduler(CODE, n_slots=1, chunk=16, depth=30, backend="scan")
    sched.open_stream("empty")
    sched.close("empty")
    _, bm = _noisy_bm(rng, 62)
    ref, _ = viterbi_decode(CODE, bm)
    sched.submit("real", bm[0])
    out = sched.run()
    assert out["empty"][0].shape == (0,)
    np.testing.assert_array_equal(out["real"][0], np.asarray(ref[0]))


def test_early_close_mid_chunk_tail(rng):
    """close() with a buffered sub-chunk tail (the connection dropped):
    the tail is finalized through the grouped tail-feed, bit-exact."""
    sched = StreamScheduler(CODE, n_slots=2, chunk=32, depth=200, backend="scan")
    _, bm = _noisy_bm(rng, 75)  # 77 steps: 2 full chunks + 13-row tail
    ref, _ = viterbi_decode(CODE, bm)
    sched.open_stream("s")
    sched.submit_chunk("s", bm[0][:64])
    sched.step()
    sched.submit_chunk("s", bm[0][64:])  # 13 rows
    sched.close("s")
    out = sched.run()
    np.testing.assert_array_equal(out["s"][0], np.asarray(ref[0]))


def test_chunk_api_validation(rng):
    # a queue bound below one decode chunk could never fill a tick: the
    # stream would starve forever with zero credit — rejected up front
    with pytest.raises(ValueError, match="max_buffered"):
        StreamScheduler(CODE, n_slots=1, chunk=16, backend="scan", max_buffered=8)
    sched = StreamScheduler(CODE, n_slots=1, chunk=16, depth=30, backend="scan")
    with pytest.raises(ValueError, match="max_buffered"):
        sched.open_stream("tiny-bound", max_buffered=4)
    with pytest.raises(KeyError, match="unknown or finished"):
        sched.submit_chunk("nope", np.zeros((4, CODE.n_symbols), np.float32))
    with pytest.raises(KeyError, match="unknown or finished"):
        sched.close("nope")
    sched.open_stream("s")
    with pytest.raises(KeyError, match="duplicate"):
        sched.open_stream("s")
    with pytest.raises(ValueError, match="shaped"):
        sched.submit_chunk("s", np.zeros((4, 3), np.float32))
    sched.close("s")
    with pytest.raises(RuntimeError, match="closed"):
        sched.submit_chunk("s", np.zeros((4, CODE.n_symbols), np.float32))
    sched.run()
    with pytest.raises(KeyError):  # finished streams are gone from the intake
        sched.close("s")


def test_evict_pending_chunk_fed_stream(rng):
    """Evicting a stream that queued rows but never got a slot drops its
    host-side queue cleanly."""
    sched = StreamScheduler(CODE, n_slots=1, chunk=16, depth=30, backend="scan")
    _, bm_a = _noisy_bm(rng, 158)
    sched.submit("a", bm_a[0])
    sched.open_stream("b")
    sched.submit_chunk("b", _noisy_bm(jax.random.fold_in(rng, 1), 62)[1][0])
    assert sched.evict("b") is None  # pending: nothing committed
    out = sched.run()
    assert set(out) == {"a"}


def test_load_report_queue_depth_stats(rng):
    sched = StreamScheduler(
        CODE, n_slots=2, chunk=16, depth=30, backend="scan", max_buffered=64
    )
    _, bm = _noisy_bm(rng, 62)
    sched.open_stream("starved")  # admitted, nothing buffered
    sched.open_stream("fed")
    sched.submit_chunk("fed", bm[0][:40])
    report = sched.load_report()
    assert report["active_total"] == 2
    assert report["queued_rows_total"] == 40
    assert report["starved_active"] >= 1  # 'starved' holds no full chunk
    assert sum(report["per_shard_queued_rows"]) == 40
    sched.submit_chunk("fed", bm[0][40:], close=True)
    sched.close("starved")
    sched.run()
    assert sched.load_report()["queued_rows_total"] == 0


# --------------------------------------------------------------------------- #
# arrival-schedule fuzz (hypothesis)                                           #
# --------------------------------------------------------------------------- #

try:
    from hypothesis import example, given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # dev-only dep: the fuzz leg runs in CI
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @st.composite
    def arrival_schedules(draw):
        """Per-stream arrival plans: chunk sizes (bursty), starvation gaps,
        and whether the stream closes early (truncating mid-chunk)."""
        n_streams = draw(st.integers(2, 4))
        plans = []
        for _ in range(n_streams):
            info_bits = draw(st.integers(20, 140))
            sizes = draw(st.lists(st.integers(1, 70), min_size=1, max_size=8))
            gap = draw(st.integers(0, 3))  # ticks between deliveries
            early_close = draw(st.integers(0, 1))
            plans.append((info_bits, tuple(sizes), gap, early_close))
        seed = draw(st.integers(0, 2 ** 16))
        return plans, seed

else:  # pragma: no cover - placeholder so the skip is visible in reports

    def arrival_schedules():
        return None

    def given(*_a, **_k):
        return pytest.mark.skip(reason="hypothesis not installed")

    def settings(*_a, **_k):
        return lambda fn: fn

    example = settings


@settings(max_examples=12, deadline=None)
@given(arrival_schedules())
# a chunk larger than the credit left after a one-row delivery: it fits
# only if the feeder splits it at the credit StreamBusy reports
@example(([(20, (1,), 0, 0), (127, (1,), 0, 0)], 0))
def test_fuzz_arrival_schedule_invariance(case):
    """However chunks arrive — bursty, starved, early-closed — the online
    decode is bit-identical to one-shot submit() of the same rows."""
    plans, seed = case
    key = jax.random.PRNGKey(seed)
    # full telemetry on the online side: tracing + metrics + device counters
    # must observe the decode, never perturb it — the invariance holds with
    # the instrumented tick vs the bare offline scheduler
    online = StreamScheduler(CODE, n_slots=2, chunk=16, depth=400, backend="scan",
                             telemetry=Telemetry.enabled(device_counters=True))
    offline = StreamScheduler(CODE, n_slots=2, chunk=16, depth=400, backend="scan")
    feeds = {}
    for i, (info_bits, sizes, gap, early_close) in enumerate(plans):
        _, bm = _noisy_bm(jax.random.fold_in(key, i), info_bits, 0.04)
        table = bm[0]
        chunks = _chunks_of(table, sizes)
        if early_close:
            chunks = chunks[: max(1, len(chunks) - 1)]  # drop the tail: early EOF
        actual = np.concatenate(chunks, axis=0)
        sid = f"s{i}"
        offline.submit(sid, actual)
        online.open_stream(sid)
        feeds[sid] = {"chunks": chunks, "gap": gap, "wait": 0}
    guard = 0
    while online.pending_work():
        for sid, f in feeds.items():
            if not f["chunks"]:
                continue
            if f["wait"] > 0:
                f["wait"] -= 1
                continue
            try:
                online.submit_chunk(sid, f["chunks"][0])
            except StreamBusy as busy:
                # a producer honours backpressure by sending what fits
                # and keeping the rest for a later tick
                if busy.credit == 0:
                    continue
                head = f["chunks"][0]
                online.submit_chunk(sid, head[: busy.credit])
                f["chunks"][0] = head[busy.credit :]
                continue
            f["chunks"].pop(0)
            f["wait"] = f["gap"]
            if not f["chunks"]:
                online.close(sid)
        online.step()
        guard += 1
        assert guard < 2000, "online drain did not converge"
    out_online, out_offline = online.results, offline.run()
    for sid in out_offline:
        np.testing.assert_array_equal(out_online[sid][0], out_offline[sid][0])
        assert abs(out_online[sid][1] - out_offline[sid][1]) <= 1e-3 * max(
            1.0, abs(out_offline[sid][1])
        )
