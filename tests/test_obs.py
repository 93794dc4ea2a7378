"""Telemetry plane: metrics / tracing / logging primitives, device-side
decode counters, and the scheduler + session instrumentation contract.

The load-bearing guarantees under test:

  * the metric primitives are exact where they claim exactness (count, sum,
    min, max) and ordered where they claim order (p50 <= p95);
  * decode output is bit-identical with telemetry on — tracing and device
    counters observe, never perturb;
  * device counters add ZERO per-tick host syncs: the tick's only
    device->host materialization stays the committed-bits transfer (spied
    on below by counting ``np.asarray(jax.Array)`` calls);
  * ``survivor_merge_depth`` matches a brute-force walker oracle;
  * every ``load_report()`` field exists and satisfies its invariant on the
    single-device AND the unit-mesh sharded scheduler.
"""
import io
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CODE_K3_STD,
    bsc,
    encode,
    hard_branch_metrics,
)
from repro.decode import CodecSpec, DecodeRequest, decode, plan_decode
from repro.obs import (
    MetricsRegistry,
    Telemetry,
    Tracer,
    percentile,
    span,
)
from repro.obs.log import get_logger, kv
from repro.obs.metrics import Histogram
from repro.parallel.collectives import reduce_across_shards
from repro.stream import StreamScheduler, StreamSession
from repro.stream import window as _w
from repro.stream.scheduler import TICK_PHASES

CODE = CODE_K3_STD


def _noisy_bm(code, key, batch, info_bits, flip=0.04):
    bits = jax.random.bernoulli(key, 0.5, (batch, info_bits)).astype(jnp.int32)
    coded = encode(code, bits, terminate=True)
    rx = bsc(jax.random.fold_in(key, 1), coded, flip)
    return bits, hard_branch_metrics(code, rx)


# --------------------------------------------------------------------------- #
# metrics primitives                                                           #
# --------------------------------------------------------------------------- #


def test_percentile_nearest_rank():
    vals = [5.0, 1.0, 3.0, 2.0, 4.0]  # unsorted on purpose
    assert percentile(vals, 0.0) == 1.0
    assert percentile(vals, 0.5) == 3.0
    assert percentile(vals, 0.95) == 5.0
    assert percentile(vals, 1.0) == 5.0


def test_percentile_empty_and_bounds():
    assert percentile([], 0.5) == 0.0
    assert percentile([], 0.5, default=-1.0) == -1.0
    with pytest.raises(ValueError):
        percentile([1.0], 1.5)
    with pytest.raises(ValueError):
        percentile([1.0], -0.1)


def test_counter_and_gauge():
    m = MetricsRegistry()
    c = m.counter("ticks")
    c.inc()
    c.inc(3)
    assert c.value == 4
    c.set(10)  # absorbing an external monotone count
    assert c.value == 10
    g = m.gauge("depth")
    g.set(2.5)
    g.inc()
    g.dec(0.5)
    assert g.value == 3.0


def test_histogram_exact_envelope_and_quantiles():
    h = Histogram("lat", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 100.0):  # last lands in the +inf overflow
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(105.0)
    assert h.min == 0.5 and h.max == 100.0
    assert h.counts == [1, 1, 1, 1]
    # bucket-upper estimate, clamped into the exact [min, max] envelope
    assert h.quantile(0.5) == 2.0
    assert h.quantile(1.0) == 100.0
    s = h.summary()
    assert set(s) == {"count", "mean", "p50", "p95", "max"}
    assert s["p50"] <= s["p95"] <= s["max"]


def test_histogram_single_observation_is_exact():
    h = Histogram("one", buckets=(1.0, 4.0))
    h.observe(3.0)
    # 3.0 falls in the le=4 bucket, but clamping reports the sample itself
    assert h.quantile(0.5) == 3.0 == h.quantile(0.95) == h.max == h.min


def test_histogram_empty_summary():
    h = Histogram("empty", buckets=(1.0,))
    assert h.summary() == {"count": 0, "mean": 0.0, "p50": 0.0, "p95": 0.0,
                           "max": 0.0}


def test_registry_get_or_create_and_kind_mismatch():
    m = MetricsRegistry()
    assert m.counter("x") is m.counter("x")
    with pytest.raises(TypeError):
        m.gauge("x")
    m.histogram("h", buckets=(1, 2)).observe(1.5)
    snap = m.snapshot()
    assert snap["x"] == 0.0
    assert snap["h"]["count"] == 1
    assert list(snap) == sorted(snap)


def test_registry_prometheus_render():
    m = MetricsRegistry()
    m.counter("reqs", help="requests").inc(2)
    m.gauge("util").set(0.5)
    m.histogram("lat", buckets=(1.0, 2.0)).observe(1.5)
    text = m.render()
    assert "# HELP reqs requests" in text
    assert "# TYPE reqs counter" in text and "reqs 2" in text
    assert "# TYPE util gauge" in text
    assert '# TYPE lat histogram' in text
    assert 'lat_bucket{le="2"} 1' in text  # cumulative
    assert 'lat_bucket{le="+Inf"} 1' in text
    assert "lat_count 1" in text


# --------------------------------------------------------------------------- #
# tracing                                                                      #
# --------------------------------------------------------------------------- #


def test_span_disabled_is_noop():
    s = span(None, "anything")
    with s:
        pass
    assert span(None, "other") is s  # one shared instance, no allocation


def test_tracer_records_nested_spans_and_coverage():
    tr = Tracer("test")
    with span(tr, "tick"):
        with span(tr, "step"):
            pass
        with span(tr, "commit"):
            pass
    assert len(tr) == 3
    assert tr.durations_s("tick") and tr.total_s("tick") > 0
    cov = tr.coverage("tick", ("step", "commit"))
    assert 0.0 < cov <= 1.0
    assert tr.coverage("missing", ("step",)) == 0.0
    tr.clear()
    assert len(tr) == 0


def test_tracer_spans_are_absolute_perf_counter_times():
    tr = Tracer("test")
    before = time.perf_counter_ns()
    with span(tr, "outer"):
        with span(tr, "inner"):
            pass
    after = time.perf_counter_ns()
    spans = tr.spans()
    assert [n for n, _, _ in spans] == ["inner", "outer"]  # in completion order
    for _, t0, dur in spans:
        assert before <= t0 and t0 + dur <= after
    (_, t_in, d_in), (_, t_out, d_out) = spans
    assert t_out <= t_in and t_in + d_in <= t_out + d_out
    spans.clear()  # a copy: the tracer keeps its record
    assert len(tr) == 2


def test_phase_spans_tile_their_parent():
    """``first`` starts a child when its parent started and ``then`` starts a
    span when the previous one ended, so phases cover their parent with no
    gap for the tracer's own cost; disabled, they are the shared no-op."""
    tr = Tracer("test")
    with span(tr, "call") as call:
        with call.first("check") as check:
            time.sleep(0.001)
        with check.then("accept"):
            pass
    (_, t_a, d_a), (_, t_b, d_b), (_, t_p, d_p) = tr.spans()
    assert t_a == t_p and t_b == t_a + d_a and t_b + d_b <= t_p + d_p
    assert 0.9 < tr.coverage("call", ("check", "accept")) <= 1.0
    off = span(None, "call")
    with off as call:
        with call.first("check") as check:
            pass
        with check.then("accept") as accept:
            pass
    assert call is check is accept is off


def test_tracer_chrome_and_jsonl_export(tmp_path):
    tr = Tracer("proc-name")
    with span(tr, "tick"):
        pass
    events = tr.chrome_events()
    meta, ev = events[0], events[1]
    assert meta["ph"] == "M" and meta["args"]["name"] == "proc-name"
    assert ev["ph"] == "X" and ev["name"] == "tick"
    assert ev["ts"] >= 0 and ev["dur"] >= 0 and ev["pid"] == 1
    tr.write_chrome(tmp_path / "trace.json")
    payload = json.loads((tmp_path / "trace.json").read_text())
    assert payload["traceEvents"][1]["name"] == "tick"
    tr.write_jsonl(tmp_path / "trace.jsonl")
    lines = (tmp_path / "trace.jsonl").read_text().splitlines()
    assert len(lines) == 1
    assert json.loads(lines[0])["name"] == "tick"


# --------------------------------------------------------------------------- #
# structured logging                                                           #
# --------------------------------------------------------------------------- #


def test_kv_formatting():
    line = kv(a=1, rate=123456.789, label="two words", flag=True)
    assert "a=1" in line
    assert "rate=123457" in line  # 6 significant digits
    assert "label='two words'" in line
    assert "flag=True" in line


def test_get_logger_structured_lines_and_quiet():
    buf = io.StringIO()
    log = get_logger("test-obs", stream=buf)
    log.info("tick done", bits=64, elapsed_s=0.25)
    assert "tick done bits=64 elapsed_s=0.25" in buf.getvalue()

    quiet_buf = io.StringIO()
    log = get_logger("test-obs", quiet=True, stream=quiet_buf)
    log.info("suppressed", n=1)
    log.warning("kept", n=2)
    out = quiet_buf.getvalue()
    assert "suppressed" not in out and "kept n=2" in out
    # reconfiguration replaced (not stacked) the handler: exactly one line
    assert out.count("\n") == 1


# --------------------------------------------------------------------------- #
# survivor merge depth: device computation vs brute-force oracle               #
# --------------------------------------------------------------------------- #


def _merge_depth_oracle(code, ring):
    """Walk all S survivor paths back from the frontier one step at a time;
    the merge depth is the first step at which they all sit on one node."""
    ring = np.asarray(ring)
    R, B, S = ring.shape
    half = S // 2
    out = np.full((B,), R + 1, dtype=np.int32)
    for b in range(B):
        walkers = np.arange(S)
        for d, i in enumerate(range(R - 1, -1, -1), start=1):
            j = ring[i, b][walkers]
            v = walkers & (half - 1) if half > 1 else np.zeros_like(walkers)
            walkers = 2 * v + j
            if (walkers == walkers[0]).all():
                out[b] = d
                break
    return out


def test_survivor_merge_depth_matches_oracle(rng):
    sess = StreamSession(CODE, batch=4, chunk=16, depth=16, backend="scan")
    _, bm = _noisy_bm(CODE, rng, 4, 94)
    for i in range(4):  # 64 steps: the (R=32)-deep ring is fully real
        sess.push(bm[:, i * 16 : (i + 1) * 16])
    got = np.asarray(_w.survivor_merge_depth(CODE, sess.state.ring))
    np.testing.assert_array_equal(got, _merge_depth_oracle(CODE, sess.state.ring))
    assert (1 <= got).all() and (got <= sess.ring_size + 1).all()


def test_survivor_merge_depth_unpacks_packed_rings(rng):
    sess = StreamSession(CODE, batch=2, chunk=32, depth=32, backend="fused_packed")
    _, bm = _noisy_bm(CODE, rng, 2, 126)
    for i in range(2):
        sess.push(bm[:, i * 32 : (i + 1) * 32])
    assert sess.state.ring.dtype == jnp.uint32
    got = np.asarray(_w.survivor_merge_depth(CODE, sess.state.ring))
    unpacked = _w.unpack_ring(CODE, sess.state.ring)
    np.testing.assert_array_equal(got, _merge_depth_oracle(CODE, unpacked))


def test_never_merging_ring_reports_sentinel():
    # identity backpointers (j == 0 for even, parity split) never coalesce
    # beyond construction: an all-zeros ring sends every walker to state
    # floor(s/2)*... -- easier: two states that map to themselves forever.
    R, B, S = 8, 1, CODE.n_states
    ring = np.zeros((R, B, S), dtype=np.int32)
    ring[:, :, :] = np.arange(S) % 2  # prev = 2*(s & 1) + (s % 2): fixed pts
    got = np.asarray(_w.survivor_merge_depth(CODE, jnp.asarray(ring)))
    oracle = _merge_depth_oracle(CODE, ring)
    np.testing.assert_array_equal(got, oracle)
    assert (got == R + 1).all()  # walkers 0 and 3 never meet


# --------------------------------------------------------------------------- #
# session telemetry                                                            #
# --------------------------------------------------------------------------- #


def test_session_device_counters_leave_decode_unchanged(rng):
    _, bm = _noisy_bm(CODE, rng, 3, 126)
    plain = StreamSession(CODE, batch=3, chunk=16, depth=30, backend="scan")
    tel = Telemetry.enabled()
    traced = StreamSession(
        CODE, batch=3, chunk=16, depth=30, backend="scan", telemetry=tel
    )
    bits_p, metric_p = plain.decode_all(bm)
    bits_t, metric_t = traced.decode_all(bm)
    np.testing.assert_array_equal(np.asarray(bits_p), np.asarray(bits_t))
    np.testing.assert_allclose(
        np.asarray(metric_p), np.asarray(metric_t), rtol=1e-6
    )
    # push + finish spans were recorded
    assert len(tel.tracer.durations_s("push")) == 8  # 128 // 16 full chunks
    assert len(tel.tracer.durations_s("finish")) == 1
    rep = traced.device_counter_report()
    assert rep["ticks"] == [8, 8, 8]
    assert all(1 <= d <= traced.ring_size + 1 for d in rep["merge_depth_last"])
    assert all(m >= 1 for m in rep["merge_depth_mean"])
    assert all(r >= 0 for r in rep["renorm_sum"])


def test_session_counter_report_requires_flag():
    sess = StreamSession(CODE, batch=1, chunk=16, backend="scan")
    with pytest.raises(RuntimeError):
        sess.device_counter_report()


# --------------------------------------------------------------------------- #
# scheduler telemetry                                                          #
# --------------------------------------------------------------------------- #


def _run_workload(sched, bm_by_id):
    for sid, bm in bm_by_id.items():
        sched.submit(sid, bm)
    return sched.run()


def _make_streams(rng, n, info_bits=94):
    _, bm = _noisy_bm(CODE, rng, n, info_bits)
    return {f"s{i}": bm[i] for i in range(n)}


def test_scheduler_decode_bit_exact_with_full_telemetry(rng):
    streams = _make_streams(rng, 3)
    plain = StreamScheduler(CODE, n_slots=2, chunk=16, depth=30, backend="scan")
    out_p = _run_workload(plain, streams)
    tel = Telemetry.enabled()
    traced = StreamScheduler(
        CODE, n_slots=2, chunk=16, depth=30, backend="scan", telemetry=tel
    )
    out_t = _run_workload(traced, streams)
    for sid in streams:
        np.testing.assert_array_equal(out_p[sid][0], out_t[sid][0])


def test_scheduler_tick_phase_coverage_and_stats_mirror(rng):
    tel = Telemetry.enabled(device_counters=False)
    sched = StreamScheduler(
        CODE, n_slots=2, chunk=16, depth=30, backend="scan", telemetry=tel
    )
    _run_workload(sched, _make_streams(rng, 3))
    tr = tel.tracer
    # every advancing tick gets a span; idle polls (nothing ready) are also
    # spanned but don't count as scheduler ticks
    assert len(tr.durations_s("tick")) >= sched.stats.ticks > 0
    # the named phases account for (at least) 95% of tick wall clock
    assert tr.coverage("tick", TICK_PHASES) >= 0.95
    snap = sched.metrics_snapshot()
    for name, v in sched.stats.asdict().items():
        assert snap[f"scheduler_{name}"] == v
    assert snap["scheduler_active_slots"] == 0  # drained
    assert snap["scheduler_utilization"] == 0.0
    text = sched.metrics_text()
    assert "# TYPE scheduler_ticks counter" in text
    assert "stream_arrival_to_commit_seconds_count" in text


def test_scheduler_stats_deterministic_accounting(rng):
    n, info_bits = 3, 94
    sched = StreamScheduler(CODE, n_slots=2, chunk=16, depth=30, backend="scan",
                            telemetry=Telemetry.enabled())
    _run_workload(sched, _make_streams(rng, n, info_bits))
    T = info_bits + CODE.constraint - 1  # terminated: bits + flush
    s = sched.stats
    assert s.streams_submitted == s.streams_finished == s.slot_claims == n
    assert s.steps_decoded == n * T
    assert s.chunks_submitted == n
    assert s.busy_rejections == 0
    # one merge-depth observation per retiring stream
    assert sched.telemetry.metrics.histogram("stream_merge_depth").count == n


def _check_load_report_fields(report, n_shards, device_counters):
    for field in ("n_shards", "per_shard_active", "per_shard_queued_rows",
                  "active_total", "pending_total", "queued_rows_total",
                  "pending_rows", "max_stream_queued_rows", "starved_active",
                  "utilization", "latency_s"):
        assert field in report, f"load_report missing {field}"
    assert report["n_shards"] == n_shards
    assert len(report["per_shard_active"]) == n_shards
    assert len(report["per_shard_queued_rows"]) == n_shards
    assert report["active_total"] == sum(report["per_shard_active"])
    assert 0.0 <= report["utilization"] <= 1.0
    lat = report["latency_s"]
    assert set(lat) == {"count", "mean", "p50", "p95", "max"}
    assert 0 <= lat["mean"] <= lat["max"] or lat["count"] == 0
    assert lat["p50"] <= lat["p95"]
    assert ("merge_depth" in report) == device_counters


@pytest.mark.parametrize("device_counters", [False, True])
def test_load_report_fields_single_device(rng, device_counters):
    tel = Telemetry.enabled(device_counters=device_counters)
    sched = StreamScheduler(
        CODE, n_slots=2, chunk=16, depth=60, backend="scan", telemetry=tel
    )
    for sid, bm in _make_streams(rng, 2, info_bits=126).items():
        sched.submit(sid, bm)
    for _ in range(3):  # mid-flight: streams still admitted + decoding
        sched.step()
    report = sched.load_report()
    _check_load_report_fields(report, n_shards=1, device_counters=device_counters)
    assert report["active_total"] == 2
    if device_counters:
        md = report["merge_depth"]
        assert set(md) == {"s0", "s1"}
        R = sched.depth + sched.chunk
        for row in md.values():
            assert set(row) == {"ticks", "starved_ticks", "merge_depth_last",
                                "merge_depth_mean", "merge_depth_max",
                                "renorm_sum"}
            assert row["ticks"] == 3
            assert 1 <= row["merge_depth_last"] <= R + 1
            assert row["merge_depth_mean"] <= row["merge_depth_max"] <= R + 1
    sched.run()
    done = sched.load_report()
    assert done["active_total"] == 0 and done["latency_s"]["count"] >= 2


def test_device_counter_report_requires_flag(rng):
    sched = StreamScheduler(CODE, n_slots=2, chunk=16, backend="scan")
    with pytest.raises(RuntimeError):
        sched.device_counter_report()


def test_device_counters_add_no_per_tick_host_syncs(rng, sanitized_guards):
    """THE zero-sync guarantee: with device counters on, a steady-state tick
    materializes exactly one device array on the host — the committed bits —
    same as with telemetry off entirely.  Runs under the full sanitizer
    bundle (transfer guard + debug-NaNs + recompile counter), with the
    original np.asarray spy kept as an independent cross-check on the
    guard's own host-sync counter."""
    with sanitized_guards.allow_transfers():  # control plane may move data
        streams = _make_streams(rng, 2, info_bits=158)  # 160 steps = 10 ticks
        sched = StreamScheduler(
            CODE, n_slots=2, chunk=16, depth=30, backend="scan",
            telemetry=Telemetry.enabled(device_counters=True),
        )
        for sid, bm in streams.items():
            sched.submit(sid, bm)
        # warm here: trace + compile land before the snapshot, so the
        # steady-state recompile assertion below is a real zero-delta check
        sched.step()

    real_asarray = np.asarray  # already the guard's counting wrapper
    raw_asarray = getattr(real_asarray, "_orig", real_asarray)
    sync_counts = []

    def spy(a, *args, **kwargs):
        caller = sys._getframe(1).f_globals.get("__name__", "")
        if caller == "jax" or caller.startswith("jax."):
            # debug_nans output checks: sanitizer overhead, not user syncs —
            # bypass the guard's counter the same way it would filter them
            return raw_asarray(a, *args, **kwargs)
        if isinstance(a, jax.Array):
            sync_counts.append(1)
        return real_asarray(a, *args, **kwargs)

    np.asarray = spy
    try:
        base = sanitized_guards.snapshot()
        for _ in range(4):  # steady-state ticks, far from the final drain
            before = len(sync_counts)
            tick_base = sanitized_guards.snapshot()
            sched.step()
            assert len(sync_counts) - before == 1, (
                "device counters leaked an extra per-tick host sync"
            )
            assert sanitized_guards.host_syncs - tick_base.host_syncs == 1, (
                "sanitizer host-sync counter disagrees with the spy"
            )
        assert sanitized_guards.recompiles == base.recompiles, (
            "steady-state tick recompiled — shape leak in the tick body"
        )
    finally:
        np.asarray = real_asarray
    with sanitized_guards.allow_transfers():  # drain: finishing slots is
        sched.run()                           # control plane, not the tick


# --------------------------------------------------------------------------- #
# sharded (unit-mesh) scheduler telemetry                                      #
# --------------------------------------------------------------------------- #


def test_sharded_scheduler_telemetry_bit_exact_and_report(rng, mesh11):
    streams = _make_streams(rng, 3)
    plain = StreamScheduler(CODE, n_slots=2, chunk=16, depth=30, backend="scan")
    out_p = _run_workload(plain, streams)
    tel = Telemetry.enabled(device_counters=True)
    sharded = StreamScheduler(
        CODE, n_slots=2, chunk=16, depth=30, backend="scan",
        mesh=mesh11, telemetry=tel,
    )
    for sid, bm in streams.items():
        sharded.submit(sid, bm)
    for _ in range(3):
        sharded.step()
    report = sharded.load_report()
    _check_load_report_fields(report, n_shards=1, device_counters=True)
    for row in report["merge_depth"].values():
        assert row["ticks"] > 0
        assert 1 <= row["merge_depth_last"] <= sharded.depth + sharded.chunk + 1
    out_t = sharded.run()
    for sid in streams:
        np.testing.assert_array_equal(out_p[sid][0], out_t[sid][0])
    assert tel.tracer.coverage("tick", TICK_PHASES) >= 0.95
    assert sharded.load_report()["latency_s"]["count"] >= 3
    assert (
        sharded.telemetry.metrics.histogram("stream_merge_depth").count == 3
    )


def test_reduce_across_shards_ops(mesh11):
    per_shard = jnp.asarray([[3.0, -1.0, 2.0]])  # (n_shards=1, 3)
    for op, expect in (("sum", [3.0, -1.0, 2.0]),
                       ("max", [3.0, -1.0, 2.0]),
                       ("min", [3.0, -1.0, 2.0])):
        got = reduce_across_shards(mesh11, "data", per_shard, op=op)
        np.testing.assert_allclose(np.asarray(got), expect)
    with pytest.raises(ValueError):
        reduce_across_shards(mesh11, "data", per_shard, op="mean")


# --------------------------------------------------------------------------- #
# decode() and submit_chunk spans, the arena-append counter                    #
# --------------------------------------------------------------------------- #

DECODE_PHASES = ("decode.plan", "decode.check", "decode.dispatch")


@pytest.mark.parametrize("inputs", ["received", "bm_tables"])
def test_decode_spans_cover_the_call_and_leave_bits_unchanged(rng, inputs):
    spec = CodecSpec()
    bits = jax.random.bernoulli(rng, 0.5, (4, 48)).astype(jnp.int32)
    rx = spec.channel(jax.random.fold_in(rng, 1), spec.encode(bits), flip_prob=0.01)
    if inputs == "received":
        req = DecodeRequest(spec, received=rx)
    else:
        req = DecodeRequest(spec, bm_tables=DecodeRequest(spec, received=rx).metrics())
    plain = decode(req)
    tel = Telemetry.enabled(device_counters=False)
    for _ in range(2):  # the first call compiles; both are spanned
        traced = decode(req, telemetry=tel)
    np.testing.assert_array_equal(np.asarray(plain.bits), np.asarray(traced.bits))
    tr = tel.tracer
    names = [n for n, _, _ in tr.spans()]
    assert names.count("decode") == names.count("decode.plan") == 2
    assert names.count("decode.dispatch") == 2
    # the host copy + finiteness test runs on the raw-symbol path only
    assert names.count("decode.check") == (2 if inputs == "received" else 0)
    assert tr.coverage("decode", DECODE_PHASES) >= 0.95
    # every child lies inside a decode span
    parents = [(t0, t0 + d) for n, t0, d in tr.spans() if n == "decode"]
    for n, t0, d in tr.spans():
        if n in DECODE_PHASES:
            assert any(a <= t0 and t0 + d <= b for a, b in parents)
    # no tracer (telemetry None, or a bundle without one) records nothing
    n_before = len(tr)
    quiet = decode(req, telemetry=Telemetry())
    np.testing.assert_array_equal(np.asarray(plain.bits), np.asarray(quiet.bits))
    assert len(tr) == n_before


def _inside(child, parents):
    _, t0, d = child
    return any(a <= t0 and t0 + d <= b for a, b in parents)


def test_turbo_spans_nest_and_count_iterations():
    """decode() of a 36.212 turbo block: ``turbo`` inside
    ``decode.dispatch``, each ``turbo.iteration`` inside ``turbo`` holding
    one ``turbo.dispatch`` then one ``turbo.sync``, and the telemetry's
    ``turbo_iterations_total`` equal to the iterations run."""
    from repro.siso import RSC_K4_LTE, TurboSpec
    from repro.siso.interleave import lte_qpp

    spec = TurboSpec(RSC_K4_LTE, lte_qpp(40), iterations=8, tail="36.212")
    bits = jax.random.bernoulli(jax.random.PRNGKey(7), 0.5, (4, 40)).astype(jnp.int32)
    coded = spec.encode(bits)
    rx = 1.0 - 2.0 * coded + 0.8 * jax.random.normal(jax.random.PRNGKey(8), coded.shape)
    tel = Telemetry.enabled(device_counters=False)
    runs = [decode(spec, rx, telemetry=tel).diagnostics["iterations"] for _ in range(2)]
    spans = tel.tracer.spans()

    def of(name):
        return [s for s in spans if s[0] == name]

    def outer(name):
        return [(t0, t0 + d) for _, t0, d in of(name)]

    assert len(of("turbo")) == 2
    assert all(_inside(s, outer("decode.dispatch")) for s in of("turbo"))
    assert len(of("turbo.iteration")) == sum(runs)
    assert all(_inside(s, outer("turbo")) for s in of("turbo.iteration"))
    for name in ("turbo.dispatch", "turbo.sync"):
        assert len(of(name)) == sum(runs)
        assert all(_inside(s, outer("turbo.iteration")) for s in of(name))
    for (_, d0, dd), (_, s0, _) in zip(of("turbo.dispatch"), of("turbo.sync")):
        assert d0 + dd <= s0  # the sync follows its iteration's dispatch
    assert tel.metrics.snapshot()["turbo_iterations_total"] == sum(runs)


@pytest.mark.parametrize("sharded", [False, True])
def test_submit_chunk_spans_its_phases(request, rng, sharded):
    """submit_chunk stages on the host: its spans cover the call, no device
    write or arena re-placement runs per chunk, and the next tick writes
    every staged row at once, in one ``arena.write`` inside its gather."""
    mesh = request.getfixturevalue("mesh11") if sharded else None
    tel = Telemetry.enabled(device_counters=False)
    sched = StreamScheduler(CODE, n_slots=2, chunk=16, depth=30, backend="scan",
                            mesh=mesh, telemetry=tel)
    bm = np.asarray(_make_streams(rng, 1, info_bits=62)["s0"])  # 64 rows
    sched.open_stream("s0")
    for k in range(0, 64, 16):
        sched.submit_chunk("s0", bm[k:k + 16])
    tr = tel.tracer
    names = [n for n, _, _ in tr.spans()]
    assert names.count("submit") == 4
    for child in ("submit.check", "submit.accept", "submit.features", "submit.append"):
        assert names.count(child) == 4, child
    # nothing per chunk touches the device: no write, no re-placement
    assert not set(names) & {"submit.pin", "arena.write", "arena.pin"}
    # features and append nest inside accept
    assert 0.9 <= tr.coverage("submit", ("submit.check", "submit.accept")) <= 1.0
    # none of the names collides with a tick phase the benchmark reads
    assert not set(names) & {"tick", *TICK_PHASES}

    tr.clear()
    sched.step()
    spans = tr.spans()
    (write,) = [s for s in spans if s[0] == "arena.write"]
    (gather,) = [s for s in spans if s[0] == "gather"]
    assert gather[1] <= write[1] and write[1] + write[2] <= gather[1] + gather[2]
    # 64 staged rows outgrew the 16-row arena: re-placed once, on a mesh only
    assert [s[0] for s in spans].count("arena.pin") == (1 if sharded else 0)
    tr.clear()
    sched.step()  # nothing staged since: no write
    assert "arena.write" not in [n for n, _, _ in tr.spans()]


def test_arena_appends_count_every_append_and_survive_restore(rng):
    """One append per chunk that lands in the arena: directly for an admitted
    stream, once for the whole backlog of a stream queued before its slot
    was claimed, and never for a refused chunk."""
    tel = Telemetry.enabled(device_counters=False)
    sched = StreamScheduler(CODE, n_slots=1, chunk=16, depth=30, backend="scan",
                            telemetry=tel)
    streams = _make_streams(rng, 2, info_bits=30)  # 32 rows each
    a, b = (np.asarray(streams[s]) for s in ("s0", "s1"))
    sched.open_stream("a")
    sched.open_stream("b")  # no free slot: queues
    sched.submit_chunk("a", a[:16])
    sched.submit_chunk("a", a[16:], close=True)
    assert sched.stats.arena_appends == 2
    sched.submit_chunk("b", b[:16])
    sched.submit_chunk("b", b[16:], close=True)
    assert sched.stats.arena_appends == 2  # queued on the host, not appended
    snap = sched.snapshot()
    restored = StreamScheduler.restore(snap)
    assert restored.stats.arena_appends == 2
    sched.run()  # "a" retires, "b" is admitted: its two chunks land as one
    assert sched.stats.arena_appends == 3
    assert sched.stats.chunks_submitted == 4
    assert sched.metrics_snapshot()["scheduler_arena_appends"] == 3
    assert len(tel.tracer.durations_s("submit.append")) == 3
    restored.run()
    assert restored.stats.arena_appends == 3
    for sid in ("a", "b"):
        np.testing.assert_array_equal(sched.results[sid][0], restored.results[sid][0])


def test_arena_writes_one_per_tick_with_staged_rows(rng):
    """One device write per tick that found rows staged, compaction ticks
    included (the compaction writes them first), and none for a tick that
    found none; appends over writes is the chunks each write batched."""
    sched = StreamScheduler(CODE, n_slots=3, chunk=16, depth=30, backend="scan")
    sched._compact_floor = 0
    sched._compact_ratio = 1  # compact whenever consumed rows are left
    tables = {sid: np.asarray(bm) for sid, bm in _make_streams(rng, 3).items()}
    for sid, t in tables.items():
        sched.open_stream(sid)
        sched.submit_chunk(sid, t[:16])  # one chunk ahead: rows stay live
    compacted_with_staged = 0
    for k in range(16, 96, 16):
        for sid, t in tables.items():
            sched.submit_chunk(sid, t[k:k + 16], close=k + 16 >= len(t))
        writes, compactions = sched.stats.arena_writes, sched.stats.arena_compactions
        sched.step()
        assert sched.stats.arena_writes == writes + 1
        compacted_with_staged += sched.stats.arena_compactions - compactions
    assert compacted_with_staged > 0
    writes = sched.stats.arena_writes
    sched.step()  # consumes the last chunks; nothing was staged
    assert sched.stats.arena_writes == writes == 5
    assert sched.stats.arena_appends == sched.stats.chunks_submitted == 18
    assert sched.metrics_snapshot()["scheduler_arena_writes"] == 5
    out = sched.run()
    whole = _run_workload(
        StreamScheduler(CODE, n_slots=3, chunk=16, depth=30, backend="scan"), tables
    )
    for sid in tables:
        np.testing.assert_array_equal(out[sid][0], whole[sid][0])


# --------------------------------------------------------------------------- #
# planner roofline cost surfacing                                              #
# --------------------------------------------------------------------------- #


def test_planner_predicted_costs_and_explain():
    plan = plan_decode(CODE, (4, 128))
    assert plan.backend == "fused_packed"
    costs = plan.predicted_costs()
    assert costs is not None
    assert costs["flops"] > 0 and costs["bytes"] > 0 and costs["input_bytes"] > 0
    text = plan.explain(costs=True)
    assert "cost:" in text and "flops/byte" in text
    # without the flag the plan summary stays cost-free
    assert "cost:" not in plan.explain()
