"""Streaming vs. block Viterbi throughput — and sharded-scheduler scaling.

All results merge into the ONE benchmark artifact,
``results/BENCH_viterbi.json`` (see benchmarks/README.md): each mode owns a
section and preserves the others, so any invocation order converges to the
same file.  No mode writes a private side-car JSON.

Five modes:

* default: drives the continuous-batching StreamScheduler with >= 64
  concurrent decode sessions multiplexed through ONE jitted chunked Pallas
  call per tick — the ``fused_packed`` pipeline (bit-packed survivor ring +
  on-device traceback, device-resident input arena), plus ``--backend``'s
  loop when that is ``scan`` — and reports sustained decoded bits/s
  against the full-block fused decoder on the same workload, re-checking the
  two correctness gates the streaming path promises (depth >= T bit-exact;
  depth = 5K within 1e-3 BER of the block decoder).

* ``--shards N``: ONE scheduler spanning an N-way ``data`` mesh (the slot
  table, input arena, and survivor ring partitioned per device, shard_map
  tick).  The slot table weak-scales — ``--slots-per-shard`` slots per
  device — so aggregate bits/s measures how throughput grows with the mesh;
  results land in a per-shard-count table (``stream.by_shards``) inside
  ``results/BENCH_viterbi.json`` and the run prints the scaling factor vs
  the recorded ``--shards 1`` row.  On a CPU container the mesh is
  host-platform devices (``--xla_force_host_platform_device_count``, set
  below BEFORE jax initializes — it cannot be applied afterwards); on a real
  TPU slice the same flag-free invocation spans the physical devices.

* ``--online``: true online ingestion under steady-state load — every
  stream is fed by a RATE-LIMITED producer (rows released on a wall clock,
  polled within the stream's backpressure credit) instead of a full table,
  and the run measures what a serving deployment cares about: sustained
  bits/s at the offered rate, per-bit commit latency from symbol ARRIVAL to
  emission (mean/p50/p95), queue-depth statistics from ``load_report()``,
  and how often slots starved.  The decoded bits are asserted identical to
  the same scheduler fed offline (arrival timing must never change the
  decode).  Results land in ``stream.online`` of BENCH_viterbi.json.

* ``--telemetry``: the observability acceptance run — drain the same
  workload with telemetry OFF and ON (tick-phase tracing + metrics +
  latency histograms), assert the decode is bit-identical and the measured
  host-plane overhead stays under 5%, check the tick phase spans cover
  >= 95% of tick wall clock, export ``results/trace.json`` (Perfetto) and
  ``results/trace.jsonl``, run a separate device-counter drain (merge
  depth / starved ticks / renorm accumulated inside the jitted tick; its
  overhead is recorded but NOT gated — the S-walker merge-depth scan is
  comparable to the whole tick on toy interpret-mode shapes), and merge an
  ``obs`` section into BENCH_viterbi.json (schema v4).

* ``--chaos``: the resilience acceptance run — drain the workload under
  seeded fault injection (~``--fault-rate`` producer faults per poll via
  ``ChaosPolicy.producer_mix`` plus simulated device-step failures on the
  tick), assert every stream either finishes bit-exact vs a fault-free
  reference drain or is quarantined with a structured error and a metrics
  trail, then measure snapshot/restore recovery latency mid-drain and
  assert the restored drain is bit-exact.  Results land in
  ``stream.resilience`` of BENCH_viterbi.json (schema v6).

  PYTHONPATH=src python benchmarks/stream_throughput.py [--sessions 64]
      [--steps 512] [--chunk 64] [--flip 0.02] [--backend fused_packed]
  PYTHONPATH=src python benchmarks/stream_throughput.py --smoke --shards 1
  PYTHONPATH=src python benchmarks/stream_throughput.py --smoke --shards 8
  PYTHONPATH=src python benchmarks/stream_throughput.py --smoke --online
  PYTHONPATH=src python benchmarks/stream_throughput.py --smoke --telemetry
  PYTHONPATH=src python benchmarks/stream_throughput.py --smoke --chaos

Numbers from the CPU container are interpret-mode / host-platform proxies
(shape + scheduling parity only); on a real TPU the same code runs the
compiled kernels.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path


def _force_host_devices() -> None:
    """--shards N needs N devices, and XLA reads the host-platform device
    count once, at first backend init — so peek at argv before importing
    jax (running on a real multi-device platform skips the flag)."""
    n = None
    for i, arg in enumerate(sys.argv):
        if arg == "--shards" and i + 1 < len(sys.argv):
            n = sys.argv[i + 1]
        elif arg.startswith("--shards="):
            n = arg.split("=", 1)[1]
    try:
        n = int(n)
    except (TypeError, ValueError):
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if n > 1 and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


_force_host_devices()

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.paper_viterbi import DECODE_SPEC, STREAM  # noqa: E402
from repro.core.viterbi import viterbi_decode  # noqa: E402
from repro.decode import DecodeContext, get_decoder  # noqa: E402
from repro.obs import Telemetry, get_logger, percentile  # noqa: E402
from repro.parallel.mesh import make_mesh  # noqa: E402
from repro.stream import StreamScheduler, viterbi_decode_windowed  # noqa: E402
from repro.stream.scheduler import TICK_PHASES  # noqa: E402

RESULTS = Path(__file__).resolve().parent / "results"
BENCH_JSON = RESULTS / "BENCH_viterbi.json"

log = get_logger("bench.stream")


def make_workload(spec, key, n_streams, info_bits, flip):
    info = jax.random.bernoulli(key, 0.5, (n_streams, info_bits)).astype(np.int32)
    coded = spec.encode(info)
    rx = spec.channel(jax.random.fold_in(key, 1), coded, flip_prob=flip)
    return info, spec.branch_metrics(rx)


def run_scheduler(spec, bm, n_slots, chunk, depth, backend, mesh=None,
                  telemetry=None):
    """Drain all streams through one scheduler; returns (elapsed_s, sched,
    results, total_bits).  Submission (arena appends) happens before the
    clock starts: the timed region is the tick loop + flushes."""
    sched = StreamScheduler(
        spec, n_slots=n_slots, chunk=chunk, depth=depth, backend=backend,
        mesh=mesh, mesh_axis=STREAM.mesh_axis, telemetry=telemetry,
    )
    for i in range(bm.shape[0]):
        sched.submit(f"s{i}", bm[i])
    t0 = time.perf_counter()
    out = sched.run()
    elapsed = time.perf_counter() - t0
    total_bits = sum(len(b) for b, _ in out.values())
    return elapsed, sched, out, total_bits


def _load_bench() -> dict:
    from viterbi_throughput import BENCH_SCHEMA

    if BENCH_JSON.exists():
        with contextlib.suppress(ValueError):  # corrupt artifact: rebuild
            bench = json.loads(BENCH_JSON.read_text())
            bench["schema"] = BENCH_SCHEMA
            return bench
    return {"schema": BENCH_SCHEMA,
            "generated_by": "benchmarks/stream_throughput.py"}


def run_shard_scaling(args) -> None:
    """One weak-scaled scheduler run on an n-way data mesh; merges a row
    into the per-shard-count table in BENCH_viterbi.json."""
    n = args.shards
    if len(jax.devices()) < n:
        raise SystemExit(
            f"--shards {n} needs {n} devices, found {len(jax.devices())} "
            "(the host-platform flag must be set before jax initializes)"
        )
    spec = DECODE_SPEC
    depth = STREAM.depth(spec.code)
    slots_per_shard = args.slots_per_shard or (8 if args.smoke else STREAM.n_slots)
    steps = args.steps if args.steps else (256 if args.smoke else 512)
    n_slots = STREAM.n_slots_for(n, slots_per_shard)
    backend = args.backend or "scan"  # pure-XLA hot loop: the host-platform
    # proxy then measures scheduling + partitioning, not interpret overhead
    mesh = make_mesh((n,), (STREAM.mesh_axis,))
    key = jax.random.PRNGKey(0)
    info_bits = steps - spec.n_flush
    _, bm = make_workload(spec, key, n_slots, info_bits, args.flip)

    run_scheduler(spec, bm, n_slots, args.chunk, depth, backend, mesh=mesh)  # warm
    elapsed, sched, out, total_bits = run_scheduler(
        spec, bm, n_slots, args.chunk, depth, backend, mesh=mesh
    )
    stats = sched.stats
    assert stats.streams_finished == n_slots
    platform = jax.devices()[0].platform
    row = {
        "shards": n,
        "slots_per_shard": slots_per_shard,
        "n_slots": n_slots,
        "sessions": n_slots,
        "steps": steps,
        "chunk": args.chunk,
        "depth": depth,
        "backend": backend,
        "device": platform,
        "host_cores": os.cpu_count(),
        "ticks": stats.ticks,
        "bits_decoded": total_bits,
        "elapsed_s": elapsed,
        "wallclock_bits_per_s": total_bits / elapsed,
    }
    if n > 1 and platform == "cpu":
        # Forced host-platform "devices" time-multiplex the same few cores,
        # so single-controller wall-clock cannot exhibit the concurrency the
        # partitioned program has (the tick carries NO cross-shard
        # communication — each shard's slice runs independently).  The
        # aggregate metric is therefore the device-concurrent proxy: shard
        # count x the MEASURED one-device rate of the identical per-shard
        # slot load (one partition of the same program, same process).  On
        # real multi-chip hardware the wall-clock number itself is the
        # aggregate and this branch is skipped.
        mesh1 = make_mesh((1,), (STREAM.mesh_axis,))
        bm1 = bm[:slots_per_shard]
        run_scheduler(spec, bm1, slots_per_shard, args.chunk, depth, backend,
                      mesh=mesh1)  # warm
        t1, _, _, bits1 = run_scheduler(
            spec, bm1, slots_per_shard, args.chunk, depth, backend, mesh=mesh1
        )
        row["per_device_elapsed_s"] = t1
        row["per_device_bits_per_s"] = bits1 / t1
        # the proxy is linear by construction, so never report above n x the
        # per-device rate (run-to-run jit jitter would otherwise fabricate
        # superlinear scaling)
        row["bits_per_s"] = n * (bits1 / t1)
        row["aggregate_metric"] = "device_concurrent_proxy"
    else:
        row["bits_per_s"] = total_bits / elapsed
        row["aggregate_metric"] = "wallclock"
    log.info(
        f"shards={n}: {n_slots} sessions x {steps} steps (backend {backend}) "
        f"in {elapsed:.3f}s wallclock "
        f"-> {row['bits_per_s']:,.0f} bits/s aggregate "
        f"({row['aggregate_metric']})"
    )

    bench = _load_bench()
    stream = bench.setdefault("stream", {})
    table = stream.setdefault("by_shards", {})
    table[str(n)] = row
    base = table.get("1")
    if base:  # (re)derive every row's scaling so invocation order is free
        for k, r in table.items():
            if k == "1":
                continue
            # proxy rows are linear-by-construction: clamp at the shard
            # count so jit jitter between the two one-device measurements
            # can never fabricate superlinear scaling
            raw = r["bits_per_s"] / base["bits_per_s"]
            cap = r["shards"] if r["aggregate_metric"] != "wallclock" else raw
            r["scaling_vs_shards1"] = min(raw, cap)
            r["wallclock_scaling_vs_shards1"] = (
                r["wallclock_bits_per_s"] / base["wallclock_bits_per_s"]
            )
    if base and n > 1:
        log.info(
            f"scaling vs --shards 1: {row['scaling_vs_shards1']:.2f}x "
            f"aggregate ({row['aggregate_metric']}); single-controller "
            f"wallclock ratio {row['wallclock_scaling_vs_shards1']:.2f}x"
        )
    RESULTS.mkdir(parents=True, exist_ok=True)
    BENCH_JSON.write_text(json.dumps(bench, indent=1))
    log.info(f"merged by_shards[{n}] into {BENCH_JSON}")


def run_online(args) -> None:
    """Steady-state serving measurement: rate-limited producers feed the
    chunk ingestion path; report sustained throughput, arrival-to-commit
    latency, and queue depths; merge a ``stream.online`` section into
    BENCH_viterbi.json (schema v3)."""
    import bisect

    from repro.stream import RateLimitedProducer

    spec = DECODE_SPEC
    depth = STREAM.depth(spec.code)
    sessions = args.sessions or (8 if args.smoke else 32)
    steps = args.steps or (384 if args.smoke else 2048)
    backend = args.backend or ("scan" if args.smoke else "fused_packed")
    chunk = args.chunk
    key = jax.random.PRNGKey(0)
    info_bits = steps - spec.n_flush
    _, bm = make_workload(spec, key, sessions, info_bits, args.flip)
    bm = np.asarray(bm)

    # offered load: each producer releases rows at `rate`; default is sized
    # so the batched tick loop is the bottleneck-free steady state (the
    # interpret-mode CPU proxy is slow — scale to finish in reasonable time)
    sched_probe = StreamScheduler(
        spec, n_slots=sessions, chunk=chunk, depth=depth, backend=backend,
        max_buffered=STREAM.max_buffered,
    )
    for i in range(sessions):  # calibration: offline drain rate of this box
        sched_probe.submit(f"w{i}", bm[i])
    t0 = time.perf_counter()
    sched_probe.run()
    offline_elapsed = time.perf_counter() - t0
    offline_rate = sessions * steps / offline_elapsed / sessions  # rows/s/stream
    rate = args.rate or max(50.0, 0.5 * offline_rate)

    sched = StreamScheduler(
        spec, n_slots=sessions, chunk=chunk, depth=depth, backend=backend,
        max_buffered=STREAM.max_buffered,
    )
    producers = {}
    for i in range(sessions):
        producers[f"s{i}"] = RateLimitedProducer(bm[i], rows_per_s=rate)
        sched.open_stream(f"s{i}", producer=producers[f"s{i}"])

    latencies: list = []
    queue_depths: list = []
    stream_depths: list = []
    committed = {f"s{i}": 0 for i in range(sessions)}
    t0 = time.perf_counter()
    while sched.pending_work():
        emitted = sched.step()
        now = time.perf_counter()
        for sid, bits in emitted.items():
            # latency of the NEWEST committed bit: now - arrival time of the
            # producer chunk that contained its row
            committed[sid] += len(bits)
            arr = producers[sid].arrivals
            j = bisect.bisect_left(arr, (committed[sid],))
            if j < len(arr):
                latencies.append(now - arr[j][1])
        report = sched.load_report()
        queue_depths.append(report["queued_rows_total"])
        stream_depths.append(report["max_stream_queued_rows"])
    elapsed = time.perf_counter() - t0
    total_bits = sum(len(b) for b, _ in sched.results.values())

    # arrival timing must never change the decode: online == offline, bit
    # for bit (the acceptance gate; a clean exit IS the verification)
    for i in range(sessions):
        on_bits, _ = sched.results[f"s{i}"]
        off_bits, _ = sched_probe.results[f"w{i}"]
        assert (on_bits == off_bits).all(), f"online decode diverged on s{i}"

    row = {
        "sessions": sessions,
        "steps": steps,
        "chunk": chunk,
        "depth": depth,
        "backend": backend,
        "device": jax.devices()[0].platform,
        "max_buffered": STREAM.max_buffered,
        "offered_rows_per_s_per_stream": rate,
        "elapsed_s": elapsed,
        "bits_decoded": total_bits,
        "bits_per_s": total_bits / elapsed,
        "ticks": sched.stats.ticks,
        "starved_slot_ticks": sched.stats.starved_slot_ticks,
        "busy_rejections": sched.stats.busy_rejections,
        "chunks_ingested": sched.stats.chunks_submitted,
        # per-bit commit latency, summarized through the ONE shared helper
        # (obs.percentile: sorts, nearest-rank, safe on empty)
        "latency_s": {
            "mean": float(np.mean(latencies)) if latencies else 0.0,
            "p50": percentile(latencies, 0.5),
            "p95": percentile(latencies, 0.95),
            "max": float(max(latencies)) if latencies else 0.0,
        },
        # the scheduler's own arrival-to-commit histogram (chunk granularity,
        # tracked on-line inside the commit phase — no benchmark bookkeeping)
        "latency_scheduler_s": sched.load_report()["latency_s"],
        "queue_depth_rows": {
            "mean": float(np.mean(queue_depths)) if queue_depths else 0.0,
            "max": int(max(queue_depths)) if queue_depths else 0,
            "max_stream": int(max(stream_depths)) if stream_depths else 0,
        },
        "bit_exact_vs_offline": True,  # asserted above
    }
    log.info(f"online: {sessions} rate-limited streams x {steps} steps "
             f"({rate:,.0f} rows/s/stream offered, backend {backend})")
    log.info(f"  {total_bits} bits in {elapsed:.3f}s -> {row['bits_per_s']:,.0f} "
             f"bits/s sustained; latency mean {row['latency_s']['mean'] * 1e3:.1f}ms "
             f"p95 {row['latency_s']['p95'] * 1e3:.1f}ms")
    log.info(f"  queue depth mean {row['queue_depth_rows']['mean']:.0f} / "
             f"max {row['queue_depth_rows']['max']} rows total, deepest stream "
             f"{row['queue_depth_rows']['max_stream']} (bound {STREAM.max_buffered}"
             f"/stream); {row['starved_slot_ticks']} starved slot-ticks over "
             f"{row['ticks']} ticks")
    log.info("  online decode bit-exact vs offline feed of the same symbols")

    bench = _load_bench()
    bench.setdefault("stream", {})["online"] = row
    RESULTS.mkdir(parents=True, exist_ok=True)
    BENCH_JSON.write_text(json.dumps(bench, indent=1))
    log.info(f"merged stream.online into {BENCH_JSON}")


def run_telemetry(args) -> None:
    """Observability acceptance run: telemetry-off vs telemetry-on drains of
    the same workload.  Gates (all asserted here, re-checked by CI):

      * decode bits identical with telemetry on (observation never changes
        the result);
      * host-plane overhead (tracing + metrics + latency histograms)
        < 5% of the telemetry-off drain time, min-of-``--repeats``;
      * tick phase spans cover >= 95% of tick wall clock;
      * the Perfetto export loads (trace.json with a non-empty traceEvents
        list containing tick spans).

    A separate drain with device counters on records merge-depth statistics
    and ITS overhead ungated: the S-walker merge-depth scan is O(R·S) work
    per tick — comparable to the whole tick on the toy interpret-mode CPU
    shapes CI runs, and a deliberate opt-in everywhere.
    """
    spec = DECODE_SPEC
    depth = STREAM.depth(spec.code)
    sessions = args.sessions or (8 if args.smoke else 32)
    steps = args.steps or (384 if args.smoke else 1024)
    backend = args.backend or "scan"
    chunk = args.chunk
    repeats = args.repeats
    key = jax.random.PRNGKey(0)
    info_bits = steps - spec.n_flush
    _, bm = make_workload(spec, key, sessions, info_bits, args.flip)
    bm = np.asarray(bm)

    def drain(make_tel):
        return run_scheduler(
            spec, bm, sessions, chunk, depth, backend,
            telemetry=make_tel() if make_tel else None,
        )

    host_tel = lambda: Telemetry.enabled(device_counters=False)  # noqa: E731
    dev_tel = lambda: Telemetry.enabled(device_counters=True)  # noqa: E731

    # warm every jit variant before any timed drain (the device-counter step
    # is a different traced computation)
    drain(None)
    drain(host_tel)
    drain(dev_tel)

    t_off = min(drain(None)[0] for _ in range(repeats))
    on_runs = [drain(host_tel) for _ in range(repeats)]
    t_on = min(r[0] for r in on_runs)
    _, sched_on, out_on, _ = on_runs[-1]
    _, _, out_off, total_bits = drain(None)

    for i in range(sessions):
        assert (out_on[f"s{i}"][0] == out_off[f"s{i}"][0]).all(), (
            f"telemetry changed the decode of s{i}"
        )

    tracer = sched_on.telemetry.tracer
    coverage = tracer.coverage("tick", TICK_PHASES)
    overhead = (t_on - t_off) / t_off
    n_ticks = sched_on.stats.ticks

    RESULTS.mkdir(parents=True, exist_ok=True)
    trace_path = RESULTS / "trace.json"
    tracer.write_chrome(trace_path)
    tracer.write_jsonl(RESULTS / "trace.jsonl")
    trace = json.loads(trace_path.read_text())
    tick_events = [e for e in trace["traceEvents"] if e.get("name") == "tick"]
    assert tick_events, "trace.json has no tick spans"

    # device-counter drain: overhead recorded, not gated
    t_dev, sched_dev, out_dev, _ = min(
        (drain(dev_tel) for _ in range(repeats)), key=lambda r: r[0]
    )
    for i in range(sessions):
        assert (out_dev[f"s{i}"][0] == out_off[f"s{i}"][0]).all(), (
            f"device counters changed the decode of s{i}"
        )
    depth_hist = sched_dev.telemetry.metrics.histogram("stream_merge_depth")

    row = {
        "sessions": sessions,
        "steps": steps,
        "chunk": chunk,
        "depth": depth,
        "backend": backend,
        "device": jax.devices()[0].platform,
        "repeats": repeats,
        "ticks": n_ticks,
        "elapsed_off_s": t_off,
        "elapsed_on_s": t_on,
        "overhead_frac": overhead,
        "tick_span_coverage": coverage,
        "trace_events": len(trace["traceEvents"]),
        "latency_s": sched_on.load_report()["latency_s"],
        "device_counters": {
            "elapsed_s": t_dev,
            "overhead_frac_ungated": (t_dev - t_off) / t_off,
            "merge_depth": depth_hist.summary(),
        },
        "bit_exact_with_telemetry": True,  # asserted above
    }
    log.info(f"telemetry: {sessions} streams x {steps} steps "
             f"(backend {backend}, min of {repeats})")
    log.info(f"  off {t_off:.3f}s / on {t_on:.3f}s -> overhead "
             f"{overhead * 100:.2f}% (gate < 5%); phase coverage "
             f"{coverage * 100:.2f}% of {n_ticks} ticks (gate >= 95%)")
    log.info(f"  device counters: {t_dev:.3f}s "
             f"({row['device_counters']['overhead_frac_ungated'] * 100:.1f}% "
             f"ungated); retiree merge depth "
             f"p50 {depth_hist.summary()['p50']:.0f} / "
             f"max {depth_hist.summary()['max']:.0f} steps (window {depth})")
    log.info(f"  wrote {trace_path} ({len(trace['traceEvents'])} events) "
             f"+ trace.jsonl; {total_bits} bits bit-exact on all three drains")

    assert coverage >= 0.95, f"tick phase coverage {coverage:.3f} < 0.95"
    assert overhead < 0.05, (
        f"telemetry overhead {overhead * 100:.2f}% exceeds the 5% budget"
    )

    bench = _load_bench()
    bench["obs"] = row
    BENCH_JSON.write_text(json.dumps(bench, indent=1))
    log.info(f"merged obs section into {BENCH_JSON}")


def run_chaos(args) -> None:
    """Resilience acceptance run: drain the workload under seeded fault
    injection (``ChaosPolicy.producer_mix`` producer faults + simulated
    device-step failures), verify every stream is accounted for (finished
    bit-exact or quarantined with a structured error), then measure
    snapshot/restore recovery latency on a clean mid-drain scheduler.
    Merges a ``stream.resilience`` section into BENCH_viterbi.json
    (schema v6)."""
    import pickle

    from repro.stream import ChaosPolicy, ChaosProducer, install_tick_faults

    spec = DECODE_SPEC
    depth = STREAM.depth(spec.code)
    sessions = args.sessions or (8 if args.smoke else 32)
    steps = args.steps or (384 if args.smoke else 1024)
    backend = args.backend or "scan"
    chunk = args.chunk
    seed = args.seed
    rate = args.fault_rate
    key = jax.random.PRNGKey(0)
    info_bits = steps - spec.n_flush
    _, bm = make_workload(spec, key, sessions, info_bits, args.flip)
    bm = np.asarray(bm)

    # fault-free reference drain: the bit-exactness oracle
    _, _, ref, _ = run_scheduler(spec, bm, sessions, chunk, depth, backend)

    # ---- chaotic drain: producer faults + injected device-step failures ----
    sched = StreamScheduler(
        spec, n_slots=sessions, chunk=chunk, depth=depth, backend=backend,
        max_buffered=STREAM.max_buffered,
    )
    policy = ChaosPolicy.producer_mix(rate, seed=seed)
    tick_injector = install_tick_faults(
        sched, ChaosPolicy(seed=seed, device_step_failure=rate / 2)
    )
    def _chunked(table):
        # bind the table now: a bare genexp in the loop would close over the
        # loop variable and feed every stream the LAST table
        return (table[j:j + chunk] for j in range(0, len(table), chunk))

    producers = {}
    for i in range(sessions):
        sid = f"s{i}"
        producers[sid] = ChaosProducer(
            _chunked(bm[i]), policy, stream_id=sid,
            metrics=sched.telemetry.metrics,
        )
        sched.open_stream(sid, producer=producers[sid],
                          max_buffered=STREAM.max_buffered)

    t0 = time.perf_counter()
    guard = 0
    while sched.pending_work():
        sched.step()
        guard += 1
        assert guard < 200_000, "chaotic drain failed to converge"
    elapsed = time.perf_counter() - t0

    injected: dict = dict(tick_injector.injected)
    for p in producers.values():
        for cls, n in p.injected.items():
            injected[cls] = injected.get(cls, 0) + n
    quarantined = sorted(sched.errors)
    survivors = [f"s{i}" for i in range(sessions) if f"s{i}" not in sched.errors]
    # timing faults (stall, drip, dropped ticks) must never change the
    # decode: every non-quarantined stream is bit-identical to the
    # fault-free drain
    for sid in survivors:
        assert (sched.results[sid][0] == ref[sid][0]).all(), (
            f"chaos changed the decode of surviving stream {sid}"
        )
    metrics = sched.metrics_text()
    for cls, n in injected.items():
        assert f"chaos_{cls}_total {n}" in metrics, (
            f"injected {cls} not visible in metrics_text()"
        )
    bits_committed = sum(len(b) for b, _ in sched.results.values())

    # ---- snapshot/restore recovery latency on a clean mid-drain state ----
    snap_sched = StreamScheduler(
        spec, n_slots=sessions, chunk=chunk, depth=depth, backend=backend,
    )
    for i in range(sessions):
        snap_sched.submit(f"s{i}", bm[i])
    snap_tick = max(1, (steps // chunk) // 2)
    for _ in range(snap_tick):
        snap_sched.step()
    t0 = time.perf_counter()
    blob = pickle.dumps(snap_sched.snapshot())
    save_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    restored = StreamScheduler.restore(pickle.loads(blob))
    restore_s = time.perf_counter() - t0
    out = restored.run()
    snap_exact = all(
        (out[f"s{i}"][0] == ref[f"s{i}"][0]).all() for i in range(sessions)
    )
    assert snap_exact, "restore diverged from the uninterrupted drain"

    row = {
        "sessions": sessions,
        "steps": steps,
        "chunk": chunk,
        "depth": depth,
        "backend": backend,
        "device": jax.devices()[0].platform,
        "seed": seed,
        "producer_fault_rate": rate,
        "elapsed_s": elapsed,
        "injected": injected,
        "streams_finished": len(survivors),
        "streams_quarantined": len(quarantined),
        "quarantine_reasons": {
            sid: sched.errors[sid].reason for sid in quarantined
        },
        "ticks": sched.stats.ticks,
        "ticks_dropped": sched.stats.tick_device_failures,
        "bits_committed": bits_committed,
        "timing_faults_bit_exact": True,  # asserted above
        "snapshot": {
            "tick": snap_tick,
            "streams": len(out),
            "bytes": len(blob),
            "save_s": save_s,
            "restore_s": restore_s,
            "bit_exact": bool(snap_exact),
        },
    }
    n_inj = sum(injected.values())
    log.info(f"chaos: {sessions} streams x {steps} steps (backend {backend}, "
             f"fault rate {rate}, seed {seed})")
    log.info(f"  {n_inj} faults injected {injected}; "
             f"{len(survivors)} streams finished bit-exact, "
             f"{len(quarantined)} quarantined "
             f"({row['quarantine_reasons']}); "
             f"{row['ticks_dropped']} ticks dropped and retried")
    log.info(f"  {bits_committed} bits committed in {elapsed:.3f}s; snapshot "
             f"at tick {snap_tick}: save {save_s * 1e3:.1f}ms / restore "
             f"{restore_s * 1e3:.1f}ms ({len(blob)} bytes), restored drain "
             f"bit-exact")

    bench = _load_bench()
    bench.setdefault("stream", {})["resilience"] = row
    RESULTS.mkdir(parents=True, exist_ok=True)
    BENCH_JSON.write_text(json.dumps(bench, indent=1))
    log.info(f"merged stream.resilience into {BENCH_JSON}")


def run_backend_comparison(args) -> None:
    spec = DECODE_SPEC
    code = spec.code
    depth = STREAM.depth(code)
    key = jax.random.PRNGKey(0)
    steps = args.steps or 512
    sessions = args.sessions or STREAM.n_slots
    backend = args.backend or "fused_packed"
    info_bits = steps - spec.n_flush
    info, bm = make_workload(spec, key, sessions, info_bits, args.flip)
    ref_bits, _ = viterbi_decode(code, bm)

    # ---------------- correctness gates ---------------- #
    wide, _ = viterbi_decode_windowed(
        code, bm[:4], depth=steps, chunk=args.chunk, backend="scan"
    )
    exact = bool((np.asarray(wide) == np.asarray(ref_bits[:4])).all())
    trunc, _ = viterbi_decode_windowed(
        code, bm, depth=depth, chunk=args.chunk, backend="scan"
    )
    ber_ref = float((np.asarray(ref_bits)[:, :info_bits] != np.asarray(info)).mean())
    ber_win = float((np.asarray(trunc)[:, :info_bits] != np.asarray(info)).mean())
    log.info(f"gate 1  depth>=T bit-identical to block decode : {exact}")
    log.info(
        f"gate 2  BER block {ber_ref:.2e} vs windowed(D=5K) {ber_win:.2e} "
        f"(|diff| {abs(ber_win - ber_ref):.2e} <= 1e-3: {abs(ber_win - ber_ref) <= 1e-3})"
    )
    assert exact and abs(ber_win - ber_ref) <= 1e-3

    # ---------------- streaming scheduler: requested + packed ---------------- #
    backends = [backend]
    if "fused_packed" not in backends:
        backends.append("fused_packed")
    sched_rows = {}
    for bk in backends:
        run_scheduler(spec, bm, sessions, args.chunk, depth, bk)  # warm
        t_stream, sched_bk, out, total_bits = run_scheduler(
            spec, bm, sessions, args.chunk, depth, bk
        )
        stats = sched_bk.stats
        mismatches = sum(
            int((out[f"s{i}"][0] != np.asarray(ref_bits[i])).sum())
            for i in range(sessions)
        )
        sched_rows[bk] = {
            "ticks": stats.ticks,
            "bits_decoded": total_bits,
            "stream_s": t_stream,
            "stream_bits_per_s": total_bits / t_stream,
            "mismatches_vs_block": mismatches,
        }
        log.info(f"scheduler[{bk}]: {sessions} sessions x {steps} "
                 f"steps, chunk {args.chunk}, depth {depth}")
        log.info(f"  {stats.ticks} ticks (one jitted call each), {stats.slot_claims} "
                 f"slot claims, {total_bits} bits in {t_stream:.3f}s "
                 f"-> {total_bits / t_stream:,.0f} bits/s; "
                 f"mismatches vs block: {mismatches}/{total_bits}")

    # ---------------- block baseline ---------------- #
    fused = get_decoder("fused_packed")
    ctx = DecodeContext(chunk=args.chunk)
    dec = jax.jit(lambda t: fused(spec, t, ctx=ctx).bits)
    jax.block_until_ready(dec(bm))  # warm
    t0 = time.perf_counter()
    jax.block_until_ready(dec(bm))
    t_block = time.perf_counter() - t0
    total_bits = sched_rows[backend]["bits_decoded"]
    log.info(f"block fused_packed decode of the same (B={sessions}, "
             f"T={steps}) workload: {t_block:.3f}s -> "
             f"{total_bits / t_block:,.0f} bits/s")
    t_stream = sched_rows[backend]["stream_s"]
    log.info(f"streaming/block time ratio: {t_stream / t_block:.2f}x "
             f"(streaming adds the sliding-window traceback per tick but needs "
             f"O(depth+chunk) memory instead of O(T))")

    # ONE results file: merge into the shared perf baseline, preserving the
    # sections owned by the other modes (see benchmarks/README.md)
    RESULTS.mkdir(parents=True, exist_ok=True)
    payload = {
        "sessions": sessions, "steps": steps, "chunk": args.chunk,
        "depth": depth, "schedulers": sched_rows,
        "block_s": t_block, "block_bits_per_s": total_bits / t_block,
        "bit_exact_wide_window": exact,
        "ber_block": ber_ref, "ber_windowed": ber_win,
    }
    bench = _load_bench()
    stream = bench.setdefault("stream", {})
    kept = {k: stream[k] for k in ("by_shards", "online", "resilience")
            if k in stream}
    stream.clear()
    stream.update(payload)
    stream.update(kept)
    BENCH_JSON.write_text(json.dumps(bench, indent=1))
    log.info(f"merged stream section into {BENCH_JSON}")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sessions", type=int, default=None)
    ap.add_argument("--steps", type=int, default=None,
                    help="trellis steps per stream")
    ap.add_argument("--chunk", type=int, default=STREAM.chunk)
    ap.add_argument("--flip", type=float, default=0.02)
    ap.add_argument("--backend", default=None,
                    choices=("fused_packed", "scan"))
    ap.add_argument("--shards", type=int, default=0,
                    help="run the sharded-scheduler scaling mode on an N-way "
                         "data mesh (weak-scaled: --slots-per-shard per device)")
    ap.add_argument("--slots-per-shard", type=int, default=None)
    ap.add_argument("--online", action="store_true",
                    help="steady-state ingestion mode: rate-limited chunk "
                         "producers, arrival-to-commit latency, queue depths")
    ap.add_argument("--rate", type=float, default=None,
                    help="--online offered load, rows/s per stream (default: "
                         "half the measured offline drain rate)")
    ap.add_argument("--telemetry", action="store_true",
                    help="observability acceptance mode: telemetry on/off "
                         "overhead, phase-span coverage, Perfetto export")
    ap.add_argument("--repeats", type=int, default=3,
                    help="--telemetry timing repeats (min is reported)")
    ap.add_argument("--chaos", action="store_true",
                    help="resilience acceptance mode: seeded fault-injection "
                         "drain + snapshot/restore recovery latency")
    ap.add_argument("--fault-rate", type=float, default=0.1,
                    help="--chaos producer fault probability per poll "
                         "(split across the producer_mix classes)")
    ap.add_argument("--seed", type=int, default=0,
                    help="--chaos injection seed (same seed, same faults)")
    ap.add_argument("--smoke", action="store_true",
                    help="small CI shapes for the scaling/online modes")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress stdout reporting (warnings still print); "
                         "the JSON artifact is the output")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    get_logger("bench.stream", quiet=args.quiet)  # reconfigure module logger
    if args.chaos:
        run_chaos(args)
    elif args.telemetry:
        run_telemetry(args)
    elif args.online:
        run_online(args)
    elif args.shards:
        run_shard_scaling(args)
    else:
        run_backend_comparison(args)


if __name__ == "__main__":
    main()
