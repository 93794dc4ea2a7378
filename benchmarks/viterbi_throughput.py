"""TPU-scale Viterbi throughput: decoder backends head-to-head on the
paper's workloads, plus the HBM-traffic accounting of the fused pipeline —
the repo's perf baseline, emitted as machine-readable ``BENCH_viterbi.json``.

The headline comparison is the K=7 NASA code (the paper's production-scale
analogue): sequential lax.scan oracle vs the packed pipeline (bit-packed
survivors + on-device traceback, optionally with in-kernel branch metrics
from raw symbols).  Wall-clock on the CPU container is interpret-mode (shape
parity only); the bytes-moved model below is exact arithmetic.

HBM bytes per trellis step per stream (float32/int32 = 4 bytes, uint32
survivor words amortized over 32 steps, decoded bit out = 4).  ``unpacked``
is the model of one int32 survivor per (step, state) — the format the
packed words replace; no decoder runs it:

  unpacked              4·(M + 2S + 1)    bm in, int32 survivors out +
                                          re-read by a traceback
  fused_packed          4·(M + S/16 + 1)  bm in, packed survivors out +
                                          re-read by the Pallas traceback
  fused_packed+rx       4·(n + S/16 + 1)  raw symbols in (no bm table)

  PYTHONPATH=src python benchmarks/viterbi_throughput.py [--smoke]
      [--long-blocks] [--out benchmarks/results/BENCH_viterbi.json]

``--long-blocks`` adds the time-parallel section: the K=3 production code on
single long streams, sequential scan vs the tiled decoder at several tile
counts P — wall-clock, bit-exactness (the exact seam regime must never
trade correctness for speed), and the crossover T where tiling first wins.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.paper_viterbi import CODES, DECODE_SPEC
from repro.obs.log import get_logger
from repro.core.viterbi import viterbi_decode
from repro.decode import CodecSpec, plan_decode
from repro.kernels import fused_metric_plan
from repro.kernels.common import PACK_BITS
from repro.kernels.ops import (
    viterbi_decode_fused_packed,
    viterbi_decode_packed,
    viterbi_decode_tiled_op,
)

log = get_logger("bench.viterbi")

#: v2 added the optional ``stream.by_shards`` per-shard-count scaling table
#: (stream_throughput.py --shards N); v3 adds the optional ``stream.online``
#: steady-state ingestion section (stream_throughput.py --online: sustained
#: bits/s under rate-limited producers, arrival-to-commit latency, queue
#: depths, backpressure counters); v4 adds the optional top-level ``obs``
#: telemetry-acceptance section (stream_throughput.py --telemetry: tracing
#: on/off overhead, tick-phase span coverage, device-counter drain); v5 adds
#: the optional top-level ``turbo`` SISO section (siso_throughput.py: a BER
#: point vs the equivalent-rate Viterbi baseline + decoded bits/s per
#: iteration count); v6 adds the optional ``stream.resilience`` section
#: (stream_throughput.py --chaos: seeded fault-injection drain — injected
#: fault counts by class, survival accounting, snapshot/restore recovery
#: latency, bit-exactness flags); v7 adds the optional top-level
#: ``long_blocks`` section (--long-blocks: sequential vs time-parallel tiled
#: decode on single long K=3 streams — time vs tile count P, per-row
#: bit-exactness, and the crossover T where tiling first beats sequential;
#: speedup-vs-P monotonicity is recorded, not asserted); v8 adds the optional
#: top-level ``analysis`` section (analysis_report.py: repo-rule lint result,
#: jaxpr contract trace of every registered hot path, pragma census, and the
#: --sanitize steady-state guard probe — one user host sync per tick, zero
#: steady recompiles, bit-exact under guards).
BENCH_SCHEMA = "bench_viterbi/v8"
DEFAULT_OUT = Path(__file__).resolve().parent / "results" / "BENCH_viterbi.json"


def _mk_inputs(spec: CodecSpec, info_bits: int, batch: int, seed: int = 0):
    key = jax.random.PRNGKey(seed)
    bits = jax.random.bernoulli(key, 0.5, (batch, info_bits)).astype(jnp.int32)
    coded = spec.encode(bits)
    rx = spec.channel(jax.random.fold_in(key, 1), coded, flip_prob=0.02)
    return bits, rx, spec.branch_metrics(rx)


def _timeit(fn, *args, iters: int = 3):
    """(mean seconds, last output) — the output doubles as the oracle check
    so callers don't pay another full decode for it."""
    out = fn(*args)  # warm (trace + compile)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters, out


def hbm_bytes_per_step(code, backend: str) -> float:
    """Hot-path HBM bytes per trellis step per stream (model, see module
    doc).  Survivor words amortize over PACK_BITS steps, read + written."""
    S, M, n = code.n_states, code.n_symbols, code.n_out
    packed_sv = 2 * S * 4.0 / PACK_BITS  # write + traceback re-read
    if backend == "unpacked":
        return 4.0 * (M + 2 * S + 1)
    if backend == "fused_packed":
        return 4.0 * (M + 1) + packed_sv
    if backend == "fused_packed_received":
        return 4.0 * (n + 1) + packed_sv
    raise KeyError(backend)


def bench_backends(spec: CodecSpec, batch: int, info_bits: int, iters: int) -> Dict:
    """One workload, all hot-path backends: measured bits/s + modeled HBM
    traffic.  ``fused_packed_received`` feeds raw symbols (in-kernel
    metrics); the others consume precomputed bm tables."""
    code = spec.code
    bits, rx, bm = _mk_inputs(spec, info_bits, batch)
    T = bm.shape[1]
    total_bits = batch * T
    plan = fused_metric_plan(code, spec.metric, spec.puncture_array)
    runners = {
        "sequential": (jax.jit(lambda b: viterbi_decode(code, b)[0]), bm),
        "fused_packed": (jax.jit(lambda b: viterbi_decode_packed(code, b)[0]), bm),
        "fused_packed_received": (
            jax.jit(lambda r: viterbi_decode_fused_packed(plan, r)[0]),
            rx,
        ),
    }
    backends: Dict[str, Dict] = {}
    decoded = {}
    for name, (fn, arg) in runners.items():
        t, out = _timeit(fn, arg, iters=iters)
        decoded[name] = np.asarray(out)
        row = {"time_s": t, "bits_per_s": total_bits / t}
        if name != "sequential":
            bps = hbm_bytes_per_step(code, name)
            row["hbm_bytes_per_step_per_stream"] = bps
            row["hbm_bytes_total"] = bps * total_bits
            row["hbm_bytes_per_bit"] = bps
        backends[name] = row
    # every backend must agree with the oracle before its number counts
    for name in ("fused_packed", "fused_packed_received"):
        assert (decoded[name] == decoded["sequential"]).all(), (
            f"{name} diverged from the sequential oracle"
        )
    S = code.n_states
    return {
        "workload": {
            "constraint": code.constraint,
            "polys_oct": [oct(g) for g in code.polys],
            "n_states": S,
            "batch": batch,
            "steps": T,
            "metric": spec.metric,
            "decoded_bits": total_bits,
        },
        "backends": backends,
        "survivor_bytes": {
            "unpacked_int32": T * S * batch * 4,
            "packed_uint32": -(-T // PACK_BITS) * S * batch * 4,
            "shrink_x": T / float(-(-T // PACK_BITS)),
        },
        "speedup": {
            "fused_packed_vs_sequential_measured": (
                backends["fused_packed"]["bits_per_s"]
                / backends["sequential"]["bits_per_s"]
            ),
            # exact arithmetic against the unpacked-survivor model
            "fused_packed_vs_fused_hbm_model": (
                hbm_bytes_per_step(code, "unpacked")
                / hbm_bytes_per_step(code, "fused_packed")
            ),
            "fused_packed_received_vs_fused_hbm_model": (
                hbm_bytes_per_step(code, "unpacked")
                / hbm_bytes_per_step(code, "fused_packed_received")
            ),
        },
    }


#: --long-blocks sweep: single-stream lengths and tile counts.  Smoke keeps
#: the CI job short; full adds the deep point where tiling matters most.
LONG_BLOCK_SWEEP = {"Ts": (2048, 8192), "tile_counts": (4, 16)}
LONG_BLOCK_SWEEP_FULL = {"Ts": (2048, 8192, 32768), "tile_counts": (4, 16, 32)}


def bench_long_blocks(spec: CodecSpec, Ts, tile_counts, iters: int) -> Dict:
    """Single long streams (B=1): the un-tiled packed pipeline walks a
    T-step launch time grid, the tiled decoder a T/P-step one plus seam
    work — measure where the crossover lands and that the exact seam regime
    stays bit-exact while winning.

    The ``sequential`` baseline is viterbi_decode_packed — the SAME kernel
    pipeline with P=1, so the delta is the time-tiling and nothing else (the
    only apples-to-apples wall-clock on an interpret-mode container, where
    Pallas-vs-XLA ratios say nothing about TPU).  The XLA lax.scan oracle is
    recorded alongside as ``xla_scan`` for context and the oracle check."""
    code = spec.code
    by_T: Dict[str, Dict] = {}
    crossover = None
    for T in Ts:
        n_info = T - (code.constraint - 1)  # steps == T after flush
        _, _, bm = _mk_inputs(spec, n_info, 1, seed=7)
        assert bm.shape[1] == T, (bm.shape, T)
        t_scan, out_scan = _timeit(
            jax.jit(lambda b: viterbi_decode(code, b)[0]), bm, iters=iters
        )
        ref = np.asarray(out_scan)
        t_seq, out_seq = _timeit(
            jax.jit(lambda b: viterbi_decode_packed(code, b)[0]), bm,
            iters=iters,
        )
        assert (np.asarray(out_seq) == ref).all(), "packed baseline diverged"
        tiled_rows: Dict[str, Dict] = {}
        for P in tile_counts:
            fn = jax.jit(lambda b, P=P: viterbi_decode_tiled_op(code, b, P)[0])
            t, out = _timeit(fn, bm, iters=iters)
            tiled_rows[str(P)] = {
                "time_s": t,
                "bits_per_s": T / t,
                "bit_exact": bool((np.asarray(out) == ref).all()),
                "speedup_vs_sequential": t_seq / t,
            }
        best = max(tiled_rows, key=lambda p: tiled_rows[p]["speedup_vs_sequential"])
        by_T[str(T)] = {
            "sequential": {"time_s": t_seq, "bits_per_s": T / t_seq,
                           "backend": "fused_packed (un-tiled, P=1)"},
            "xla_scan": {"time_s": t_scan, "bits_per_s": T / t_scan},
            "tiled": tiled_rows,
            "best_tiles": int(best),
            "best_speedup_vs_sequential": (
                tiled_rows[best]["speedup_vs_sequential"]
            ),
        }
        if crossover is None and by_T[str(T)]["best_speedup_vs_sequential"] > 1.0:
            crossover = T
    return {
        "workload": {
            "constraint": code.constraint,
            "n_states": code.n_states,
            "metric": spec.metric,
            "batch": 1,
            "Ts": [int(T) for T in Ts],
            "tile_counts": [int(P) for P in tile_counts],
            "sequential_backend": "fused_packed (un-tiled, P=1)",
        },
        "by_T": by_T,
        # smallest swept T where the best tiled config beats the un-tiled run
        "crossover_T_vs_sequential": crossover,
        "note": ("measured wall-clock vs the un-tiled run of the same packed "
                 "pipeline (interpret-mode off-TPU); speedup monotonicity in "
                 "P is recorded, not asserted"),
    }


def run(quick: bool = True, out: Path = DEFAULT_OUT,
        long_blocks: bool = False) -> Dict:
    """Benchmark + write BENCH_viterbi.json; returns the payload.  ``quick``
    is the CPU-container (--smoke) shape; full mode runs the production
    batch."""
    interpret = jax.default_backend() != "tpu"
    k7 = CodecSpec(code=CODES["k7_nasa"], metric=DECODE_SPEC.metric)
    k3 = DECODE_SPEC
    if quick:
        k7_shape, k3_shape, iters = (8, 90), (32, 126), 2
    else:
        k7_shape, k3_shape, iters = (128, 1018), (1024, 1022), 3
    payload = {
        "schema": BENCH_SCHEMA,
        "generated_by": "benchmarks/viterbi_throughput.py",
        "smoke": quick,
        "interpret_mode": interpret,
        "device": jax.devices()[0].platform,
        "paper_workload_k7": bench_backends(k7, *k7_shape, iters=iters),
        "paper_workload_k3": bench_backends(k3, *k3_shape, iters=iters),
        "planned_backend_short_block": plan_decode(k7, (k7_shape[0], 256)).backend,
        "planned_backend_long_block": plan_decode(k3, (1, 8192)).backend,
    }
    if long_blocks:
        sweep = LONG_BLOCK_SWEEP if quick else LONG_BLOCK_SWEEP_FULL
        payload["long_blocks"] = bench_long_blocks(
            k3, sweep["Ts"], sweep["tile_counts"], iters=2 if quick else 3
        )
    out = Path(out)
    out.parent.mkdir(parents=True, exist_ok=True)
    if out.exists():  # preserve sections merged in by other benchmarks/runs
        try:
            existing = json.loads(out.read_text())
        except (ValueError, OSError):
            existing = {}
        preserved = ["stream", "obs", "turbo", "analysis"]
        if not long_blocks:
            preserved.append("long_blocks")
        for section in preserved:
            if existing.get(section) is not None:
                payload[section] = existing[section]
    out.write_text(json.dumps(payload, indent=1))
    return payload


def check_schema(payload: Dict) -> None:
    """Schema gate used by the CI smoke job (and tests)."""
    assert payload["schema"] == BENCH_SCHEMA
    for wl_key in ("paper_workload_k7", "paper_workload_k3"):
        wl = payload[wl_key]
        for field in ("workload", "backends", "survivor_bytes", "speedup"):
            assert field in wl, f"{wl_key} missing {field}"
        for name in ("sequential", "fused_packed", "fused_packed_received"):
            assert wl["backends"][name]["bits_per_s"] > 0
        assert wl["survivor_bytes"]["shrink_x"] > 16  # ~32 for T >> 32
        assert wl["speedup"]["fused_packed_vs_fused_hbm_model"] >= 2.0
        assert wl["speedup"]["fused_packed_received_vs_fused_hbm_model"] >= 2.0
    # optional sharded-scheduler scaling table (stream_throughput --shards N)
    by_shards = (payload.get("stream") or {}).get("by_shards")
    if by_shards is not None:
        for n, row in by_shards.items():
            assert row["shards"] == int(n)
            assert row["n_slots"] == row["slots_per_shard"] * row["shards"]
            assert row["bits_per_s"] > 0
        if "1" in by_shards:
            for n, row in by_shards.items():
                if n != "1":
                    assert "scaling_vs_shards1" in row
    # optional online-ingestion section (stream_throughput --online): v3
    online = (payload.get("stream") or {}).get("online")
    if online is not None:
        for field in ("sessions", "steps", "chunk", "depth", "max_buffered",
                      "offered_rows_per_s_per_stream", "bits_per_s",
                      "latency_s", "queue_depth_rows", "ticks"):
            assert field in online, f"stream.online missing {field}"
        assert online["bits_per_s"] > 0
        assert online["bit_exact_vs_offline"] is True
        lat = online["latency_s"]
        assert 0 <= lat["mean"] <= lat["max"] and lat["p50"] <= lat["p95"]
        q = online["queue_depth_rows"]
        # backpressure invariant: no single stream's bounded queue can ever
        # overrun its credit limit (totals are bounded by sessions x limit)
        assert 0 <= q["max_stream"] <= online["max_buffered"]
        assert 0 <= q["mean"] <= q["max"] <= (
            online["sessions"] * online["max_buffered"]
        )
    # optional telemetry-acceptance section (stream_throughput --telemetry): v4
    obs = payload.get("obs")
    if obs is not None:
        for field in ("sessions", "steps", "chunk", "depth", "ticks", "repeats",
                      "elapsed_off_s", "elapsed_on_s", "overhead_frac",
                      "tick_span_coverage", "trace_events", "latency_s",
                      "device_counters", "bit_exact_with_telemetry"):
            assert field in obs, f"obs missing {field}"
        assert obs["bit_exact_with_telemetry"] is True
        # the acceptance gates the benchmark already enforced, re-checked here
        # so a hand-edited or stale results file cannot pass CI
        assert obs["overhead_frac"] < 0.05, obs["overhead_frac"]
        assert obs["tick_span_coverage"] >= 0.95, obs["tick_span_coverage"]
        assert obs["trace_events"] > 0 and obs["ticks"] > 0
        lat = obs["latency_s"]
        assert 0 <= lat["mean"] <= lat["max"] and lat["p50"] <= lat["p95"]
        dc = obs["device_counters"]
        for field in ("elapsed_s", "overhead_frac_ungated", "merge_depth"):
            assert field in dc, f"obs.device_counters missing {field}"
        md = dc["merge_depth"]
        # merge depth is measured in trellis steps within the R-deep window;
        # R+1 is the sentinel for "never merged"
        window = obs["depth"] + obs["chunk"]
        assert 1 <= md["p50"] <= md["max"] <= window + 1
    # optional resilience / fault-injection section (--chaos): v6
    res = (payload.get("stream") or {}).get("resilience")
    if res is not None:
        for field in ("sessions", "steps", "chunk", "depth", "backend", "seed",
                      "producer_fault_rate", "injected", "streams_finished",
                      "streams_quarantined", "ticks_dropped", "snapshot",
                      "bits_committed", "timing_faults_bit_exact"):
            assert field in res, f"stream.resilience missing {field}"
        inj = res["injected"]
        assert inj and all(int(v) >= 0 for v in inj.values()), inj
        # the drain must actually have been chaotic: at least one injected
        # fault, and every stream accounted for — finished or quarantined,
        # none lost
        assert sum(int(v) for v in inj.values()) > 0
        assert (res["streams_finished"] + res["streams_quarantined"]
                == res["sessions"])
        # only fatal fault classes may quarantine; timing faults never do
        fatal = (inj.get("producer_exception", 0) + inj.get("corrupt_nan", 0)
                 + inj.get("corrupt_inf", 0) + inj.get("corrupt_shape", 0))
        assert res["streams_quarantined"] <= res["sessions"]
        if fatal == 0:
            assert res["streams_quarantined"] == 0
        # dropped ticks are exactly the injected device-step failures
        assert res["ticks_dropped"] == inj.get("device_step_failure", 0)
        assert res["timing_faults_bit_exact"] is True
        assert res["bits_committed"] > 0
        snap = res["snapshot"]
        for field in ("tick", "streams", "save_s", "restore_s", "bit_exact"):
            assert field in snap, f"stream.resilience.snapshot missing {field}"
        assert snap["bit_exact"] is True
        assert snap["save_s"] >= 0 and snap["restore_s"] >= 0
        assert 0 < snap["streams"] <= res["sessions"]
    # optional time-parallel tiled section (--long-blocks): v7
    lb = payload.get("long_blocks")
    if lb is not None:
        for field in ("workload", "by_T", "crossover_T_vs_sequential", "note"):
            assert field in lb, f"long_blocks missing {field}"
        assert lb["by_T"], "long_blocks.by_T must be non-empty"
        for T, row in lb["by_T"].items():
            assert int(T) >= 1
            assert row["sequential"]["time_s"] > 0
            if "xla_scan" in row:
                assert row["xla_scan"]["time_s"] > 0
            assert row["tiled"], f"long_blocks.by_T[{T}] has no tiled rows"
            for P, trow in row["tiled"].items():
                assert int(P) >= 1
                assert trow["time_s"] > 0 and trow["bits_per_s"] > 0
                # the exact seam regime may never trade correctness for
                # speed: every recorded tiled row must be bit-exact
                assert trow["bit_exact"] is True, f"tiled P={P} at T={T}"
                assert trow["speedup_vs_sequential"] > 0
                # speedup monotonicity in P is recorded, NOT asserted: it
                # legitimately rolls off past the lane budget
            assert str(row["best_tiles"]) in row["tiled"]
            best = row["tiled"][str(row["best_tiles"])]
            assert abs(row["best_speedup_vs_sequential"]
                       - best["speedup_vs_sequential"]) < 1e-9
        cx = lb["crossover_T_vs_sequential"]
        if cx is not None:
            row = lb["by_T"][str(cx)]
            assert row["best_speedup_vs_sequential"] > 1.0, (
                "crossover recorded at a T where tiling does not win"
            )
            # no smaller swept T already won
            for T, r in lb["by_T"].items():
                if int(T) < int(cx):
                    assert r["best_speedup_vs_sequential"] <= 1.0
    # optional static-analysis section (analysis_report.py): v8
    ana = payload.get("analysis")
    if ana is not None:
        for field in ("lint", "jaxpr", "pragmas", "stream_pragmas"):
            assert field in ana, f"analysis missing {field}"
        lint = ana["lint"]
        assert lint["files"] > 0 and lint["rules"] >= 5
        # the whole point of the section: the repo lints clean
        assert lint["violations"] == 0, lint.get("violation_lines")
        jx = ana["jaxpr"]
        assert jx["violations"] == 0, jx
        # every registered backend must be traced by a contract — a new
        # backend that lands without a hot-path contract fails the gate
        assert jx["backends_traced"] == jx["backends_registered"], jx
        assert jx["contracts"] and len(jx["contracts"]) >= jx["backends_traced"]
        for name, row in jx["contracts"].items():
            assert row["equations"] > 0, f"contract {name} traced nothing"
            assert row["violations"] == 0, f"contract {name} has violations"
        # exactly one sanctioned host sync in the streaming hot path
        assert ana["stream_pragmas"] == {"RPR003": 1}, ana["stream_pragmas"]
        san = ana.get("sanitize")
        if san is not None:
            assert san["ticks"] >= 1
            assert all(s == 1 for s in san["host_syncs_per_tick"]), san
            assert san["steady_recompiles"] == 0, san
            assert san["bit_exact_vs_unguarded"] is True
            assert san["transfer_guard"] == "disallow" and san["debug_nans"]
    # optional SISO turbo section (siso_throughput.py): v5
    turbo = payload.get("turbo")
    if turbo is not None:
        for field in ("workload", "ebn0_db", "ber", "by_iterations",
                      "early_exit"):
            assert field in turbo, f"turbo missing {field}"
        ber = turbo["ber"]
        # the reason the subsystem exists: iterative SISO decode must beat
        # the equivalent-rate conv/Viterbi baseline at the pinned Eb/N0
        assert ber["turbo"] <= ber["viterbi"], ber
        assert 0 <= ber["turbo"] <= 1 and 0 <= ber["viterbi"] <= 1
        assert turbo["by_iterations"], "by_iterations must be non-empty"
        for n, row in turbo["by_iterations"].items():
            assert int(n) >= 1
            assert row["bits_per_s"] > 0 and row["time_s"] > 0
        ee = turbo["early_exit"]
        assert ee["bits_per_s"] > 0
        assert 1 <= ee["iterations_run"] <= turbo["workload"]["iterations"]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    size = ap.add_mutually_exclusive_group()
    size.add_argument("--smoke", action="store_true",
                      help="small CPU-container shapes (the CI gate; default)")
    size.add_argument("--full", action="store_true", help="production batch shapes")
    ap.add_argument("--long-blocks", action="store_true",
                    help="add the sequential-vs-tiled long-stream sweep")
    ap.add_argument("--out", type=Path, default=DEFAULT_OUT)
    ap.add_argument("--quiet", action="store_true",
                    help="warnings only (the JSON artifact is still written)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    global log
    log = get_logger("bench.viterbi", quiet=args.quiet)
    payload = run(quick=not args.full, out=args.out,
                  long_blocks=args.long_blocks)
    check_schema(payload)
    for wl_key in ("paper_workload_k7", "paper_workload_k3"):
        wl = payload[wl_key]
        for name, row in wl["backends"].items():
            log.info(
                f"{wl_key}/{name}",
                time_s=row["time_s"],
                bits_per_s=row["bits_per_s"],
                hbm_bytes_per_bit=row.get("hbm_bytes_per_bit", 0.0),
            )
        log.info(
            f"{wl_key}/speedup",
            packed_vs_fused_hbm_model=wl["speedup"]["fused_packed_vs_fused_hbm_model"],
            packed_vs_sequential_measured=(
                wl["speedup"]["fused_packed_vs_sequential_measured"]
            ),
        )
    lb = payload.get("long_blocks")
    if lb is not None:
        for T, row in lb["by_T"].items():
            log.info(
                f"long_blocks/T={T}",
                sequential_s=row["sequential"]["time_s"],
                best_tiles=row["best_tiles"],
                best_speedup=row["best_speedup_vs_sequential"],
            )
        log.info("long_blocks/crossover",
                 T=lb["crossover_T_vs_sequential"])
    log.info("wrote", path=str(args.out), schema=payload["schema"],
             smoke=payload["smoke"], interpret=payload["interpret_mode"])


if __name__ == "__main__":
    main()
