"""BENCH_viterbi.json schema gate (v8): the validator the CI bench-smoke job
runs must accept well-formed payloads — including the ``stream.online``,
telemetry-acceptance ``obs``, SISO ``turbo``, fault-injection
``stream.resilience``, time-parallel ``long_blocks``, and static-analysis
``analysis`` sections — and reject the invariants it exists to guard."""
import copy

import pytest

from benchmarks.viterbi_throughput import BENCH_SCHEMA, check_schema


def _workload():
    return {
        "workload": {"constraint": 7, "n_states": 64, "batch": 8, "steps": 90},
        "backends": {
            name: {"bits_per_s": 1e6}
            for name in ("sequential", "fused_packed", "fused_packed_received")
        },
        "survivor_bytes": {"shrink_x": 30.0},
        "speedup": {
            "fused_packed_vs_fused_hbm_model": 14.8,
            "fused_packed_received_vs_fused_hbm_model": 19.0,
        },
    }


def _payload():
    return {
        "schema": BENCH_SCHEMA,
        "paper_workload_k7": _workload(),
        "paper_workload_k3": _workload(),
        "stream": {
            "by_shards": {
                "1": {"shards": 1, "slots_per_shard": 8, "n_slots": 8,
                      "bits_per_s": 1e5},
                "8": {"shards": 8, "slots_per_shard": 8, "n_slots": 64,
                      "bits_per_s": 8e5, "scaling_vs_shards1": 8.0},
            },
            "online": {
                "sessions": 8,
                "steps": 384,
                "chunk": 64,
                "depth": 15,
                "max_buffered": 512,
                "offered_rows_per_s_per_stream": 250.0,
                "bits_per_s": 1.2e3,
                "ticks": 7,
                "bit_exact_vs_offline": True,
                "latency_s": {"mean": 0.6, "p50": 0.55, "p95": 1.0, "max": 1.2},
                "queue_depth_rows": {"mean": 640.0, "max": 1650,
                                     "max_stream": 244},
            },
            "resilience": {
                "sessions": 8,
                "steps": 384,
                "chunk": 64,
                "depth": 15,
                "backend": "scan",
                "seed": 0,
                "producer_fault_rate": 0.1,
                "elapsed_s": 2.5,
                "injected": {
                    "producer_stall": 21,
                    "slow_drip": 9,
                    "producer_exception": 1,
                    "corrupt_nan": 1,
                    "device_step_failure": 2,
                },
                "streams_finished": 6,
                "streams_quarantined": 2,
                "quarantine_reasons": {"s1": "producer_error",
                                       "s4": "poisoned_chunk"},
                "ticks": 19,
                "ticks_dropped": 2,
                "bits_committed": 2500,
                "timing_faults_bit_exact": True,
                "snapshot": {
                    "tick": 3,
                    "streams": 8,
                    "bytes": 120000,
                    "save_s": 0.004,
                    "restore_s": 0.02,
                    "bit_exact": True,
                },
            },
        },
        "obs": {
            "sessions": 4,
            "steps": 192,
            "chunk": 64,
            "depth": 15,
            "backend": "scan",
            "ticks": 3,
            "repeats": 2,
            "elapsed_off_s": 0.034,
            "elapsed_on_s": 0.032,
            "overhead_frac": -0.045,
            "tick_span_coverage": 0.998,
            "trace_events": 24,
            "latency_s": {"count": 3, "mean": 0.01, "p50": 0.008,
                          "p95": 0.02, "max": 0.02},
            "device_counters": {
                "elapsed_s": 0.035,
                "overhead_frac_ungated": 0.032,
                "merge_depth": {"count": 4, "mean": 2.0, "p50": 2,
                                "p95": 2, "max": 2},
            },
            "bit_exact_with_telemetry": True,
        },
        "long_blocks": {
            "workload": {"constraint": 3, "n_states": 4, "metric": "hard",
                         "batch": 1, "Ts": [2048, 8192],
                         "tile_counts": [4, 16]},
            "by_T": {
                "2048": {
                    "sequential": {"time_s": 0.45, "bits_per_s": 4551.0},
                    "tiled": {
                        "4": {"time_s": 0.30, "bits_per_s": 6826.0,
                              "bit_exact": True,
                              "speedup_vs_sequential": 1.5},
                        "16": {"time_s": 0.21, "bits_per_s": 9752.0,
                               "bit_exact": True,
                               "speedup_vs_sequential": 2.14},
                    },
                    "best_tiles": 16,
                    "best_speedup_vs_sequential": 2.14,
                },
                "8192": {
                    "sequential": {"time_s": 0.48, "bits_per_s": 17066.0},
                    "tiled": {
                        "16": {"time_s": 0.28, "bits_per_s": 29257.0,
                               "bit_exact": True,
                               "speedup_vs_sequential": 1.71},
                    },
                    "best_tiles": 16,
                    "best_speedup_vs_sequential": 1.71,
                },
            },
            "crossover_T_vs_sequential": 2048,
            "note": "measured wall-clock; monotonicity recorded, not asserted",
        },
        "analysis": {
            "lint": {"files": 93, "rules": 5, "violations": 0,
                     "violation_lines": []},
            "jaxpr": {
                "contracts": {
                    "fused_packed": {"backend": "fused_packed",
                                     "equations": 69, "violations": 0},
                    "sharded_stream_tick": {"backend": "sharded_stream",
                                            "equations": 913, "violations": 0},
                },
                "backends_registered": 2,
                "backends_traced": 2,
                "violations": 0,
            },
            "pragmas": {"RPR003": 5},
            "stream_pragmas": {"RPR003": 1},
            "sanitize": {
                "ticks": 4,
                "host_syncs_per_tick": [1, 1, 1, 1],
                "steady_recompiles": 0,
                "guarded_tick_s": 0.004,
                "transfer_guard": "disallow",
                "debug_nans": True,
                "bit_exact_vs_unguarded": True,
            },
        },
        "turbo": {
            "workload": {
                "code": "rsc_k4_lte", "interleaver": "qpp(512,31,64)",
                "batch": 8, "block_len": 512, "iterations": 6,
            },
            "ebn0_db": 1.0,
            "ber": {"turbo": 0.0007, "viterbi": 0.012},
            "by_iterations": {
                "1": {"time_s": 0.02, "bits_per_s": 1.6e5},
                "2": {"time_s": 0.05, "bits_per_s": 8.2e4},
                "6": {"time_s": 0.15, "bits_per_s": 2.6e4},
            },
            "early_exit": {
                "time_s": 0.13, "bits_per_s": 3.1e4,
                "iterations_run": 5, "converged_frac": 1.0,
            },
        },
    }


def test_schema_is_v8():
    assert BENCH_SCHEMA == "bench_viterbi/v8"


def test_check_schema_accepts_valid_payload():
    check_schema(_payload())


def test_check_schema_accepts_payload_without_optional_sections():
    payload = _payload()
    del payload["stream"]
    del payload["obs"]
    del payload["turbo"]
    del payload["long_blocks"]  # pre-v7 content is fine
    del payload["analysis"]  # pre-v8 content is fine
    check_schema(payload)
    payload = _payload()
    del payload["analysis"]["sanitize"]  # lint-only analysis run is fine
    check_schema(payload)
    payload = _payload()
    del payload["stream"]["online"]  # by_shards alone (pre-v3 content) is fine
    del payload["stream"]["resilience"]  # pre-v6 content is fine too
    check_schema(payload)


def test_check_schema_accepts_chaos_run_with_no_fatal_faults():
    # a lucky seed can inject only timing faults: nothing quarantined
    payload = _payload()
    res = payload["stream"]["resilience"]
    res["injected"] = {"producer_stall": 4, "slow_drip": 2,
                       "device_step_failure": 1}
    res["streams_finished"] = 8
    res["streams_quarantined"] = 0
    res["quarantine_reasons"] = {}
    res["ticks_dropped"] = 1
    check_schema(payload)


@pytest.mark.parametrize(
    "mutate",
    [
        lambda p: p.__setitem__("schema", "bench_viterbi/v2"),
        lambda p: p["stream"]["online"].pop("latency_s"),
        lambda p: p["stream"]["online"].pop("max_buffered"),
        lambda p: p["stream"]["online"].__setitem__("bit_exact_vs_offline", False),
        # a single stream's queue deeper than its bound = backpressure broken
        lambda p: p["stream"]["online"]["queue_depth_rows"].__setitem__(
            "max_stream", 513
        ),
        # total queue deeper than sessions x bound = accounting broken
        lambda p: p["stream"]["online"]["queue_depth_rows"].__setitem__(
            "max", 8 * 512 + 1
        ),
        lambda p: p["stream"]["online"]["latency_s"].__setitem__("p95", 0.1),
    ],
)
def test_check_schema_rejects_broken_online_sections(mutate):
    payload = copy.deepcopy(_payload())
    mutate(payload)
    with pytest.raises((AssertionError, KeyError)):
        check_schema(payload)


@pytest.mark.parametrize(
    "mutate",
    [
        # the telemetry-plane acceptance gates, re-checked on the artifact
        lambda p: p["obs"].__setitem__("overhead_frac", 0.06),
        lambda p: p["obs"].__setitem__("tick_span_coverage", 0.90),
        lambda p: p["obs"].__setitem__("trace_events", 0),
        lambda p: p["obs"].__setitem__("bit_exact_with_telemetry", False),
        lambda p: p["obs"].pop("latency_s"),
        lambda p: p["obs"].pop("device_counters"),
        lambda p: p["obs"]["device_counters"].pop("merge_depth"),
        # merge depth above the R+1 "never merged" sentinel is impossible
        lambda p: p["obs"]["device_counters"]["merge_depth"].__setitem__(
            "max", 15 + 64 + 2
        ),
        lambda p: p["obs"]["latency_s"].__setitem__("p95", 0.001),
    ],
)
def test_check_schema_rejects_broken_obs_sections(mutate):
    payload = copy.deepcopy(_payload())
    mutate(payload)
    with pytest.raises((AssertionError, KeyError)):
        check_schema(payload)


@pytest.mark.parametrize(
    "mutate",
    [
        # a chaos run with zero injected faults is not a chaos run
        lambda p: p["stream"]["resilience"].__setitem__("injected", {}),
        # stream accounting broken: finished + quarantined != sessions
        lambda p: p["stream"]["resilience"].__setitem__("streams_finished", 7),
        # quarantine without any fatal fault class injected
        lambda p: p["stream"]["resilience"].__setitem__(
            "injected", {"producer_stall": 5, "device_step_failure": 2}
        ),
        # dropped ticks must equal injected device-step failures
        lambda p: p["stream"]["resilience"].__setitem__("ticks_dropped", 5),
        # timing faults changing the decode = arrival invariance broken
        lambda p: p["stream"]["resilience"].__setitem__(
            "timing_faults_bit_exact", False
        ),
        lambda p: p["stream"]["resilience"].pop("snapshot"),
        lambda p: p["stream"]["resilience"]["snapshot"].__setitem__(
            "bit_exact", False
        ),
        lambda p: p["stream"]["resilience"]["snapshot"].__setitem__(
            "restore_s", -0.01
        ),
        lambda p: p["stream"]["resilience"]["snapshot"].__setitem__(
            "streams", 0
        ),
        lambda p: p["stream"]["resilience"].__setitem__("bits_committed", 0),
    ],
)
def test_check_schema_rejects_broken_resilience_sections(mutate):
    payload = copy.deepcopy(_payload())
    mutate(payload)
    with pytest.raises((AssertionError, KeyError)):
        check_schema(payload)


@pytest.mark.parametrize(
    "mutate",
    [
        # the exact seam regime may never trade correctness for speed
        lambda p: p["long_blocks"]["by_T"]["2048"]["tiled"]["16"].__setitem__(
            "bit_exact", False
        ),
        lambda p: p["long_blocks"]["by_T"]["2048"]["tiled"]["4"].__setitem__(
            "time_s", 0.0
        ),
        lambda p: p["long_blocks"]["by_T"]["8192"]["sequential"].__setitem__(
            "time_s", -0.1
        ),
        # crossover claimed at a T where the best tiled config does not win
        lambda p: p["long_blocks"]["by_T"]["2048"].__setitem__(
            "best_speedup_vs_sequential", 0.9
        ),
        # crossover later than a T that already won
        lambda p: p["long_blocks"].__setitem__(
            "crossover_T_vs_sequential", 8192
        ),
        # best_tiles must point at a recorded tiled row
        lambda p: p["long_blocks"]["by_T"]["8192"].__setitem__("best_tiles", 32),
        lambda p: p["long_blocks"]["by_T"]["2048"].__setitem__("tiled", {}),
        lambda p: p["long_blocks"].pop("by_T"),
        lambda p: p["long_blocks"].pop("crossover_T_vs_sequential"),
    ],
)
def test_check_schema_rejects_broken_long_blocks_sections(mutate):
    payload = copy.deepcopy(_payload())
    mutate(payload)
    with pytest.raises((AssertionError, KeyError)):
        check_schema(payload)


@pytest.mark.parametrize(
    "mutate",
    [
        # the whole point of the section: the repo must lint clean
        lambda p: p["analysis"]["lint"].__setitem__("violations", 1),
        lambda p: p["analysis"]["jaxpr"].__setitem__("violations", 1),
        # a registered backend with no hot-path contract trace
        lambda p: p["analysis"]["jaxpr"].__setitem__("backends_registered", 3),
        lambda p: p["analysis"]["jaxpr"]["contracts"]["fused_packed"].__setitem__(
            "violations", 2
        ),
        lambda p: p["analysis"]["jaxpr"]["contracts"]["fused_packed"].__setitem__(
            "equations", 0
        ),
        lambda p: p["analysis"]["jaxpr"].__setitem__("contracts", {}),
        # a second RPR003 pragma sneaking into the streaming hot path
        lambda p: p["analysis"].__setitem__("stream_pragmas", {"RPR003": 2}),
        lambda p: p["analysis"].__setitem__("stream_pragmas", {}),
        # the guarded probe leaking an extra per-tick sync or a recompile
        lambda p: p["analysis"]["sanitize"].__setitem__(
            "host_syncs_per_tick", [1, 2, 1, 1]
        ),
        lambda p: p["analysis"]["sanitize"].__setitem__("steady_recompiles", 1),
        lambda p: p["analysis"]["sanitize"].__setitem__(
            "bit_exact_vs_unguarded", False
        ),
        lambda p: p["analysis"]["sanitize"].__setitem__("transfer_guard", None),
        lambda p: p["analysis"].pop("lint"),
        lambda p: p["analysis"].pop("stream_pragmas"),
    ],
)
def test_check_schema_rejects_broken_analysis_sections(mutate):
    payload = copy.deepcopy(_payload())
    mutate(payload)
    with pytest.raises((AssertionError, KeyError)):
        check_schema(payload)


@pytest.mark.parametrize(
    "mutate",
    [
        # the whole point of the section: turbo worse than Viterbi = rejected
        lambda p: p["turbo"]["ber"].__setitem__("turbo", 0.05),
        lambda p: p["turbo"]["ber"].__setitem__("viterbi", -0.01),
        lambda p: p["turbo"].pop("ber"),
        lambda p: p["turbo"].pop("early_exit"),
        lambda p: p["turbo"].__setitem__("by_iterations", {}),
        lambda p: p["turbo"]["by_iterations"]["6"].__setitem__("bits_per_s", 0),
        lambda p: p["turbo"]["by_iterations"]["1"].__setitem__("time_s", -1.0),
        # early exit cannot have run more iterations than the spec allows
        lambda p: p["turbo"]["early_exit"].__setitem__("iterations_run", 7),
        lambda p: p["turbo"]["early_exit"].__setitem__("bits_per_s", 0),
    ],
)
def test_check_schema_rejects_broken_turbo_sections(mutate):
    payload = copy.deepcopy(_payload())
    mutate(payload)
    with pytest.raises((AssertionError, KeyError)):
        check_schema(payload)
