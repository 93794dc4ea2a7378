"""SISO turbo decoder benchmark: one BER point against the equivalent-rate
Viterbi baseline + decoded bits/s per iteration count, merged as the
``turbo`` section of the ONE benchmark artifact, BENCH_viterbi.json
(schema bench_viterbi/v5).

Workload: the golden-gate pair from tests/test_golden_ber.py — a rate-1/3
LTE-constituent turbo code (K=4 RSC, N=512 QPP interleaver) against the
rate-1/3 K=7 (133,171,165) soft-decision Viterbi code, both at
Eb/N0 = 1.0 dB.  The BER comparison is the acceptance gate (iterative
SISO must beat the one-shot Viterbi baseline there); the per-iteration
throughput rows show what each extra BCJR sweep costs.

Numbers from the CPU container are interpret-mode proxies (shape parity
only); on a real TPU the same code runs the compiled kernels.

  PYTHONPATH=src python benchmarks/siso_throughput.py [--smoke]
"""
from __future__ import annotations

import argparse
import contextlib
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.trellis import ConvCode
from repro.decode import CodecSpec, decode
from repro.obs.log import get_logger
from repro.siso import QPPInterleaver, RSC_K4_LTE, TurboSpec, turbo_decode

BENCH_JSON = Path(__file__).resolve().parent / "results" / "BENCH_viterbi.json"
log = get_logger("bench.siso")

EBN0_DB = 1.0
RATE = 1.0 / 3.0
TURBO_SPEC = TurboSpec(code=RSC_K4_LTE, interleaver=QPPInterleaver(512, 31, 64))
CONV_SPEC = CodecSpec(
    code=ConvCode(7, (0o133, 0o171, 0o165)), metric="soft", terminated=False
)


def _load_bench() -> dict:
    from viterbi_throughput import BENCH_SCHEMA

    if BENCH_JSON.exists():
        with contextlib.suppress(ValueError):  # corrupt artifact: rebuild
            bench = json.loads(BENCH_JSON.read_text())
            bench["schema"] = BENCH_SCHEMA
            return bench
    return {"schema": BENCH_SCHEMA,
            "generated_by": "benchmarks/siso_throughput.py"}


def _timed_turbo(spec, llrs, *, iterations, early_exit, repeats):
    """(mean seconds, result) with a warm-up decode excluded from timing."""
    result = turbo_decode(spec, llrs, iterations=iterations,
                          early_exit=early_exit)
    jax.block_until_ready(result.llr)
    t0 = time.perf_counter()
    for _ in range(repeats):
        result = turbo_decode(spec, llrs, iterations=iterations,
                              early_exit=early_exit)
    jax.block_until_ready(result.llr)
    return (time.perf_counter() - t0) / repeats, result


def run(quick: bool = True) -> dict:
    batch, n_keys, repeats = (8, 2, 1) if quick else (64, 8, 3)
    tspec, cspec = TURBO_SPEC, CONV_SPEC
    snr_db = EBN0_DB + 10 * np.log10(RATE)
    rng = np.random.default_rng(2026)
    bits = jnp.asarray(rng.integers(0, 2, size=(batch, tspec.block_len)),
                       jnp.int32)
    tcoded = tspec.encode(bits)
    ccoded = cspec.encode(bits)

    # --- BER point: turbo vs equivalent-rate Viterbi at Eb/N0 = 1 dB ------- #
    t_errs = c_errs = total = 0
    for k in range(n_keys):
        key = jax.random.PRNGKey(500 + k)
        k1, k2 = jax.random.split(key)
        rx_t = tspec.channel(k1, tcoded, snr_db=snr_db)
        res_t = turbo_decode(tspec, tspec.channel_llrs(rx_t, snr_db=snr_db))
        t_errs += int(jnp.sum(res_t.bits != bits))
        rx_c = cspec.channel(k2, ccoded, snr_db=snr_db)
        res_c = decode(cspec, rx_c)
        c_errs += int(jnp.sum(res_c.info_bits != bits))
        total += bits.size
    ber_turbo, ber_viterbi = t_errs / total, c_errs / total

    # --- throughput per iteration count ------------------------------------ #
    rx = tspec.channel(jax.random.PRNGKey(900), tcoded, snr_db=snr_db)
    llrs = tspec.channel_llrs(rx, snr_db=snr_db)
    decoded_bits = batch * tspec.block_len
    by_iterations = {}
    for n_iter in (1, 2, tspec.iterations):
        t, _ = _timed_turbo(tspec, llrs, iterations=n_iter, early_exit=False,
                            repeats=repeats)
        by_iterations[str(n_iter)] = {
            "time_s": t, "bits_per_s": decoded_bits / t,
        }
    t_ee, res_ee = _timed_turbo(tspec, llrs, iterations=None, early_exit=True,
                                repeats=repeats)
    section = {
        "workload": {
            "constituent_constraint": tspec.code.constraint,
            "constituent_fb_oct": oct(tspec.code.feedback),
            "constituent_fwd_oct": [oct(g) for g in tspec.code.forward],
            "interleaver": repr(tspec.interleaver),
            "block_len": tspec.block_len,
            "batch": batch,
            "rate": RATE,
            "iterations": tspec.iterations,
            "extrinsic_scale": tspec.extrinsic_scale,
            "noise_keys": n_keys,
            "viterbi_baseline": cspec.describe(),
        },
        "ebn0_db": EBN0_DB,
        "ber": {"turbo": ber_turbo, "viterbi": ber_viterbi},
        "by_iterations": by_iterations,
        "early_exit": {
            "time_s": t_ee,
            "bits_per_s": decoded_bits / t_ee,
            "iterations_run": int(res_ee.iterations_run),
            "converged_frac": float(jnp.mean(res_ee.converged.astype(
                jnp.float32))),
        },
    }
    return section


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small CPU-container shapes (the CI gate; default)")
    ap.add_argument("--full", action="store_true", help="production shapes")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    global log
    log = get_logger("bench.siso", quiet=args.quiet)
    section = run(quick=not args.full)
    bench = _load_bench()
    bench["turbo"] = section
    BENCH_JSON.parent.mkdir(parents=True, exist_ok=True)
    BENCH_JSON.write_text(json.dumps(bench, indent=1))
    ber = section["ber"]
    log.info("turbo vs viterbi BER @ Eb/N0=1.0dB",
             turbo=ber["turbo"], viterbi=ber["viterbi"])
    for n, row in section["by_iterations"].items():
        log.info(f"turbo x{n} iterations", bits_per_s=row["bits_per_s"])
    ee = section["early_exit"]
    log.info("turbo early-exit", bits_per_s=ee["bits_per_s"],
             iterations_run=ee["iterations_run"])
    log.info(f"merged turbo section into {BENCH_JSON}")
    assert ber["turbo"] <= ber["viterbi"], (
        f"turbo BER {ber['turbo']} did not beat viterbi {ber['viterbi']}"
    )


if __name__ == "__main__":
    main()
