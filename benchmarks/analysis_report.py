"""Static-analysis acceptance report -> ``analysis`` section of
``results/BENCH_viterbi.json`` (schema v8).

Records, as CI-gated data rather than prose:

  * the repo-rule lint result over ``src/`` (files, violations — must be 0),
  * the jaxpr contract trace of EVERY registered hot path (equations
    walked, violations — must be 0, backend coverage must equal the
    registry),
  * the pragma census (total and the stream-scope count, which must be
    exactly the one sanctioned host sync),
  * with ``--sanitize``: a steady-state scheduler probe run under the full
    :func:`repro.analysis.sanitized` bundle — transfer guard + debug-NaNs +
    counters — asserting exactly one user host sync per tick, zero
    steady-state recompiles, and bit-exact output vs an unguarded run.

Exit status is non-zero on any violation, so the CI job fails loudly even
if nobody reads the JSON.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.analysis import (  # noqa: E402
    check_hot_paths,
    count_pragmas,
    lint_paths,
    sanitized,
)
from repro.analysis.repo_lint import RULES  # noqa: E402
from repro.core import CODE_K3_STD, bsc, encode, hard_branch_metrics  # noqa: E402
from repro.decode import list_decoders  # noqa: E402
from repro.obs import get_logger  # noqa: E402
from repro.stream import StreamScheduler  # noqa: E402

RESULTS = HERE / "results"
BENCH_JSON = RESULTS / "BENCH_viterbi.json"
SRC = REPO / "src"

log = get_logger("bench.analysis")

SANITIZE_TICKS = 4


def _lint_block() -> dict:
    violations, n_files = lint_paths([SRC])
    return {
        "files": n_files,
        "rules": len(RULES),
        "violations": len(violations),
        "violation_lines": [str(v) for v in violations[:20]],
    }


def _contracts_block() -> dict:
    report = check_hot_paths()
    contracts = {
        name: {
            "backend": entry["backend"],
            "equations": entry["equations"],
            "violations": len(entry["violations"]),
        }
        for name, entry in sorted(report.items())
    }
    return {
        "contracts": contracts,
        "backends_registered": len(list_decoders()),
        "backends_traced": len({e["backend"] for e in report.values()}),
        "violations": sum(len(e["violations"]) for e in report.values()),
    }


def _scheduler_outputs(streams, guarded: bool) -> tuple:
    """Drain the probe workload; when guarded, steady ticks run under the
    full sanitizer and the per-tick counters are recorded."""
    sched = StreamScheduler(
        CODE_K3_STD, n_slots=2, chunk=16, depth=30, backend="scan"
    )
    if not guarded:
        for sid, bm in streams.items():
            sched.submit(sid, bm)
        return sched.run(), None
    per_tick = []
    with sanitized() as rep:
        with rep.allow_transfers():  # admission + warm-up: control plane
            for sid, bm in streams.items():
                sched.submit(sid, bm)
            sched.step()
        base = rep.snapshot()
        t0 = time.perf_counter()
        for _ in range(SANITIZE_TICKS):
            tick = rep.snapshot()
            sched.step()
            per_tick.append(rep.host_syncs - tick.host_syncs)
        elapsed = time.perf_counter() - t0
        steady_recompiles = rep.recompiles - base.recompiles
        with rep.allow_transfers():  # drain: slot finishing is control plane
            out = sched.run()
    return out, {
        "ticks": SANITIZE_TICKS,
        "host_syncs_per_tick": per_tick,
        "steady_recompiles": steady_recompiles,
        "guarded_tick_s": elapsed / SANITIZE_TICKS,
        "transfer_guard": rep.transfer_guard,
        "debug_nans": rep.debug_nans,
    }


def _sanitize_block() -> dict:
    key = jax.random.PRNGKey(0)
    bits = jax.random.bernoulli(key, 0.5, (2, 158)).astype(np.int32)
    coded = encode(CODE_K3_STD, bits, terminate=True)
    rx = bsc(jax.random.fold_in(key, 1), coded, 0.04)
    bm = hard_branch_metrics(CODE_K3_STD, rx)
    streams = {f"s{i}": bm[i] for i in range(2)}
    plain, _ = _scheduler_outputs(streams, guarded=False)
    guarded, stats = _scheduler_outputs(streams, guarded=True)
    bit_exact = all(
        np.array_equal(plain[sid][0], guarded[sid][0]) for sid in streams
    )
    stats["bit_exact_vs_unguarded"] = bool(bit_exact)
    return stats


def build_section(sanitize: bool) -> dict:
    section = {
        "lint": _lint_block(),
        "jaxpr": _contracts_block(),
        "pragmas": count_pragmas([SRC]),
        "stream_pragmas": count_pragmas([SRC / "repro" / "stream"]),
    }
    if sanitize:
        section["sanitize"] = _sanitize_block()
    return section


def _violation_count(section: dict) -> int:
    n = section["lint"]["violations"] + section["jaxpr"]["violations"]
    if section["jaxpr"]["backends_traced"] != section["jaxpr"]["backends_registered"]:
        n += 1
    san = section.get("sanitize")
    if san is not None:
        if any(s != 1 for s in san["host_syncs_per_tick"]):
            n += 1
        if san["steady_recompiles"] != 0 or not san["bit_exact_vs_unguarded"]:
            n += 1
    return n


def _merge(section: dict) -> None:
    from viterbi_throughput import BENCH_SCHEMA

    if BENCH_JSON.exists():
        try:
            bench = json.loads(BENCH_JSON.read_text())
        except ValueError:
            bench = {}
    else:
        bench = {}
    bench.setdefault("generated_by", "benchmarks/analysis_report.py")
    bench["schema"] = BENCH_SCHEMA
    bench["analysis"] = section
    RESULTS.mkdir(parents=True, exist_ok=True)
    BENCH_JSON.write_text(json.dumps(bench, indent=1))
    log.info(f"merged analysis into {BENCH_JSON}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--sanitize", action="store_true",
        help="also run the steady-state scheduler probe under the runtime "
             "sanitizer bundle (transfer guard + debug-NaNs + counters)",
    )
    ap.add_argument(
        "--no-merge", action="store_true",
        help="report only; do not touch results/BENCH_viterbi.json",
    )
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    section = build_section(sanitize=args.sanitize)
    for line in section["lint"]["violation_lines"]:
        log.warning(line)
    jx = section["jaxpr"]
    log.info(
        "analysis",
        files=section["lint"]["files"],
        lint_violations=section["lint"]["violations"],
        hot_paths=len(jx["contracts"]),
        backends=f"{jx['backends_traced']}/{jx['backends_registered']}",
        contract_violations=jx["violations"],
        stream_pragmas=sum(section["stream_pragmas"].values()),
    )
    san = section.get("sanitize")
    if san is not None:
        log.info(
            "sanitize",
            host_syncs_per_tick=",".join(map(str, san["host_syncs_per_tick"])),
            steady_recompiles=san["steady_recompiles"],
            bit_exact=san["bit_exact_vs_unguarded"],
            guarded_tick_s=san["guarded_tick_s"],
        )
    if not args.no_merge:
        _merge(section)
    return 1 if _violation_count(section) else 0


if __name__ == "__main__":
    sys.exit(main())
