"""Benchmark harness entry point: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # everything quick
  PYTHONPATH=src python -m benchmarks.run --full     # bigger sweeps
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback
from pathlib import Path

from repro.obs.log import get_logger

RESULTS = Path(__file__).resolve().parent / "results"

log = get_logger("bench.run")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--quiet", action="store_true",
                    help="warnings/failures only (JSON artifacts still written)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    global log
    log = get_logger("bench.run", quiet=args.quiet)
    RESULTS.mkdir(parents=True, exist_ok=True)

    from benchmarks import fig3_scaling, fig4_trend, roofline_report, tables, viterbi_throughput

    jobs = {
        "tables_3_4_5": tables.run,
        "fig3_scaling": fig3_scaling.run,
        "fig4_trend": fig4_trend.run,
        "viterbi_throughput": lambda: viterbi_throughput.run(quick=not args.full),
        "roofline_report": roofline_report.run,
    }
    if args.only:
        jobs = {k: v for k, v in jobs.items() if args.only in k}

    report = {}
    failed = []
    for name, fn in jobs.items():
        log.info(f"== {name} ==")
        try:
            out = fn()
            report[name] = out
            (RESULTS / f"{name}.json").write_text(
                json.dumps(out, indent=1, default=float))
            if name == "tables_3_4_5":
                log.info(json.dumps({k: out[k] for k in
                                     ("table3_dlx", "table4_picojava")}, indent=1,
                                    default=float))
            elif name == "roofline_report":
                log.info(json.dumps({k: v for k, v in out.items() if k != "rows"},
                                    indent=1, default=float))
            else:
                log.info("ok", group=name)
        except Exception as e:
            failed.append(name)
            log.error(f"FAILED {name}: {e}")
            traceback.print_exc()
    log.info("benchmark groups done", succeeded=len(report), total=len(jobs),
             results=str(RESULTS))
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
