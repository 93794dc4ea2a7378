"""Run one cell of the benchmark once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (a workload of ``BENCHMARK.json``) names a configuration file under
``bench/configs/`` and a traffic file ``bench/traffic/<cell>.json``, whose
``kind`` names the driver module under ``bench/drivers/``.  The run loads,
warms up every shape the window uses (set-up, reported as ``setup_s``),
measures for ``--seconds``, compares what the timed path produced with the
plain reference, and prints one JSON line last on standard output.  With
``--trace 1`` the window runs under the JAX profiler and the line carries
the cell's per-layer metrics, read by ``bench/layer_metrics/<metric>.py``.

Without the TPU chips the cell asks for it exits nonzero before measuring.
``--rehearse`` is the explicit CPU rehearsal: tiny sizes, any platform,
and a result line with no device metric in it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

#: Longest window a traced run measures: a trace of more is large to read.
TRACE_SECONDS = 3.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes; reports no device metric")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    chips = int(cell["workload"]["chips"])

    import jax

    if args.rehearse:
        devices = jax.devices()[:chips]
        if len(devices) < chips:
            raise harness.NoAccelerator(f"rehearsal needs {chips} devices")
    else:
        devices = harness.require_chips(chips)
        harness.enable_compile_cache()
    traced = bool(args.trace)
    ctx = {**cell, "seed": args.seed, "rehearse": args.rehearse, "devices": devices,
           "traced": traced}
    run = harness.driver(cell["traffic"]["kind"]).Cell(ctx)
    compiles = harness.CompileCounter()
    collections = harness.GcTimer()
    setup_s = time.perf_counter() - T_START

    seconds = min(args.seconds, TRACE_SECONDS) if traced else args.seconds
    with harness.Window(traced) as window:
        run.measure(seconds, window)
    compiled_in_window = compiles.within(*run.window)
    gc_in_window = collections.within(*run.window)
    peak = harness.memory_peak(devices)
    e2e = run.end_to_end()
    readings = run.readings(None if args.rehearse else _peaks(devices))
    run.release()
    checks = run.check()
    correct = all(v <= lim for v, lim in checks.values())

    print("window: " + json.dumps({
        "compiles_in_window": len(compiled_in_window),
        "compiled_in_window": sorted(set(compiled_in_window)), "peak_bytes_in_use": peak,
        "setup_s": setup_s, "gc_in_window": {"collections": gc_in_window[0],
                                              "seconds": gc_in_window[1]},
        **run.notes(), "bits_compared": getattr(run, "bits_compared", None),
    }), flush=True)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": run.attempted, "failed": run.failed}
    if args.rehearse:  # no device metric comes from a rehearsal
        result.update(metrics={}, device={"platform": devices[0].platform,
                                          "rehearsal": True})
    elif not traced:
        e2e["setup_s"] = setup_s
        result.update(metrics={m["name"]: {"value": e2e[harness.quantity_of(m["name"], e2e)],
                                           "unit": m["unit"]}
                               for m in cell["end_to_end"]}, device=device)
    else:
        from bench import tracereduce

        extra = tracereduce.program_spans_on_trace_clock(
            readings.get("program_spans", ()), window.perf_ns_at_start, window.raw)
        summary = tracereduce.reduce(window.raw, extra)
        readings["trace"] = summary
        metrics = {}
        for m in cell["per_layer"]:
            value = harness.layer_metric(m["name"])(readings)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result.update(metrics=metrics, device=device,
                      breakdown=tracereduce.breakdown(summary))
    harness.emit(result, checks)
    return 0


def _peaks(devices):
    from bench.peaks import peaks

    return peaks(devices[0].device_kind)


if __name__ == "__main__":
    sys.exit(main())
