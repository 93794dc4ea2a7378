"""The readings that a cell's ``correct`` limits are set from, on the chip.

    python3 bench/readings.py --workload <cell> --seeds 12 --control-seeds 3 --seconds 3

For each seed, in one process: set up the cell, run its timed path for a
short window, compare what it produced with the reference (the program's
reading); on the first ``--control-seeds`` seeds, also put the control
(the reference in bfloat16) in the program's place and compare that (the
control's reading).  One JSON line per seed.  The benchmark's own runs do
not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--first-seed", type=int, default=5_000_000_000)
    ap.add_argument("--rehearse", action="store_true", help="CPU rehearsal, tiny sizes")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    chips = int(cell["workload"]["chips"])
    if args.rehearse:
        import jax

        devices = jax.devices()[:chips]
    else:
        devices = harness.require_chips(chips)
        harness.enable_compile_cache()
    for k in range(args.seeds):
        seed = args.first_seed + k
        t = time.perf_counter()
        ctx = {**cell, "seed": seed, "rehearse": args.rehearse, "devices": devices, "traced": False}
        run = harness.driver(cell["traffic"]["kind"]).Cell(ctx)
        run.measure(args.seconds, harness.Window(False))
        run.release()
        line = {"seed": seed, "program": {k: v for k, (v, _) in run.check().items()},
                "bits_compared": run.bits_compared}
        if k < args.control_seeds:
            run.use_control()
            line["control"] = {k: v for k, (v, _) in run.check().items()}
        line["seconds"] = round(time.perf_counter() - t, 1)
        print(json.dumps(line), flush=True)
        del run
    return 0


if __name__ == "__main__":
    sys.exit(main())
