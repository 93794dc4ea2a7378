"""Assemble the EXPERIMENTS.md §Roofline table from the dry-run records.

Reads benchmarks/results/dryrun/*.json (written by launch/dryrun.py),
computes the three roofline terms per (arch × shape) on the single-pod mesh,
flags the dominant term, and emits both a JSON report and a markdown table.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

RESULTS = Path(__file__).resolve().parent / "results"


def load_cells(mesh: str = "single", tag: str = "") -> List[Dict]:
    cells = []
    for f in sorted((RESULTS / "dryrun").glob(f"*--{mesh}{tag}.json")):
        cells.append(json.loads(f.read_text()))
    return cells


def one_row(cell: Dict) -> Dict:
    from repro.roofline.analysis import hardware, roofline_report

    if cell["status"] != "ok":
        return {"arch": cell["arch"], "shape": cell["shape"],
                "status": cell["status"], "reason": cell.get("reason", "")}
    hw = hardware(cell["device_kind"])
    terms = roofline_report(cell, hw)
    mem = cell["memory_analysis"]
    fits = (mem["temp_size_in_bytes"] + mem["argument_size_in_bytes"]) \
        < hw.hbm_bytes
    return {
        "arch": cell["arch"], "shape": cell["shape"], "status": "ok",
        "compute_s": terms["compute_s"], "memory_s": terms["memory_s"],
        "collective_s": terms["collective_s"], "dominant": terms["dominant"],
        "bound_s": terms["bound_s"],
        "mfu_bound": terms["mfu_bound"],
        "useful_ratio": terms["useful_ratio"],
        "temp_gib": mem["temp_size_in_bytes"] / 2 ** 30,
        "args_gib": mem["argument_size_in_bytes"] / 2 ** 30,
        "fits_hbm": fits,
    }


def markdown(rows: List[Dict]) -> str:
    hdr = ("| arch | shape | compute (s) | memory (s) | collective (s) | "
           "dominant | MFU-bound | useful | fits |")
    sep = "|" + "---|" * 9
    lines = [hdr, sep]
    for r in rows:
        if r["status"] != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | "
                         f"{r['status']}: {r.get('reason','')[:40]} | — | — | — |")
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} | "
            f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | "
            f"**{r['dominant'].replace('_s','')}** | {r['mfu_bound']:.2f} | "
            f"{r['useful_ratio']:.2f} | "
            f"{'yes' if r['fits_hbm'] else 'NO'} ({r['temp_gib']+r['args_gib']:.1f}G) |")
    return "\n".join(lines)


def run(tag: str = "") -> Dict:
    cells = load_cells("single", tag)
    rows = [one_row(c) for c in cells]
    md = markdown(rows)
    (RESULTS / f"roofline{tag or ''}.md").write_text(md + "\n")
    multi = load_cells("multi", tag)
    multi_ok = sum(1 for c in multi if c["status"] == "ok")
    multi_skip = sum(1 for c in multi if c["status"] == "skipped")
    summary = {
        "n_single": len(cells),
        "n_single_ok": sum(1 for r in rows if r["status"] == "ok"),
        "n_single_skipped": sum(1 for r in rows if r["status"] == "skipped"),
        "n_multi_ok": multi_ok,
        "n_multi_skipped": multi_skip,
        "n_fit": sum(1 for r in rows if r.get("fits_hbm")),
        "dominant_histogram": _hist(rows),
        "rows": rows,
    }
    (RESULTS / f"roofline{tag or ''}.json").write_text(
        json.dumps(summary, indent=1, default=float))
    return summary


def _hist(rows):
    h = {}
    for r in rows:
        if r["status"] == "ok":
            h[r["dominant"]] = h.get(r["dominant"], 0) + 1
    return h


if __name__ == "__main__":
    from repro.obs.log import get_logger

    log = get_logger("bench.roofline")
    out = run()
    log.info(json.dumps({k: v for k, v in out.items() if k != "rows"}, indent=1))
    log.info((RESULTS / "roofline.md").read_text())
