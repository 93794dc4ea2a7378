"""Profiler trace -> device busy/idle, kernel time, glue share, idle gaps.

A traced run wraps its measured window in the host annotation
``bench.window``; everything is read inside that interval, on the trace's
own clock.  Device operations are the events of each TPU plane's
``XLA Ops`` line, named by the program (``XLA Modules`` line) they ran in
and their HLO instruction.  A Pallas kernel is an operation whose HLO is a
``tpu_custom_call``; everything else on the device is glue (layout,
padding, gathers, the frontier argmin).  Idle gaps are the intervals of the
window in which no operation ran, each labelled with the innermost host
span open at its midpoint.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Dict, List, Sequence, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_KERNEL_MARK = "tpu_custom_call"

Event = Tuple[str, float, float]  # (name, start_ns, duration_ns)


def load_xplane(profile_dir: str) -> dict:
    """The raw events of the one ``.xplane.pb`` under ``profile_dir``:
    {"host": [(name, start, dur)], "devices": {id: [(op, start, dur,
    is_kernel)]}}, each op named ``<program>:<instruction>``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {profile_dir}, found {files}")
    data = ProfileData.from_file(files[0])
    host: List[Event] = []
    devices: Dict[str, list] = {}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name.split("(")[0]) for ev in lines.get(MODULES_LINE, []))
            starts = [a for a, _, _ in modules]
            ops = devices.setdefault(m.group(1), [])
            for ev in lines.get(OPS_LINE, []):
                k = bisect.bisect_right(starts, ev.start_ns) - 1
                module = modules[k][2] if k >= 0 and ev.start_ns < modules[k][1] else "?"
                instr = ev.name.split(" = ", 1)[0]
                ops.append((f"{module}:{instr}", ev.start_ns, ev.duration_ns,
                            _KERNEL_MARK in ev.name))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend((ev.name, ev.start_ns, ev.duration_ns)
                            for ev in line.events if ev.name.startswith("bench."))
    return {"host": host, "devices": devices}


def save_raw(raw: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(raw, f)


def load_raw(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # mean over the chips traced
    kernel_s: float  # summed kernel time, all chips
    glue_s: float  # summed non-kernel op time, all chips
    op_s: Dict[str, float]  # total device time per operation name
    gaps: List[Tuple[str, float]]  # longest idle gaps on chip 0, labelled

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def window_of(raw: dict) -> Tuple[float, float]:
    wins = [(s, s + d) for n, s, d in raw["host"] if n == WINDOW]
    if len(wins) != 1:
        raise RuntimeError(f"expected one {WINDOW} span in the trace, found {len(wins)}")
    return wins[0]


def reduce(raw: dict, extra_spans: Sequence[Event] = (), n_gaps: int = 10) -> TraceSummary:
    """Summarise the traced window.  ``extra_spans`` are further host spans
    on the trace clock (the program's own tick phases), for gap labels."""
    lo, hi = window_of(raw)
    if not raw["devices"]:
        raise RuntimeError("the trace holds no TPU operations")
    busy, kernel, glue = [], 0.0, 0.0
    op_s: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for dev in sorted(raw["devices"], key=int):
        spans = []
        for name, s, d, is_kernel in raw["devices"][dev]:
            a, b = max(s, lo), min(s + d, hi)
            if b <= a:
                continue
            spans.append((a, b))
            op_s[name] = op_s.get(name, 0.0) + (b - a) * 1e-9
            if is_kernel:
                kernel += (b - a) * 1e-9
            else:
                glue += (b - a) * 1e-9
        merged = _union(spans)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        if dev == min(raw["devices"], key=int):
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    host = [e for e in raw["host"] if e[0] != WINDOW] + list(extra_spans)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:n_gaps]
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / len(busy),
        kernel_s=kernel,
        glue_s=glue,
        op_s=op_s,
        gaps=[(_label(host, (a + b) / 2), (b - a) * 1e-9) for a, b in longest],
    )


def _label(host: Sequence[Event], t: float) -> str:
    """Innermost host span open at ``t`` (the latest-starting one)."""
    open_ = [(s, n) for n, s, d in host if s <= t < s + d]
    return max(open_)[1] if open_ else "no host span"


def breakdown(summary: TraceSummary, n: int = 10) -> dict:
    top = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:n]
    return {
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": [[k, v] for k, v in summary.gaps[:n]],
    }


def program_spans_on_trace_clock(
    spans: Sequence[Tuple[str, int, int]], perf_ns_at_window: int, raw: dict,
) -> List[Event]:
    """Map spans timed by ``time.perf_counter_ns`` onto the trace clock,
    anchored at the start of the ``bench.window`` annotation, whose
    ``perf_counter_ns`` reading the caller took as it entered it."""
    lo, _ = window_of(raw)
    shift = lo - perf_ns_at_window
    return [(f"tick.{n}", t0 + shift, d) for n, t0, d in spans]

