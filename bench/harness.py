"""What every cell's run shares: the manifest, the device check, the compile
cache, compile counting, the traced window, the per-layer metric readers and
the result line.  Nothing here knows a cell, a configuration or a traffic
mix by name: each is found through ``BENCHMARK.json``."""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: JAX's persistent compilation cache, at a fixed path in the checkout
#: (the path is part of what an entry is found by).
CACHE_DIR = ROOT / ".jax_cache"


class NoAccelerator(SystemExit):
    """Raised, before any measurement, when the chips the cell needs are absent."""


def load_cell(name: str) -> dict:
    """The manifest's workload ``name`` with its configuration and traffic
    files read in, and the metrics the cell reports."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    workload = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if workload is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    config = next(c for c in manifest["configs"] if c["name"] == workload["config"])

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return {
        "workload": workload,
        "config": json.loads((ROOT / config["file"]).read_text()),
        "traffic": json.loads((BENCH / "traffic" / f"{name}.json").read_text()),
        "end_to_end": [m for m in manifest["end_to_end"] if applies(m)],
        "per_layer": [m for m in manifest["per_layer"] if applies(m)],
    }


def require_chips(chips: int):
    """The TPU devices of this run, or exit nonzero with nothing printed to
    standard output."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(
            f"bench: needs a TPU, but JAX found {devices[0].platform!r} "
            f"({devices[0].device_kind}); nothing was measured"
        )
    if len(devices) < chips:
        raise NoAccelerator(f"bench: the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Records when programs are lowered (each jit cache miss, eager
    operations included), by the host clock, with what was lowered."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, _secs, **kw):
        if name == self.EVENT:
            self.events.append((time.perf_counter(), str(kw.get("fun_name", "?"))))

    def within(self, t0: float, t1: float):
        return [f for t, f in self.events if t0 <= t <= t1]


class GcTimer:
    """Records the host clock's intervals of Python's garbage collections."""

    def __init__(self):
        import gc

        self.spans = []
        self._start = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, _info):
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            self.spans.append((self._start, time.perf_counter()))
            self._start = None

    def within(self, t0: float, t1: float):
        """(collections, seconds) that began inside [t0, t1]."""
        inside = [b - a for a, b in self.spans if t0 <= a <= t1]
        return len(inside), sum(inside)


class Window:
    """The measured window.  Traced, the JAX profiler (no Python tracer)
    runs around the whole ``measure`` call and the driver marks the window
    itself with ``begin()`` and ``end()``, which put the host annotation
    ``bench.window`` around it; the raw events are kept in ``raw``."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.raw: Optional[dict] = None
        self.perf_ns_at_start = 0
        self._dir = None
        self._ann = None

    def begin(self) -> None:
        if self.traced:
            import jax

            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self.perf_ns_at_start = time.perf_counter_ns()
            self._ann.__enter__()
        else:
            self.perf_ns_at_start = time.perf_counter_ns()

    def end(self) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def __enter__(self):
        if self.traced:
            import jax

            self._dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self._dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        if self.traced:
            import jax

            from bench import tracereduce

            self.end()
            jax.profiler.stop_trace()
            try:
                if exc[0] is None:
                    self.raw = tracereduce.load_xplane(self._dir)
            finally:
                shutil.rmtree(self._dir, ignore_errors=True)
        return False


def annotate(traced: bool, name: str):
    """A host annotation on the trace clock when traced, else nothing."""
    if traced:
        import jax

        return jax.profiler.TraceAnnotation(name)
    return _NULL


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


def quantity_of(name: str, known) -> str:
    """The quantity a metric reads.  A metric split by cell is named
    ``<quantity>.<part>`` (``stream_bits_per_s.x4``) and reads what its
    quantity reads, unless ``known`` holds its own name."""
    q = name
    while q not in known and "." in q:
        q = q.rsplit(".", 1)[0]
    return q


def layer_metric(name: str) -> Callable[[dict], Optional[float]]:
    """The ``read`` function of ``bench/layer_metrics/<name>.py``, or of the
    metric's quantity where it has no reader of its own."""
    known = {p.stem for p in (BENCH / "layer_metrics").glob("*.py")}
    q = quantity_of(name, known)
    path = BENCH / "layer_metrics" / f"{q}.py"
    spec = importlib.util.spec_from_file_location(f"bench_layer_{q}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def driver(kind: str):
    return importlib.import_module(f"bench.drivers.{kind}")


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def emit(result: dict, checks: Dict[str, tuple]) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, and the result as the last line of standard output,
    with the same numbers under ``checks``, last."""
    result = dict(result)
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    sys.stdout.flush()
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
