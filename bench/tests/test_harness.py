"""The harness finds every metric's reader by name, and a saturated stream
cell's set-up leaves nothing to compile in its window (CPU rehearsal)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_split_metrics_read_their_quantity():
    assert harness.quantity_of("stream_bits_per_s.x4", {"stream_bits_per_s"}) == \
        "stream_bits_per_s"
    assert harness.quantity_of("setup_s", {"setup_s"}) == "setup_s"
    read = harness.layer_metric("submit_host_ms.x4")
    assert read({"submit_s": 1.5, "chunks": 300}) == pytest.approx(5.0)
    assert read({"submit_s": 0.0, "chunks": 0}) is None


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(harness.layer_metric(metric))


@pytest.mark.parametrize("cell", ["dvbs_r12.saturate_x1", "dvbs_r12.sharded_x4"])
def test_saturated_window_compiles_nothing(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", str(2**33 + 5),
         "--seconds", "2", "--trace", "0", "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    window = next(json.loads(line[len("window: "):]) for line in p.stdout.splitlines()
                  if line.startswith("window: "))
    assert window["ticks"] > 0
    assert window["compiles_in_window"] == 0, window["compiled_in_window"]
