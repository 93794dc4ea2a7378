"""A rehearsal run with the timed path broken underneath reads
``correct: false``; unbroken, it reads true.  Each run is its own process
(the four-chip cell needs four virtual CPU devices from the start).

The sharded tick exchanges nothing between chips, so no fault of that kind
is planted; a block decode carries no state, so neither is a stale state."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

CASES = [
    ("wifi_bcc34.short8", "answer_altered"),
    ("wifi_bcc34.short8", "half_batch"),
    ("dvbs_r12.saturate_x1", "answer_altered"),
    ("dvbs_r12.saturate_x1", "half_batch"),
    ("dvbs_r12.saturate_x1", "state_unchanged"),
    ("dvbs_r12.sharded_x4", "half_batch"),
    ("dvbs_r12.sharded_x4", "state_unchanged"),
]


def _run(cell, fault=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    args = ["--workload", cell, "--seed", str(2**32 + 17), "--seconds", "1",
            "--trace", "0", "--rehearse"]
    cmd = ([sys.executable, str(HERE / "faults.py"), fault] if fault
           else [sys.executable, str(ROOT / "bench" / "run.py")]) + args
    p = subprocess.run(cmd, env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell, fault", CASES)
def test_fault_reads_incorrect(cell, fault):
    assert _run(cell, fault)["correct"] is False


@pytest.mark.parametrize("cell", sorted({c for c, _ in CASES}))
def test_sound_run_reads_correct(cell):
    assert _run(cell)["correct"] is True


def test_no_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "wifi_bcc34.short8",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_without_the_program_exits_nonzero_with_no_result(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "wifi_bcc34.short8",
                        "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
                       env=env, cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_open_loop_driver_rehearsal():
    """The open-loop stream driver (no cell uses it yet: see PERF.md) runs
    its schedule and reads correct, at a rehearsal size."""
    import json

    import jax

    from bench import harness

    cell = harness.load_cell("dvbs_r12.saturate_x1")
    cell["traffic"] = json.loads((ROOT / "bench/traffic/dvbs_r12.steady64.json").read_text())
    ctx = {**cell, "seed": 2**40 + 3, "rehearse": True, "devices": jax.devices()[:1],
           "traced": False}
    run = harness.driver("stream_open").Cell(ctx)
    run.measure(1.0, harness.Window(False))
    run.release()
    assert run.attempted > 0 and run.failed == 0
    assert run.check()["bits_differing"][0] == 0
