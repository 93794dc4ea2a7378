"""The lte_turbo.cb6144 cell at its rehearsal size (B=4 code blocks of
K=40, CPU): a sound run reads ``correct: true`` and compiles nothing in its
window; a run with the decoder broken underneath reads ``correct: false``.
Each run is its own process.  Also the new metrics' readers, on hand-made
readings."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness, tracereduce

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CELL = "lte_turbo.cb6144"


def _run(fault=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    args = ["--workload", CELL, "--seed", str(2**35 + 23), "--seconds", "1.5",
            "--trace", "0", "--rehearse"]
    cmd = ([sys.executable, str(HERE / "turbo_faults.py"), fault] if fault
           else [sys.executable, str(ROOT / "bench" / "run.py")]) + args
    p = subprocess.run(cmd, env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    window = next(json.loads(x[len("window: "):]) for x in lines if x.startswith("window: "))
    return json.loads(lines[-1]), window


def test_sound_rehearsal_reads_correct_and_compiles_nothing_in_its_window():
    result, window = _run()
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert window["compiles_in_window"] == 0, window["compiled_in_window"]
    assert window["plan"] == "turbo" and window["bits_compared"] == 2 * 4 * 40
    assert window["checked_inputs"] == [0, 1]  # one sampled call of each pool input
    assert window["call_p99_ms"] > 0 and len(window["slowest_calls"]) == 3


def test_config_qpp_row_must_match_the_programs():
    from bench.drivers import turbo_closed

    cfg = harness.load_cell(CELL)["config"]
    spec = turbo_closed.turbo_spec(cfg["code"], cfg["decoder"], 40, 3, 10)
    assert spec.tail == "36.212" and spec.interleaver.n == 40
    with pytest.raises(SystemExit, match="QPP row for K=40"):
        turbo_closed.turbo_spec(cfg["code"], cfg["decoder"], 40, 3, 30)


@pytest.mark.parametrize("fault", ["tails_ignored", "extrinsic_dropped"])
def test_fault_reads_incorrect(fault):
    result, _ = _run(fault)
    assert result["correct"] is False, result["checks"]


def test_turbo_span_readers():
    spans = []
    for its in (8, 6):
        spans += [("decode.plan", 0, 1)]
        for _ in range(its):
            spans += [("turbo.dispatch", 0, 2_000_000), ("turbo.sync", 0, 9),
                      ("turbo.iteration", 0, 10)]
        spans += [("turbo", 0, 99), ("decode.dispatch", 0, 99), ("decode", 0, 100)]
    r = {"program_spans": spans}
    assert harness.layer_metric("turbo_iterations_per_call")(r) == pytest.approx(7.0)
    assert harness.layer_metric("turbo_dispatch_ms")(r) == pytest.approx(2.0)
    # a program without the turbo spans (or no tracer) reads nothing
    plain = [s for s in spans if not s[0].startswith("turbo")]
    for name in ("turbo_iterations_per_call", "turbo_dispatch_ms"):
        assert harness.layer_metric(name)({"program_spans": plain}) is None
        assert harness.layer_metric(name)({}) is None


def test_turbo_device_readers():
    trace = tracereduce.TraceSummary(window_s=3.0, busy_s=2.7, kernel_s=2.4, glue_s=0.3,
                                     op_s={}, gaps=[])
    r = {"trace": trace, "essential_s": 0.0024}
    assert harness.layer_metric("bcjr_kernels_roofline")(r) == pytest.approx(0.1)
    assert harness.layer_metric("glue_device_share.turbo")(r) == pytest.approx(100 * 0.3 / 2.7)
    assert harness.layer_metric("device_idle_share.turbo")(r) == pytest.approx(10.0)
    assert harness.layer_metric("bcjr_kernels_roofline")({**r, "essential_s": 0.0}) is None
