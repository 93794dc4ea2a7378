"""The readers of the submit_chunk metrics, on hand-made readings and on the
spans a rehearsed saturated stream cell records (CPU, tiny sizes)."""
import jax
import pytest

from bench import harness


def _spans(ticks, appends_per_tick, submit_ns):
    """Readings of ``ticks`` ticks, each after ``appends_per_tick`` submits
    of ``submit_ns`` ns with one append inside each."""
    spans = []
    for _ in range(ticks):
        for _ in range(appends_per_tick):
            spans += [("submit.check", 0, 1), ("submit.append", 0, 5),
                      ("submit", 0, submit_ns)]
        spans += [("ingest", 0, 1), ("gather", 0, 1), ("commit", 0, 1), ("tick", 0, 9)]
    return spans


@pytest.mark.parametrize("metric", ["submit_chunk_ms", "submit_chunk_ms.x4"])
def test_submit_chunk_ms_reads_submit_spans_per_chunk(metric):
    read = harness.layer_metric(metric)
    r = {"program_spans": _spans(3, 4, 2_000_000), "chunks": 12, "submit_s": 0.03}
    assert read(r) == pytest.approx(2.0)
    assert read({**r, "chunks": 0}) is None  # nothing submitted in the window
    # a program without submit spans (before they existed) reads nothing
    tick_only = [s for s in r["program_spans"] if not s[0].startswith("submit")]
    assert read({**r, "program_spans": tick_only}) is None
    assert read({"chunks": 12}) is None


@pytest.mark.parametrize("metric", ["appends_per_tick", "appends_per_tick.x4"])
def test_appends_per_tick_reads_append_spans_over_ticks(metric):
    read = harness.layer_metric(metric)
    assert read({"program_spans": _spans(5, 64, 1)}) == pytest.approx(64.0)
    assert read({"program_spans": _spans(2, 256, 1)}) == pytest.approx(256.0)
    assert read({"program_spans": []}) is None  # no ticks
    tick_only = [s for s in _spans(3, 4, 1) if not s[0].startswith("submit")]
    assert read({"program_spans": tick_only}) is None
    assert read({}) is None


def test_saturated_stream_cell_feeds_the_readers():
    """Through the unchanged stream driver: its tracer picks up the spans
    inside submit_chunk, one append per stream per tick."""
    cell = harness.load_cell("dvbs_r12.saturate_x1")
    ctx = {**cell, "seed": 2**33 + 11, "rehearse": True,
           "devices": jax.devices()[:1], "traced": True}
    run = harness.driver(cell["traffic"]["kind"]).Cell(ctx)
    appends0 = run.sched.stats.arena_appends
    run.measure(0.5, harness.Window(False))
    r = run.readings(None)
    assert run.ticks > 0 and run.chunks == run.n_streams * run.ticks
    assert harness.layer_metric("appends_per_tick")(r) == pytest.approx(run.n_streams)
    assert run.sched.stats.arena_appends - appends0 == run.chunks
    submit_ms = harness.layer_metric("submit_chunk_ms")(r)
    assert 0 < submit_ms <= harness.layer_metric("submit_host_ms")(r)
