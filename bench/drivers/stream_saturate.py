"""Saturating streams: before every tick each stream is topped up to its
credit, so every slot has a chunk ready on every tick.  The rate is the
committed bits of all streams over the window, from its start to the end
of the last tick that began inside it.

Set-up runs the same traffic until the arena has compacted once, so no
arena growth or compaction compiles in the window, which starts right after
that compaction, at the same point of the compaction cycle in every run.
"""
from __future__ import annotations

import time

from bench.drivers.streams import StreamCell
from bench.harness import annotate


class Cell(StreamCell):
    def __init__(self, ctx: dict):
        super().__init__(ctx)
        self.saturate_until_compacted()

    def measure(self, seconds: float, window) -> None:
        traced = window.traced
        clock = time.perf_counter
        if self.tracer is not None:
            self.tracer.clear()
        committed0 = sum(self.n_committed)
        self.chunks = 0
        self.ticks = 0
        self.submit_s = 0.0  # host time of the top-ups, all submit_chunk calls
        window.begin()
        t0 = clock()
        while clock() - t0 < seconds:
            ts = clock()
            with annotate(traced, "bench.submit"):
                self.chunks += self.top_up(counted=True)
            self.submit_s += clock() - ts
            with annotate(traced, "bench.tick"):
                self.tick()
            self.ticks += 1
        self.t_window = clock() - t0
        window.end()
        self.window = (t0, t0 + self.t_window)
        self.window_bits = sum(self.n_committed) - committed0

    @property
    def attempted(self) -> int:
        return self.chunks

    @property
    def failed(self) -> int:
        return self.refused

    def readings(self, peaks) -> dict:
        return {**super().readings(peaks), "submit_s": self.submit_s,
                "chunks": self.chunks}

    def end_to_end(self) -> dict:
        return {"stream_bits_per_s": self.window_bits / self.t_window}

    def notes(self) -> dict:
        return {"ticks": self.ticks, "chunks": self.chunks, "streams": self.n_streams,
                "tick_ms": 1e3 * self.t_window / max(self.ticks, 1)}
