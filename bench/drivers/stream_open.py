"""Open-loop streams: every stream sends ``chunk``-row chunks at one fixed
rate, ``rate_rows_per_s``, whatever the scheduler does; the streams' phases
are staggered evenly over one chunk period.

Set-up saturates the streams until the arena has compacted once, drains,
then runs the schedule for ``settle_s``.  (Under this traffic compaction
still compiles shapes that follow the queues: PERF.md, Open questions.)  The window counts the chunks due in it.  A chunk's
latency runs from its due time to the end of the tick that consumed it, the
tick that commits the bits its rows completed (the traceback depth is
not counted).  After the window the schedule goes on until every counted
chunk is consumed, or ``drain_timeout_s`` passes; a counted chunk refused
(``StreamBusy``) or never consumed has failed.
"""
from __future__ import annotations

import time

from bench import stats
from bench.drivers.streams import StreamCell
from bench.harness import annotate


class Cell(StreamCell):
    def __init__(self, ctx: dict):
        super().__init__(ctx)
        tr = ctx["traffic"]
        rate = float(tr["rate_rows_per_s"])
        if ctx["rehearse"]:
            rate = float(tr["rehearsal"]["rate_rows_per_s"])
        self.period = self.chunk / rate  # seconds between one stream's chunks
        self.settle_s = float(tr["settle_s"])
        self.drain_timeout_s = float(tr["drain_timeout_s"])
        self.saturate_until_compacted()
        self.drain()

    def measure(self, seconds: float, window) -> None:
        traced = window.traced
        clock = time.perf_counter
        n = self.n_streams
        t_base = clock()
        t0 = t_base + self.settle_s
        t1 = t0 + seconds
        step = self.period / n
        g = 0  # chunks scheduled so far, in due order
        self.latency, self.late = [], []
        counted_due = 0
        waiting = {}  # stream -> fed chunk ordinals not yet consumed (counted only)
        done_at = [self.consumed(i) for i in range(n)]
        window_started = False
        self.backlog = []  # (seconds into the window, rows buffered in all streams)
        next_sample = t0
        while True:
            now = clock()
            if not window_started and now >= t0:
                window_started = True
                window.begin()
                if self.tracer is not None:
                    self.tracer.clear()
            if window_started and now >= t1:
                window.end()
            if t0 <= now < t1 and now >= next_sample:
                self.backlog.append((now - t0, self.buffered_rows()))
                next_sample += 0.5
            if now >= t1 and not any(waiting.values()):
                break
            if now >= t1 + self.drain_timeout_s:
                break
            sent = False
            inside = traced and t0 <= now < t1
            with annotate(inside, "bench.submit"):
                while t_base + g * step <= now:
                    due = t_base + g * step
                    i = g % n
                    counted = t0 <= due < t1
                    if counted:
                        counted_due += 1
                        self.late.append(now - due)
                    if self.submit(i, self.chunk, due=due, counted=counted):
                        if counted:
                            waiting.setdefault(i, []).append(len(self.fed[i]) - 1)
                    elif counted:
                        self.refused += 1
                    g += 1
                    sent = True
            with annotate(inside, "bench.tick"):
                done = self.tick()
            t_done = clock()
            for i, k in done.items():
                done_at[i] += k
                w = waiting.get(i)
                while w and w[0] < done_at[i]:
                    due = self.fed[i][w.pop(0)][1]
                    self.latency.append(t_done - due)
            if not done and not sent:
                time.sleep(max(0.0, min(t_base + g * step - clock(), 0.001)))
        self.window = (t0, t1)
        self.counted = counted_due
        self.unconsumed = sum(len(w) for w in waiting.values())

    @property
    def attempted(self) -> int:
        return self.counted

    @property
    def failed(self) -> int:
        return self.refused + self.unconsumed

    def end_to_end(self) -> dict:
        return {"commit_p99_ms": 1e3 * stats.percentile(self.latency, 0.99)}

    def notes(self) -> dict:
        return {
            "chunks_counted": self.counted, "refused": self.refused,
            "unconsumed": self.unconsumed,
            "commit_p50_ms": 1e3 * stats.percentile(self.latency, 0.5),
            "generator_late_p99_ms": 1e3 * stats.percentile(self.late, 0.99),
            "generator_late_max_ms": 1e3 * max(self.late, default=0.0),
            "rate_rows_per_s": self.chunk / self.period,
            "buffered_rows_first_last_max": [self.backlog[0][1], self.backlog[-1][1],
                                             max(b for _, b in self.backlog)]
            if self.backlog else None,
        }
