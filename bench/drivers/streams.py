"""What the stream drivers share: a ``StreamScheduler`` built from the
configuration, per-stream symbol pools, the bookkeeping of what was fed and
committed, and the comparison with the plain windowed reference.

Each stream's symbols are its pool (``pool_rows`` rows of one continuous
code stream, made on the device from the seed) read cyclically a chunk at a
time; a chunk the scheduler refuses is dropped by its source, so the
stream's input is the chunks it accepted, in order.
"""
from __future__ import annotations

import time

import numpy as np

from bench import channel, reference
from bench.drivers.common import codec_spec, reference_code


class StreamCell:
    def __init__(self, ctx: dict):
        import jax

        from repro.obs import Telemetry, Tracer
        from repro.stream import StreamBusy, StreamScheduler

        self.ctx = ctx
        cfg, tr = ctx["config"], ctx["traffic"]
        sc = cfg["scheduler"]
        chips = len(ctx["devices"])
        self.n_streams = int(tr["streams"])
        pool_rows = int(tr["pool_rows"])
        slots = int(sc["slots_per_chip"]) * chips
        if ctx["rehearse"]:
            r = tr["rehearsal"]
            self.n_streams = min(self.n_streams, int(r["streams"]))
            pool_rows = min(pool_rows, int(r["pool_rows"]))
            slots = min(slots, int(r["slots_per_chip"]) * chips)
        self.chunk = int(sc["chunk"])
        self.StreamBusy = StreamBusy
        self.tracer = None
        telemetry = None
        if ctx["traced"]:
            t_before = time.perf_counter_ns()
            self.tracer = Tracer("bench")
            self.tracer_origin = (t_before + time.perf_counter_ns()) // 2
            telemetry = Telemetry(tracer=self.tracer)
        mesh = None
        if chips > 1:
            from repro.parallel.mesh import make_mesh

            mesh = make_mesh((chips,), (sc["mesh_axis"],), devices=ctx["devices"])
        self.sched = StreamScheduler(
            codec_spec(cfg), n_slots=slots, chunk=self.chunk, depth=sc["depth"],
            backend=sc["backend"], inputs=sc["inputs"],
            max_buffered=int(sc["max_buffered"]), mesh=mesh,
            mesh_axis=sc["mesh_axis"], telemetry=telemetry,
        )
        self.depth = int(sc["committed_depth"])
        if self.sched.depth != self.depth:
            raise RuntimeError(f"the scheduler's depth is {self.sched.depth}, the "
                               f"configuration states {self.depth}")
        _, y = channel.received(cfg["code"], cfg["channel"], self.n_streams, pool_rows,
                                False, channel.key(ctx["seed"], 100))
        self.pool = np.asarray(jax.device_get(y))  # host rows, as a receiver has them
        self.ids = [f"s{i}" for i in range(self.n_streams)]
        self.cursor = [0] * self.n_streams  # next pool row of each stream
        self.fed = [[] for _ in self.ids]  # (pool row, due or None, counted)
        self.bits = [[] for _ in self.ids]  # committed bits, in order
        self.n_committed = [0] * self.n_streams
        self.refused = 0
        for sid in self.ids:
            self.sched.open_stream(sid)

    # ----------------------------- feeding ----------------------------- #

    def submit(self, i: int, n_rows: int, due=None, counted=False) -> bool:
        """Send stream ``i``'s next ``n_rows`` rows (whole chunks); False if
        the scheduler refused them (the source drops them)."""
        L = self.pool.shape[1]
        starts = [(self.cursor[i] + k) % L for k in range(0, n_rows, self.chunk)]
        rows = np.concatenate([self.pool[i, s:s + self.chunk] for s in starts])
        self.cursor[i] += n_rows
        try:
            self.sched.submit_chunk(self.ids[i], rows)
        except self.StreamBusy:
            return False
        self.fed[i].extend((s, due, counted) for s in starts)
        return True

    def tick(self):
        """One scheduler tick; returns {stream index: chunks newly consumed}."""
        emitted = self.sched.step()
        done = {}
        for sid, b in emitted.items():
            i = int(sid[1:])
            before = self.consumed(i)
            self.bits[i].append(b)
            self.n_committed[i] += len(b)
            done[i] = self.consumed(i) - before
        return done

    def consumed(self, i: int) -> int:
        """Chunks of stream ``i`` the kernel has consumed, as its commits
        show: every tick past the first ``depth`` steps commits one chunk."""
        n = self.n_committed[i]
        return (n + self.depth) // self.chunk if n else 0

    def saturate_until_compacted(self) -> None:
        """Set-up: top every stream up to its credit and tick, as the
        saturated window does, until the scheduler has compacted its arena
        once.  Under that traffic the arena stops growing before its first
        compaction, and every compaction finds the same fill, so the first
        one compiles every shape that later ones use."""
        start = self.sched.stats.arena_compactions
        while self.sched.stats.arena_compactions == start:
            self.top_up()
            self.tick()

    def drain(self) -> None:
        while any(self.sched.credit(s) <= self.sched.max_buffered - self.chunk
                  for s in self.ids):
            self.tick()

    def buffered_rows(self) -> int:
        return sum(self.sched.max_buffered - self.sched.credit(s) for s in self.ids)

    def top_up(self, counted: bool = False) -> int:
        """Fill every stream to its credit; returns the chunks sent."""
        sent = 0
        for i, sid in enumerate(self.ids):
            credit = self.sched.credit(sid) // self.chunk * self.chunk
            if credit and self.submit(i, credit, counted=counted):
                sent += credit // self.chunk
        return sent

    # ----------------------------- results ----------------------------- #

    def readings(self, peaks) -> dict:
        return {"program_spans": self.program_spans()}

    def program_spans(self):
        """The scheduler's tick-phase spans that began inside the window, as
        (name, perf_counter_ns start, ns)."""
        if self.tracer is None:
            return []
        lo, hi = (int(t * 1e9) for t in self.window)
        spans = [(e["name"], self.tracer_origin + int(e["ts"] * 1e3), int(e["dur"] * 1e3))
                 for e in self.tracer.chrome_events() if e["ph"] == "X"]
        return [s for s in spans if lo <= s[1] < hi]

    def release(self) -> None:
        """Free the scheduler (its device state); keep the fed rows and the
        committed bits on the host."""
        self.sched = None
        self.got = [np.concatenate(b) if b else np.zeros(0, np.int32) for b in self.bits]
        self.bits = None

    def _inputs(self):
        """Each stream's fed rows, extended with its pool's continuation to
        a common length: (B, T, n) and T, a multiple of the chunk."""
        T = max(self.consumed(i) for i in range(self.n_streams)) * self.chunk
        L = self.pool.shape[1]
        out = np.empty((self.n_streams, T, self.pool.shape[2]), np.float32)
        for i in range(self.n_streams):
            starts = [s for s, _, _ in self.fed[i]][: T // self.chunk]
            nxt = self.cursor[i]
            while len(starts) < T // self.chunk:
                starts.append(nxt % L)
                nxt += self.chunk
            for k, s in enumerate(starts):
                out[i, k * self.chunk:(k + 1) * self.chunk] = self.pool[i, s:s + self.chunk]
        return out, T

    def use_control(self) -> None:
        """Put the control, the reference in bfloat16, in the program's place."""
        y, _ = self._inputs()
        ctl = reference.decode_windowed(reference_code(self.ctx["config"]), y,
                                        chunk=self.chunk, depth=self.depth,
                                        precision="bfloat16")
        self.got = [ctl[i, :len(g)] for i, g in enumerate(self.got)]

    def check(self) -> dict:
        """Committed bits that differ from the windowed reference's."""
        y, T = self._inputs()
        want = reference.decode_windowed(reference_code(self.ctx["config"]), y,
                                         chunk=self.chunk, depth=self.depth)
        wrong = sum(int((g != want[i, :len(g)]).sum()) for i, g in enumerate(self.got))
        self.bits_compared = sum(len(g) for g in self.got)
        return {"bits_differing": (wrong, int(self.ctx["config"]["limits"]["bits_differing"]))}
