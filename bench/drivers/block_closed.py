"""Closed-loop block decode: one caller sends ``decode()`` calls back to back.

Each call is a batch of ``batch`` terminated frames of one length class.
The classes' calls follow their ``weight``s: every cycle holds ``weight``
calls of each class in an order drawn from the seed, so every seed gives the
same set of sizes.  Each class has a pool of ``pool`` inputs, made on the
device from the seed, cycled through the window.

A call is timed from ``decode()`` to its bits being ready on the device.
After the window a sample of calls drawn from the seed (reservoir, per
class) is compared, whole batch, with the plain reference.
"""
from __future__ import annotations

import time

import numpy as np

from bench import channel, reference, roofline, stats
from bench.drivers.common import codec_spec, reference_code
from bench.harness import annotate


class Cell:
    def __init__(self, ctx: dict):
        import jax

        from repro.decode import decode

        self.ctx = ctx
        cfg, tr = ctx["config"], ctx["traffic"]
        self.decode = decode
        self.spec = codec_spec(cfg)
        self.ref_code = reference_code(cfg)
        frame = cfg["frame"]
        batch = int(tr["batch"])
        octets = [int(c["psdu_octets"]) for c in tr["classes"]]
        if ctx["rehearse"]:
            r = tr["rehearsal"]
            batch = min(batch, int(r["batch"]))
            octets = [max(1, o // int(r["octet_divisor"])) for o in octets]
        self.batch = batch
        # information bits per frame (SERVICE + PSDU) and trellis steps
        self.info_bits = [int(frame["service_bits"]) + 8 * o for o in octets]
        self.steps = [n + int(frame["tail_bits"]) for n in self.info_bits]
        seed = ctx["seed"]
        rng = np.random.default_rng([seed, 1])
        cycle = [c for c, cl in enumerate(tr["classes"]) for _ in range(int(cl["weight"]))]
        self.order = list(rng.permutation(cycle))
        self.pool = [  # received symbols (B, T, n) on the device, per class
            [channel.received(cfg["code"], cfg["channel"], batch, n, True,
                              channel.key(seed, c, j))[1]
             for j in range(int(tr["pool"]))]
            for c, n in enumerate(self.info_bits)
        ]
        jax.block_until_ready(self.pool)
        # warm-up: every class's shape, twice (plan, trace, compile, then a
        # call served from the caches)
        for c in range(len(self.steps)):
            for _ in range(2):
                jax.block_until_ready(self.decode(self.spec, self.pool[c][0]).bits)
        self.keep = int(tr["check_calls_per_class"])
        self.sample_rng = np.random.default_rng([seed, 2])
        self.calls = []  # (class, pool index, start, returned, ready)

    def measure(self, seconds: float, window) -> None:
        traced = window.traced
        seen = [0] * len(self.steps)
        kept = [[] for _ in self.steps]  # reservoir: (pool index, bits)
        n_pool = len(self.pool[0])
        i = 0
        clock = time.perf_counter
        window.begin()
        t0 = clock()
        self.t0 = t0
        while clock() - t0 < seconds:
            c = self.order[i % len(self.order)]
            j = (i // len(self.order)) % n_pool
            x = self.pool[c][j]
            ts = clock()
            with annotate(traced, "bench.decode"):
                res = self.decode(self.spec, x)
            tr = clock()
            with annotate(traced, "bench.wait"):
                res.bits.block_until_ready()
            te = clock()
            self.calls.append((c, j, ts, tr, te))
            seen[c] += 1
            if len(kept[c]) < self.keep:
                kept[c].append((j, res.bits))
            else:
                r = int(self.sample_rng.integers(seen[c]))
                if r < self.keep:
                    kept[c][r] = (j, res.bits)
            i += 1
        window.end()
        self.t_end = self.calls[-1][4]
        self.window = (t0, self.t_end)
        self.kept = kept

    # ------------------------------ results ------------------------------ #

    @property
    def attempted(self) -> int:
        return len(self.calls)

    failed = 0

    def end_to_end(self) -> dict:
        bits = sum(self.batch * self.info_bits[c] for c, *_ in self.calls)
        lat = [te - ts for _, _, ts, _, te in self.calls]
        return {
            "info_bits_per_s": bits / (self.t_end - self.t0),
            "call_p99_ms": 1e3 * stats.percentile(lat, 0.99),
        }

    def readings(self, peaks) -> dict:
        code = self.ref_code
        n_out = len(code.polys)
        essential, binds = 0.0, set()
        for c, *_ in (self.calls if peaks is not None else ()):
            t, bound = roofline.roofline_s(
                roofline.viterbi_ops(self.batch, self.steps[c], code.constraint, n_out),
                roofline.viterbi_bytes(self.batch, self.steps[c], n_out),
                peaks,
            )
            essential += t
            binds.add(bound)
        return {
            "host_call_s": [tr - ts for _, _, ts, tr, _ in self.calls],
            "essential_s": essential,
            "roofline_bound": sorted(binds),
        }

    def notes(self) -> dict:
        lat = sorted(te - ts for _, _, ts, _, te in self.calls)
        between = [b[2] - a[4] for a, b in zip(self.calls, self.calls[1:])]
        slow = [t for t in lat if t > 0.05]
        return {"calls": self.attempted, "steps": self.steps, "batch": self.batch,
                "plans": [self.decode_plan(c) for c in range(len(self.steps))],
                "slowest_calls_ms": [1e3 * t for t in lat[-3:]],
                "calls_over_50ms": [len(slow), sum(slow)],
                "between_calls_s": sum(between),
                "longest_between_calls_ms": 1e3 * max(between, default=0.0)}

    def decode_plan(self, c: int) -> str:
        from repro.decode import plan_decode

        plan = plan_decode(self.spec, (self.batch, self.steps[c]))
        return f"T={self.steps[c]}:{plan.backend}"

    def release(self) -> None:
        """Free the program's inputs on the device; keep the sampled outputs
        and their inputs on the host."""
        self.checked = []
        for c, kept in enumerate(self.kept):
            for j, bits in kept:
                self.checked.append((c, np.asarray(self.pool[c][j]), np.asarray(bits)))
        self.pool = None
        self.kept = None

    def use_control(self) -> None:
        """Put the control, the reference in bfloat16, in the program's place."""
        self.checked = [(c, rx, reference.decode_block(self.ref_code, rx, "bfloat16"))
                        for c, rx, _ in self.checked]

    def check(self) -> dict:
        """Bits of the sampled calls that differ from the reference."""
        wrong = 0
        self.bits_compared = 0
        for _c, rx, got in self.checked:
            want = reference.decode_block(self.ref_code, rx)
            wrong += int((got != want).sum())
            self.bits_compared += want.size
        return {"bits_differing": (wrong, int(self.ctx["config"]["limits"]["bits_differing"]))}
