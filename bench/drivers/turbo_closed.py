"""Closed-loop turbo decode: one caller sends ``decode()`` calls back to back.

Each call is a batch of ``batch`` LTE turbo code blocks of K bits with their
36.212 tails, (B, K + 4, 3) soft symbols, through the normal planner to the
``turbo`` backend.  A pool of ``pool`` inputs is made from the seed: the
information bits and the noise are drawn on the device, the 36.212 encoder
is the benchmark's own (``bench/reference_turbo.py``); the pool is cycled
through the window.

A call is timed from ``decode()`` to its bits being ready on the device.
After the window one call of each of the first ``check_calls`` pool inputs,
drawn from the seed (a reservoir of one per input), is compared, whole
batch, with the plain reference on its LLRs.  Traced, the calls carry a
``Tracer`` and the run returns the program's spans.
"""
from __future__ import annotations

import time

import numpy as np

from bench import channel, reference_turbo, roofline_turbo, stats
from bench.harness import annotate


def turbo_spec(code: dict, decoder: dict, block_size: int, f1: int, f2: int):
    """The program's TurboSpec for the configuration, with the program's own
    36.212 QPP row for ``block_size``, which has to equal the configuration's
    (f1, f2); or exit nonzero where the program has no 36.212 termination."""
    from repro.siso import RSCCode, TurboSpec

    if "tail" not in TurboSpec.__dataclass_fields__:
        raise SystemExit("bench: this program's TurboSpec has no 36.212 trellis "
                         "termination (no tail field); nothing was measured")
    from repro.siso.interleave import lte_qpp

    interleaver = lte_qpp(block_size)
    if (interleaver.f1, interleaver.f2) != (f1, f2):
        raise SystemExit(f"bench: the program's QPP row for K={block_size} is "
                         f"({interleaver.f1}, {interleaver.f2}), the "
                         f"configuration's ({f1}, {f2})")
    rsc = RSCCode(int(code["constraint"]), int(code["feedback_octal"], 8),
                  tuple(int(g, 8) for g in code["forward_octal"]))
    return TurboSpec(code=rsc, interleaver=interleaver,
                     iterations=int(decoder["iterations"]),
                     early_exit=bool(decoder["early_exit"]),
                     extrinsic_scale=float(decoder["extrinsic_scale"]),
                     tail=code["tail"])


def received(cfg: dict, trellis, perm, batch: int, k: int, key):
    """(info bits (B, K) int8 on the host, soft symbols (B, K + 4, 3)
    float32 on the device): BPSK over AWGN at the configuration's Eb/N0,
    Eb per information bit, symbols on the 1/8 grid clipped at +-4."""
    import jax
    import jax.numpy as jnp

    kb, kn = jax.random.split(key)
    bits = np.asarray(jax.random.bernoulli(kb, 0.5, (batch, k))).astype(np.int8)
    coded = jnp.asarray(reference_turbo.encode(trellis, perm, bits))
    sigma = channel.sigma(float(cfg["channel"]["ebn0_db"]), k / (3.0 * (k + 4)))
    y = 1.0 - 2.0 * coded + sigma * jax.random.normal(kn, coded.shape)
    y = jnp.clip(jnp.round(y / channel.Q_STEP) * channel.Q_STEP, -channel.Q_CLIP,
                 channel.Q_CLIP)
    return bits, y.astype(jnp.float32)


class Cell:
    def __init__(self, ctx: dict):
        import jax

        from repro.decode import decode

        self.ctx = ctx
        cfg, tr = ctx["config"], ctx["traffic"]
        code = cfg["code"]
        batch, k = int(tr["batch"]), int(code["block_size"])
        f1, f2 = int(code["qpp"]["f1"]), int(code["qpp"]["f2"])
        if ctx["rehearse"]:
            r = tr["rehearsal"]
            batch, k = min(batch, int(r["batch"])), int(r["block_size"])
            f1, f2 = int(r["qpp"]["f1"]), int(r["qpp"]["f2"])
        self.spec = turbo_spec(code, cfg["decoder"], k, f1, f2)
        self.decode = decode
        self.batch, self.k = batch, k
        self.trellis = reference_turbo.Trellis(code["feedback_octal"],
                                               code["forward_octal"][0])
        self.perm = reference_turbo.qpp(k, f1, f2)
        self.tracer = self.telemetry = None
        if ctx["traced"]:
            from repro.obs import Telemetry, Tracer

            self.tracer = Tracer("bench")
            self.telemetry = Telemetry(tracer=self.tracer)
        seed = ctx["seed"]
        self.sent, self.pool = zip(*(
            received(cfg, self.trellis, self.perm, batch, k, channel.key(seed, 200, j))
            for j in range(int(tr["pool"]))))
        jax.block_until_ready(self.pool)
        # warm-up: plan, trace, compile, then a call served from the caches;
        # every iteration count up to the budget runs the one compiled step
        for _ in range(2):
            jax.block_until_ready(self.call(self.pool[0]).bits)
        self.keep = int(tr["check_calls"])
        if not 0 < self.keep <= len(self.pool):
            raise ValueError(f"check_calls {self.keep} must be 1..pool ({len(self.pool)})")
        self.sample_rng = np.random.default_rng([seed, 2])
        self.calls = []  # (pool index, start, returned, ready, iterations)

    def call(self, x):
        return self.decode(self.spec, x, telemetry=self.telemetry)

    def measure(self, seconds: float, window) -> None:
        traced = window.traced
        n_pool = len(self.pool)
        kept = [None] * self.keep  # one reservoir per checked input: (index, llr, bits)
        seen = [0] * self.keep
        clock = time.perf_counter
        if self.tracer is not None:
            self.tracer.clear()
        window.begin()
        t0 = clock()
        self.t0 = t0
        i = 0
        while clock() - t0 < seconds:
            j = i % n_pool
            ts = clock()
            with annotate(traced, "bench.decode"):
                res = self.call(self.pool[j])
            tr = clock()
            with annotate(traced, "bench.wait"):
                res.bits.block_until_ready()
            te = clock()
            self.calls.append((j, ts, tr, te, int(res.diagnostics["iterations"])))
            if j < self.keep:
                seen[j] += 1
                if int(self.sample_rng.integers(seen[j])) == 0:
                    kept[j] = (j, res.diagnostics["llr"], res.bits)
            i += 1
        window.end()
        self.t_end = self.calls[-1][3]
        self.window = (t0, self.t_end)
        self.kept = [item for item in kept if item is not None]

    # ------------------------------ results ------------------------------ #

    @property
    def attempted(self) -> int:
        return len(self.calls)

    failed = 0

    def end_to_end(self) -> dict:
        """Information bits (K a block, tails excluded) over the window, and
        the nearest-rank p99 of the calls' times, entry to bits ready."""
        bits = self.batch * self.k * len(self.calls)
        lat = [te - ts for _, ts, _, te, _ in self.calls]
        return {"info_bits_per_s": bits / (self.t_end - self.t0),
                "call_p99_ms": 1e3 * stats.percentile(lat, 0.99)}

    def readings(self, peaks) -> dict:
        essential = sum(
            roofline_turbo.roofline_s(roofline_turbo.turbo_ops(self.batch, self.k, its),
                                      roofline_turbo.turbo_bytes(self.batch, self.k, its),
                                      peaks)[0]
            for *_, its in (self.calls if peaks is not None else ()))
        return {"essential_s": essential, "program_spans": self.program_spans()}

    def program_spans(self):
        """The program's spans that began inside the window, as (name,
        perf_counter_ns start, ns)."""
        if self.tracer is None:
            return []
        lo, hi = (int(t * 1e9) for t in self.window)
        return [s for s in self.tracer.spans() if lo <= s[1] < hi]

    def notes(self) -> dict:
        """Besides the counts: the p99 call, and the three slowest calls, each
        as [ms, ms of it before ``decode()`` returned, s into the window]."""
        its = [c[4] for c in self.calls]
        slow = sorted(self.calls, key=lambda c: c[3] - c[1])[-3:]
        out = {"calls": self.attempted, "batch": self.batch, "block_size": self.k,
               "plan": self.decode_plan(),
               "iterations_per_call": {str(n): its.count(n) for n in sorted(set(its))},
               "call_p99_ms": self.end_to_end()["call_p99_ms"],
               "slowest_calls": [[1e3 * (te - ts), 1e3 * (tr - ts), ts - self.t0]
                                 for _, ts, tr, te, _ in slow]}
        out.update(getattr(self, "errors", {}), checked_inputs=getattr(self, "checked_inputs", None))
        return out

    def decode_plan(self) -> str:
        from repro.decode import plan_decode

        return plan_decode(self.spec, (self.batch, self.spec.n_steps(self.k))).backend

    def release(self) -> None:
        """Free the program's inputs on the device; keep the sampled outputs,
        their inputs and their information bits on the host."""
        self.checked = [(np.asarray(self.pool[j]), self.sent[j], np.asarray(llr),
                         np.asarray(bits)) for j, llr, bits in self.kept]
        self.checked_inputs = [j for j, *_ in self.kept]
        self.pool = self.kept = None

    def _reference(self, y, precision="float32"):
        dec = self.ctx["config"]["decoder"]
        return reference_turbo.decode_split(
            self.trellis, self.perm, y, iterations=int(dec["iterations"]),
            scale=float(dec["extrinsic_scale"]), early_exit=bool(dec["early_exit"]),
            precision=precision)

    def use_control(self) -> None:
        """Put the control, the reference in bfloat16, in the program's place."""
        ys = np.concatenate([y for y, *_ in self.checked])
        llr, _, _ = self._reference(ys, "bfloat16")
        parts = np.split(llr, len(self.checked))
        self.checked = [(y, sent, p, (p < 0).astype(np.int32))
                        for (y, sent, _, _), p in zip(self.checked, parts)]

    def check(self) -> dict:
        """The sampled calls' LLRs against the reference's (one batch of all
        of them), and the errors against the transmitted bits, for notes."""
        limits = self.ctx["config"]["limits"]
        ys = np.concatenate([y for y, *_ in self.checked])
        want, n_ref, frozen = self._reference(ys)
        got = np.concatenate([llr for _, _, llr, _ in self.checked])
        decided = np.concatenate([b for *_, b in self.checked])
        err, wrong, self.bits_compared = reference_turbo.compare(
            got, decided, want, float(limits["bits_differing"]["margin"]))
        sent = np.concatenate([s for _, s, _, _ in self.checked])
        block_errors = (decided != sent).any(axis=1)
        self.errors = {"ber": float((decided != sent).mean()),
                       "bler": float(block_errors.mean()),
                       "reference_iterations": n_ref,
                       "reference_frozen": int(frozen.sum())}
        return {"llr_max_err": (err, float(limits["llr_max_err"]["value"])),
                "bits_differing": (wrong, int(limits["bits_differing"]["value"]))}
