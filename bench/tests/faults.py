"""Faults planted under the timed path, for test_faults.py.

``python bench/tests/faults.py <fault> <run.py arguments>`` applies one
fault to the program in this process, then drives bench/run.py's main."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def answer_altered():
    """decode() returns one bit flipped, and the scheduler commits one bit
    flipped, where each is produced."""
    import repro.decode as d
    from repro.stream import scheduler as s

    real_decode, real_step = d.decode, s.StreamScheduler.step

    def decode(*a, **kw):
        res = real_decode(*a, **kw)
        res.bits = res.bits.at[0, 5].set(1 - res.bits[0, 5])
        return res

    def step(self):
        out = real_step(self)
        for sid in list(out)[:1]:
            out[sid] = out[sid].copy()
            out[sid][0] ^= 1
        return out

    d.decode, s.StreamScheduler.step = decode, step


def half_batch():
    """decode() decodes the first half of the batch and returns zeros for the
    rest; the scheduler's tick zeroes the bits of the upper half of its slots."""
    import jax.numpy as jnp

    import repro.decode as d
    from repro.stream import window as w

    real_decode, real_step, real_sharded = (d.decode, w.jitted_stream_step,
                                            w.make_sharded_stream_step)

    def decode(spec, x, **kw):
        half = max(1, x.shape[0] // 2)
        res = real_decode(spec, x[:half], **kw)
        res.bits = jnp.concatenate([res.bits, jnp.zeros_like(res.bits)])[: x.shape[0]]
        return res

    def halve(out):
        bits = out[1]
        return (out[0], bits.at[bits.shape[0] // 2:].set(0)) + tuple(out[2:])

    def jitted_stream_step(*a, **kw):
        f = real_step(*a, **kw)
        return lambda *x, **k: halve(f(*x, **k))

    def make_sharded_stream_step(*a, **kw):
        f = real_sharded(*a, **kw)
        return lambda *x, **k: halve(f(*x, **k))

    d.decode, w.jitted_stream_step, w.make_sharded_stream_step = (
        decode, jitted_stream_step, make_sharded_stream_step)


def state_unchanged():
    """The scheduler's tick returns its carried state unchanged."""
    from repro.stream import window as w

    real_step, real_sharded = w.jitted_stream_step, w.make_sharded_stream_step

    def jitted_stream_step(*a, **kw):
        f = real_step(*a, **kw)
        return lambda state, *x, **k: (state,) + tuple(f(state, *x, **k)[1:])

    def make_sharded_stream_step(*a, **kw):
        f = real_sharded(*a, **kw)
        return lambda arena, idx, active, state, *x: (
            (state,) + tuple(f(arena, idx, active, state, *x)[1:]))

    w.jitted_stream_step, w.make_sharded_stream_step = (
        jitted_stream_step, make_sharded_stream_step)


if __name__ == "__main__":
    globals()[sys.argv[1]]()
    from bench import run

    sys.exit(run.main(sys.argv[2:]))
