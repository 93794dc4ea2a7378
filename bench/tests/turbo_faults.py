"""Faults planted under the turbo cell's timed path, for test_turbo_cell.py.

``python bench/tests/turbo_faults.py <fault> <run.py arguments>`` applies
one fault to the program in this process, then drives bench/run.py's main."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def tails_ignored():
    """The decoder reads the 12 tail bits as erasures (LLR 0)."""
    from repro.siso import turbo as t

    real = t.turbo_decode

    def turbo_decode(spec, llrs, **kw):
        return real(spec, llrs.at[:, spec.block_len:].set(0.0), **kw)

    t.turbo_decode = turbo_decode


def extrinsic_dropped():
    """Constituent 2 gets no a-priori input: constituent 1's extrinsic
    values are dropped (every second SISO pass an iteration traces)."""
    import jax.numpy as jnp

    from repro.siso import turbo as t

    real, calls = t.bcjr_llr_op, [0]

    def bcjr_llr_op(code, coded, apriori=None, **kw):
        calls[0] += 1
        if calls[0] % 2 == 0:
            apriori = jnp.zeros_like(apriori)
        return real(code, coded, apriori, **kw)

    t.bcjr_llr_op = bcjr_llr_op


if __name__ == "__main__":
    globals()[sys.argv[1]]()
    from bench import run

    sys.exit(run.main(sys.argv[2:]))
