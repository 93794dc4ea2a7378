"""Golden BER regression: decode quality must not drift across kernel PRs.

Every entry in the CODECS registry pins a seeded noise sweep for one codec
family into its own ``tests/golden/ber_<name>.json``:

  k7     the K=7 NASA Viterbi code decoded by every hot-path backend over a
         BSC flip sweep — catches kernels that stay shape-correct but decode
         the wrong path.
  turbo  the rate-1/3 LTE-constituent turbo code (K=4 RSC, N=512 QPP) vs the
         equivalent-rate K=7 soft Viterbi baseline over an Eb/N0 sweep — the
         SISO subsystem's acceptance gate: turbo must BEAT Viterbi at the
         1.0 dB waterfall point, not merely not drift.

Info bits and channel noise come from ``np.random.default_rng`` seeded per
point, never from ``jax.random``: the JAX PRNG's bit stream may change
between JAX releases, and a gate that moves with it cannot tell a decoder
regression from an upgrade.

Regenerate (only when a change is *supposed* to move BER, e.g. a new
truncation policy) with:

    PYTHONPATH=src python tests/test_golden_ber.py --regen [name ...]

No names = every registered codec.  Adding a codec = one registry entry
(filename + payload function); the drift gate and the --regen CLI pick it
up generically.
"""
import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CODE_K7_NASA
from repro.core.trellis import ConvCode
from repro.decode import CodecSpec, DecodeContext, decode, get_decoder
from repro.siso import QPPInterleaver, RSC_K4_LTE, TurboSpec, turbo_decode

GOLDEN_DIR = Path(__file__).resolve().parent / "golden"
TOLERANCE = 1e-3  # absolute BER drift that fails the gate
SEED = 2026

# ---------------------------- k7 Viterbi sweep ---------------------------- #

K7_BATCH = 16
K7_INFO_BITS = 96
K7_FLIPS = (0.02, 0.06, 0.11)  # clean floor -> waterfall knee -> lossy region
#: every decode path whose quality the file pins: the oracle, the (min,+)
#: scan, the packed Pallas pipeline on bm tables and on the received symbols
#: (in-kernel metrics, ``fused_packed_received``), the truncated-window
#: streamer, and the time-parallel tiled decoder (P=4 exact seams — must sit
#: exactly on the sequential curve).
K7_BACKENDS = (
    "sequential",
    "parallel",
    "fused_packed",
    "fused_packed_received",
    "streaming",
    "tiled",
)


def _point_rng(i: int) -> np.random.Generator:
    """The noise generator of sweep point ``i`` (independent per point)."""
    return np.random.default_rng([SEED, 100 + i])


def _bsc(rng: np.random.Generator, coded, flip: float) -> jnp.ndarray:
    """Binary symmetric channel: each coded bit flips with probability
    ``flip``."""
    c = np.asarray(coded, np.int32)
    return jnp.asarray(c ^ (rng.random(c.shape) < flip), jnp.int32)


def _awgn(rng: np.random.Generator, coded, snr_db: float) -> jnp.ndarray:
    """BPSK (bit 0 -> +1) plus white Gaussian noise at Es/N0 = ``snr_db``."""
    sym = 1.0 - 2.0 * np.asarray(coded, np.float32)
    sigma = np.sqrt(1.0 / (2.0 * 10.0 ** (snr_db / 10.0)))
    return jnp.asarray(sym + sigma * rng.standard_normal(sym.shape), jnp.float32)


def compute_k7_payload():
    """{flip: {backend: ber}} on the pinned seeded workload."""
    spec = CodecSpec(code=CODE_K7_NASA, metric="hard")
    truth = np.random.default_rng(SEED).integers(0, 2, (K7_BATCH, K7_INFO_BITS))
    coded = spec.encode(jnp.asarray(truth, jnp.int32))
    grid = {}
    for i, flip in enumerate(K7_FLIPS):
        rx = _bsc(_point_rng(i), coded, flip)
        bm = spec.branch_metrics(rx)
        row = {}
        for name in K7_BACKENDS:
            if name == "fused_packed_received":  # decode() from the symbols
                res = decode(spec, rx, backend="fused_packed")
            else:
                ctx = DecodeContext(
                    chunk=32 if name == "streaming" else 16,  # whole words
                    tiles=4 if name == "tiled" else None,
                )
                res = get_decoder(name)(spec, bm, ctx=ctx)
            row[name] = float((np.asarray(res.info_bits) != truth).mean())
        grid[f"{flip:g}"] = row
    return {
        "code": "k7_nasa",
        "metric": "hard",
        "seed": SEED,
        "batch": K7_BATCH,
        "info_bits": K7_INFO_BITS,
        "tolerance": TOLERANCE,
        "ber": grid,
    }


# ------------------------- turbo vs Viterbi sweep ------------------------- #

TURBO_SPEC = TurboSpec(code=RSC_K4_LTE, interleaver=QPPInterleaver(512, 31, 64))
TURBO_BASELINE = CodecSpec(
    code=ConvCode(7, (0o133, 0o171, 0o165)), metric="soft", terminated=False
)
TURBO_RATE = 1.0 / 3.0
#: 128 blocks of 512 bits: enough that the 1.0 dB point, where a few blocks
#: in a hundred fail, estimates the BER rather than reading 0 by luck.
#: chip_smoke.py redraws this workload (seed, bits, per-point noise) and
#: holds the chip's turbo decode to these values.
TURBO_BATCH = 128
TURBO_EBN0S = (0.5, 1.0, 1.5)
#: the Eb/N0 point where the iterative gain must show: turbo strictly
#: below the equivalent-rate one-shot Viterbi baseline.
TURBO_GATE_EBN0 = 1.0


def compute_turbo_payload():
    """{ebn0: {"turbo": ber, "viterbi": ber}} — same info bits, same rate,
    independent AWGN draws per codec (both channels carry 3 coded bits per
    info bit at snr = ebn0 + 10*log10(1/3))."""
    rng = np.random.default_rng(SEED)
    bits = jnp.asarray(
        rng.integers(0, 2, size=(TURBO_BATCH, TURBO_SPEC.block_len)), jnp.int32
    )
    tcoded = TURBO_SPEC.encode(bits)
    ccoded = TURBO_BASELINE.encode(bits)
    grid = {}
    for i, ebn0 in enumerate(TURBO_EBN0S):
        snr_db = float(ebn0 + 10 * np.log10(TURBO_RATE))
        noise = _point_rng(i)
        rx_t = _awgn(noise, tcoded, snr_db)
        res_t = turbo_decode(
            TURBO_SPEC, TURBO_SPEC.channel_llrs(rx_t, snr_db=snr_db)
        )
        rx_c = _awgn(noise, ccoded, snr_db)
        res_c = decode(TURBO_BASELINE, rx_c)
        grid[f"{ebn0:g}"] = {
            "turbo": float((res_t.bits != bits).mean()),
            "viterbi": float((res_c.info_bits != bits).mean()),
        }
    return {
        "code": "turbo_k4_qpp512 vs k7_soft",
        "seed": SEED,
        "batch": TURBO_BATCH,
        "block_len": TURBO_SPEC.block_len,
        "rate": TURBO_RATE,
        "iterations": TURBO_SPEC.iterations,
        "extrinsic_scale": TURBO_SPEC.extrinsic_scale,
        "gate_ebn0_db": TURBO_GATE_EBN0,
        "tolerance": TOLERANCE,
        "ber": grid,
    }


# ------------------------------- registry -------------------------------- #

#: name -> (golden filename, payload function).  --regen and the drift gate
#: below iterate this; a new codec family is one entry here.
CODECS = {
    "k7": ("ber_k7.json", compute_k7_payload),
    "turbo": ("ber_turbo.json", compute_turbo_payload),
}


def _golden_path(name: str) -> Path:
    return GOLDEN_DIR / CODECS[name][0]


def _load_golden(name: str) -> dict:
    path = _golden_path(name)
    assert path.exists(), (
        f"{path} missing — regenerate with "
        f"PYTHONPATH=src python tests/test_golden_ber.py --regen {name}"
    )
    return json.loads(path.read_text())


@pytest.mark.parametrize("name", sorted(CODECS))
def test_golden_ber_no_drift(name):
    golden = _load_golden(name)
    assert golden["seed"] == SEED
    grid = CODECS[name][1]()["ber"]
    for point, row in golden["ber"].items():
        for series, want in row.items():
            got = grid[point][series]
            assert abs(got - want) <= TOLERANCE, (
                f"BER drift for {name}/{series} at {point}: "
                f"golden {want:.6f} vs current {got:.6f} "
                f"(|diff| > {TOLERANCE:g})"
            )


def test_golden_covers_every_pinned_backend():
    golden = _load_golden("k7")
    for flip in K7_FLIPS:
        assert set(golden["ber"][f"{flip:g}"]) == set(K7_BACKENDS)


def test_golden_turbo_beats_viterbi_at_gate():
    """The SISO acceptance gate: at the pinned 1.0 dB waterfall point the
    6-iteration turbo decode must be strictly better than the
    equivalent-rate soft Viterbi baseline — in the golden file AND in the
    recomputed grid (a stale-but-passing golden file cannot hide a
    regression)."""
    golden = _load_golden("turbo")
    point = f"{TURBO_GATE_EBN0:g}"
    assert golden["ber"][point]["turbo"] < golden["ber"][point]["viterbi"]
    grid = compute_turbo_payload()["ber"]
    assert grid[point]["turbo"] < grid[point]["viterbi"], grid[point]


def _regen(names):
    GOLDEN_DIR.mkdir(parents=True, exist_ok=True)
    for name in names:
        path = _golden_path(name)
        payload = CODECS[name][1]()
        path.write_text(json.dumps(payload, indent=1) + "\n")
        print(f"wrote {path}")
        print(json.dumps(payload["ber"], indent=1))


if __name__ == "__main__":
    import sys

    argv = sys.argv[1:]
    if "--regen" not in argv:
        sys.exit("refusing to overwrite golden files: pass --regen [name ...]")
    picked = [a for a in argv if a != "--regen"]
    unknown = set(picked) - set(CODECS)
    if unknown:
        sys.exit(f"unknown codec(s) {sorted(unknown)}; have {sorted(CODECS)}")
    _regen(picked or sorted(CODECS))
