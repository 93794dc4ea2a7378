"""Chip smoke test: the decoder's main path, compiled, on a TPU.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py               # block, long, stream, turbo: one chip
    python chip_smoke.py --four-chips  # sharded scheduler + seqparallel: four

It runs in this one process and starts no other.  Without a TPU it exits
nonzero before any phase runs: no phase runs on the CPU or interpreted.

Each phase drives a user entry point, ``decode()`` or ``StreamScheduler``,
at a full-width shape of ``configs/paper_viterbi.py`` on data drawn from a
fixed seed, and checks what comes out against the repository's own
oracles:

  block   K=7 soft decode() at the tpu_nasa_frame shape (B=1024, 1024 info
          bits): the planner must pick fused_packed, the compiled program
          must hold a Mosaic kernel (tpu_custom_call), and the bits must
          equal core.viterbi.viterbi_decode's bit for bit.
  long    K=7 soft decode() at the tpu_stream_64k shape (B=128, 65536 info
          bits), once as planned and once pinned to 16 tiles (the multi-tile
          seam merge); both bit-exact against the fused_packed decode.
  stream  128 K=7 soft streams fed chunk by chunk through a StreamScheduler
          (STREAM defaults: 64 slots, chunk 64) with depth >= the stream
          length, so each stream must equal its block decode bit for bit.
  turbo   the 3GPP TS 36.212 K=512 turbo code (LTE RSC constituents, QPP
          f1=31 f2=64) at B=128: one constituent BCJR pass through decode()
          within LLR_ATOL of kernels.ref.bcjr_llr_ref, and the 6-iteration
          decode of the golden workload of tests/golden/ber_turbo.json (the
          same seeded bits and noise, redrawn here) at 1.0 and 1.5 dB Eb/N0,
          whose BER must be at most the golden value plus that file's
          tolerance.

``--four-chips`` runs only the paths that span chips, each against its
one-device reference: the StreamScheduler sharded over a 4-device ``data``
mesh (``STREAM.n_slots_for(4)`` slots) against the unsharded scheduler, and
seqparallel on a 4-device ``model`` mesh at T=65536 against fused_packed.

Every phase prints one line: the plan's backend and why, compile seconds
(the first call less a steady call), steady seconds (median of three more
calls; one for the four-chip scheduler), and agreement with its oracle.  These are smoke observations, not
benchmark results.  The last line is one JSON object naming the device;
a phase that fails raises, so the exit code is nonzero and that line is
never printed.

Soft channel symbols of the convolutional phases are quantized to a 1/8
grid clipped at +-4 (a 7-bit soft-decision front end).  Every branch and
path metric is then exact in float32, and the branch metrics in bfloat16
too, so decoders that sum in different orders or precisions can be held to
bit-exact agreement: with unquantized symbols two paths whose metrics tie
to within rounding may rightly resolve either way.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.paper_viterbi import ARCH, STREAM  # noqa: E402
from repro.core import CODE_K7_NASA  # noqa: E402
from repro.core.viterbi import viterbi_decode  # noqa: E402
from repro.decode import CodecSpec, DecodeContext, decode  # noqa: E402
from repro.kernels.ref import bcjr_llr_ref  # noqa: E402
from repro.parallel.mesh import make_mesh  # noqa: E402
from repro.siso import RSC_K4_LTE, QPPInterleaver, TurboSpec  # noqa: E402
from repro.stream import StreamScheduler  # noqa: E402

SEED = 2026
K7_SOFT = CodecSpec(code=CODE_K7_NASA, metric="soft")
#: Eb/N0 of the convolutional phases: about 8% of hard decisions are wrong
#: on the channel, and the K=7 decoder corrects nearly all of them.
CONV_EBN0_DB = 3.0
Q_STEP, Q_CLIP = 1.0 / 8.0, 4.0
TURBO = TurboSpec(code=RSC_K4_LTE, interleaver=QPPInterleaver(512, 31, 64))
TURBO_EBN0S_DB = (1.0, 1.5)
#: Largest |LLR - reference| accepted from one BCJR pass, on channel LLRs of
#: unit scale: both sides are float32 max-log recursions over the same
#: operands, differing only in summation order.
LLR_ATOL = 1e-3
GOLDEN_TURBO = ROOT / "tests" / "golden" / "ber_turbo.json"
STEADY_REPS = 3
FOUR = 4


class SmokeFailure(RuntimeError):
    """A phase's output disagreed with its oracle or its contract."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, **fields) -> None:
    print(f"{phase}: " + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def shape(name: str):
    return next(s for s in ARCH.shapes if s.name == name)


def awgn(rng, coded, ebn0_db: float, rate: float, quantize: bool) -> np.ndarray:
    """BPSK (bit 0 -> +1) plus white Gaussian noise at ``ebn0_db``."""
    snr = 10.0 ** ((ebn0_db + 10.0 * np.log10(rate)) / 10.0)
    sym = 1.0 - 2.0 * np.asarray(coded, np.float32)
    y = sym + np.sqrt(1.0 / (2.0 * snr)) * rng.standard_normal(sym.shape)
    if quantize:
        y = np.clip(np.round(y / Q_STEP) * Q_STEP, -Q_CLIP, Q_CLIP)
    return y.astype(np.float32)


def conv_workload(rng, batch: int, n_info: int):
    """(info bits, received symbols) of K7_SOFT at CONV_EBN0_DB."""
    bits = rng.integers(0, 2, (batch, n_info), dtype=np.int32)
    coded = np.asarray(K7_SOFT.encode(jnp.asarray(bits)))
    rx = awgn(rng, coded, CONV_EBN0_DB, 1.0 / K7_SOFT.code.n_out, quantize=True)
    return bits, coded, rx


def hard_error_rate(coded, rx) -> float:
    return float(((rx < 0) != (np.asarray(coded) == 1)).mean())


def timed(run, ready=lambda out: out.bits, reps: int = STEADY_REPS):
    """(compile_s, steady_s, out): the first call compiles; steady is the
    median of ``reps`` more calls; compile is the first less steady."""
    t0 = time.perf_counter()
    out = run()
    jax.block_until_ready(ready(out))
    first = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(ready(run()))
        times.append(time.perf_counter() - t0)
    steady = statistics.median(times)
    return first - steady, steady, out


def agreement(got, want) -> str:
    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape, f"shape {got.shape} != oracle {want.shape}")
    n_diff = int((got != want).sum())
    check(n_diff == 0, f"{n_diff} of {want.size} bits differ from the oracle")
    return f"bit-exact({want.size}_bits)"


# ------------------------------- one chip ------------------------------- #


def phase_block(rng, *, batch: int, n_info: int, require_kernel: bool = True) -> None:
    bits, coded, rx = conv_workload(rng, batch, n_info)
    compile_s, steady_s, res = timed(lambda: decode(K7_SOFT, rx))
    plan = res.plan
    check(plan.backend == "fused_packed", f"planner picked {plan.backend!r}")
    if require_kernel:
        hlo = jax.jit(
            lambda r: plan.decoder.decode_received(plan.spec, r, ctx=plan.ctx).bits
        ).lower(jnp.asarray(rx)).compile().as_text()
        check("tpu_custom_call" in hlo, "no Mosaic kernel in the compiled decode")
    oracle = jax.jit(viterbi_decode, static_argnums=(0, 2))
    with jax.default_matmul_precision("highest"):
        want, _ = oracle(K7_SOFT.code, K7_SOFT.branch_metrics(jnp.asarray(rx)), True)
    report(
        "block", backend=plan.backend, reason=json.dumps(plan.reason),
        shape=f"B={batch},T={rx.shape[1]}", compile_s=f"{compile_s:.3f}",
        steady_s=f"{steady_s:.4f}",
        oracle="core.viterbi.viterbi_decode:" + agreement(res.bits, want),
        channel_err=f"{hard_error_rate(coded, rx):.4f}",
        ber=f"{float((np.asarray(res.info_bits) != bits).mean()):.2e}",
    )


def phase_long(rng, *, batch: int, n_info: int, tiles: int = 16) -> None:
    bits, _, rx = conv_workload(rng, batch, n_info)
    compile_s, steady_s, ref = timed(lambda: decode(K7_SOFT, rx, backend="fused_packed"))
    report(
        "long/reference", backend="fused_packed", shape=f"B={batch},T={rx.shape[1]}",
        compile_s=f"{compile_s:.3f}", steady_s=f"{steady_s:.4f}",
        ber=f"{float((np.asarray(ref.info_bits) != bits).mean()):.2e}",
    )
    for label, ctx in (("planned", None), (f"P={tiles}", DecodeContext(tiles=tiles))):
        compile_s, steady_s, res = timed(lambda ctx=ctx: decode(K7_SOFT, rx, ctx=ctx))
        if ctx is not None:
            check(res.plan.backend == "tiled", f"P={tiles} ran {res.plan.backend!r}")
            check(res.diagnostics["tiles"] == tiles, f"ran {res.diagnostics}")
        report(
            f"long/{label}", backend=res.plan.backend,
            tiles=res.diagnostics.get("tiles"), reason=json.dumps(res.plan.reason),
            compile_s=f"{compile_s:.3f}", steady_s=f"{steady_s:.4f}",
            oracle="fused_packed:" + agreement(res.bits, ref.bits),
        )


def feed_streams(sched: StreamScheduler, rx: np.ndarray) -> np.ndarray:
    """Open one stream per row of ``rx``, submit each chunk by chunk within
    its credit, close it after its last row, and tick until every stream
    has retired.  Returns the (n_streams, T) decoded bits."""
    ids = [f"s{i}" for i in range(rx.shape[0])]
    T = rx.shape[1]
    sent = [0] * len(ids)
    for sid in ids:
        sched.open_stream(sid)
    while sched.pending_work():
        for i, sid in enumerate(ids):
            if sent[i] < T:
                n = min(sched.credit(sid), T - sent[i])
                if n:
                    sched.submit_chunk(sid, rx[i, sent[i]:sent[i] + n],
                                       close=sent[i] + n == T)
                    sent[i] += n
        sched.step()
    return np.stack([sched.pop_result(sid)[0] for sid in ids])


def run_scheduler(rx, *, n_slots: int, depth=None, mesh=None):
    sched = StreamScheduler(
        K7_SOFT, n_slots=n_slots, chunk=STREAM.chunk, depth=depth,
        backend="fused_packed", inputs="received", mesh=mesh,
        mesh_axis=STREAM.mesh_axis,
    )
    return sched, feed_streams(sched, rx)


def phase_stream(rng, *, n_streams: int, n_info: int) -> None:
    _, _, rx = conv_workload(rng, n_streams, n_info)
    T = rx.shape[1]
    block = np.asarray(decode(K7_SOFT, rx, backend="fused_packed").bits)
    compile_s, steady_s, (sched, got) = timed(
        lambda: run_scheduler(rx, n_slots=STREAM.n_slots, depth=T),
        ready=lambda out: out[1],
    )
    report(
        "stream", backend="StreamScheduler/fused_packed", inputs="received",
        streams=n_streams, slots=sched.n_slots, chunk=sched.chunk,
        depth=sched.depth, steps=T, ticks=sched.stats.ticks,
        compile_s=f"{compile_s:.3f}", steady_s=f"{steady_s:.4f}",
        oracle="block_decode:" + agreement(got, block),
    )


def phase_turbo(rng, *, batch: int) -> None:
    code, N = TURBO.code, TURBO.block_len
    # one constituent max-log-MAP pass, routed by family to the bcjr backend
    rsc = CodecSpec(code=code, metric="soft", terminated=False)
    coded = np.asarray(rsc.encode(jnp.asarray(rng.integers(0, 2, (batch, N)))))
    rx = awgn(rng, coded, TURBO_EBN0S_DB[0], 1.0 / code.n_out, quantize=False)
    compile_s, steady_s, res = timed(lambda: decode(rsc, rx))
    feat = np.concatenate([rx, np.zeros(rx.shape[:2] + (1,), np.float32)], axis=-1)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(bcjr_llr_ref(code, jnp.asarray(feat.transpose(1, 2, 0)))).T
    err = float(np.abs(np.asarray(res.diagnostics["llr"]) - want).max())
    check(err <= LLR_ATOL, f"BCJR LLRs off the reference by {err} > {LLR_ATOL}")
    report(
        "turbo/bcjr", backend=res.plan.backend, reason=json.dumps(res.plan.reason),
        shape=f"B={batch},N={N}", compile_s=f"{compile_s:.3f}",
        steady_s=f"{steady_s:.4f}",
        oracle=f"kernels.ref.bcjr_llr_ref:max_abs_err={err:.2e}<={LLR_ATOL:g}",
    )
    # the golden workload of tests/test_golden_ber.py: info bits from
    # default_rng(seed), the noise of its i-th Eb/N0 point (sorted) from
    # default_rng([seed, 100 + i])
    golden = json.loads(GOLDEN_TURBO.read_text())
    seed = golden["seed"]
    check(golden["batch"] == batch, f"golden workload is B={golden['batch']}")
    points = sorted(float(p) for p in golden["ber"])
    bits = np.random.default_rng(seed).integers(0, 2, (batch, N))
    tcoded = np.asarray(TURBO.encode(jnp.asarray(bits, jnp.int32)))
    for ebn0 in TURBO_EBN0S_DB:
        noise = np.random.default_rng([seed, 100 + points.index(ebn0)])
        rx = awgn(noise, tcoded, ebn0, 1.0 / TURBO.n_streams, quantize=False)
        compile_s, steady_s, res = timed(lambda rx=rx: decode(TURBO, rx))
        wrong = np.asarray(res.bits) != bits
        ber = float(wrong.mean())
        limit = golden["ber"][f"{ebn0:g}"]["turbo"] + golden["tolerance"]
        check(ber <= limit, f"turbo BER {ber} at {ebn0} dB above {limit}")
        report(
            f"turbo/{ebn0:g}dB", backend=res.plan.backend,
            reason=json.dumps(res.plan.reason), shape=f"B={batch},N={N}",
            iterations=res.diagnostics["iterations"], compile_s=f"{compile_s:.3f}",
            steady_s=f"{steady_s:.4f}", oracle=f"golden:ber={ber:.2e}<={limit:g}",
            errors=int(wrong.sum()), blocks_in_error=int(wrong.any(axis=1).sum()),
        )


# ------------------------------ four chips ------------------------------ #


def phase_sharded_stream(rng, *, n_devices: int, n_info: int) -> None:
    n_slots = STREAM.n_slots_for(n_devices)
    # twice as many streams as slots: every slot is recycled once
    _, _, rx = conv_workload(rng, 2 * n_slots, n_info)
    mesh = make_mesh((n_devices,), (STREAM.mesh_axis,))
    # one steady run: each takes about half a minute, on four chips
    compile_s, steady_s, (sched, got) = timed(
        lambda: run_scheduler(rx, n_slots=n_slots, mesh=mesh),
        ready=lambda out: out[1], reps=1,
    )
    _, want = run_scheduler(rx, n_slots=STREAM.n_slots)
    report(
        "sharded_stream", backend="StreamScheduler/fused_packed",
        mesh=f"{STREAM.mesh_axis}={n_devices}", streams=rx.shape[0],
        slots=n_slots, depth=sched.depth, steps=rx.shape[1],
        compile_s=f"{compile_s:.3f}", steady_s=f"{steady_s:.4f}",
        oracle="single_device_scheduler:" + agreement(got, want),
    )


def phase_seqparallel(rng, *, n_devices: int, batch: int, n_steps: int) -> None:
    _, _, rx = conv_workload(rng, batch, n_steps - K7_SOFT.n_flush)
    mesh = make_mesh((n_devices,), ("model",))
    compile_s, steady_s, res = timed(lambda: decode(K7_SOFT, rx, mesh=mesh))
    check(res.plan.backend == "seqparallel", f"planner picked {res.plan.backend!r}")
    ref = decode(K7_SOFT, rx, backend="fused_packed")
    report(
        "seqparallel", backend=res.plan.backend, reason=json.dumps(res.plan.reason),
        shape=f"B={batch},T={rx.shape[1]}", compile_s=f"{compile_s:.3f}",
        steady_s=f"{steady_s:.4f}", oracle="fused_packed:" + agreement(res.bits, ref.bits),
    )


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip paths, on four chips")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(
            f"chip_smoke: needs a TPU, but JAX found platform {dev.platform!r} "
            f"({dev.device_kind}); nothing was run"
        )
    need = FOUR if args.four_chips else 1
    check(len(devices) >= need, f"--four-chips needs {need} chips, found {len(devices)}")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    def rng(i: int):
        return np.random.default_rng([SEED, i])

    if args.four_chips:
        phase_sharded_stream(rng(10), n_devices=FOUR, n_info=1024)
        phase_seqparallel(rng(11), n_devices=FOUR, batch=128, n_steps=65536)
    else:
        frame, long_ = shape("tpu_nasa_frame"), shape("tpu_stream_64k")
        phase_block(rng(0), batch=frame.batch, n_info=frame.n_info_bits)
        phase_long(rng(1), batch=long_.batch, n_info=long_.n_info_bits)
        phase_stream(rng(2), n_streams=2 * STREAM.n_slots, n_info=1024)
        phase_turbo(rng(3), batch=128)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
