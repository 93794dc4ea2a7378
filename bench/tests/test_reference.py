"""The plain reference against a second witness (the program's own XLA
oracle, core.viterbi), and the control against the reference.

Run by hand:  JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import channel, reference
from bench.drivers.common import codec_spec, reference_code
from bench.harness import load_cell


def _data(cell, batch, n_info, terminate, seed):
    cfg = load_cell(cell)["config"]
    bits, y = channel.received(cfg["code"], cfg["channel"], batch, n_info, terminate,
                               channel.key(seed, 0))
    return cfg, np.asarray(bits), np.asarray(y)


@pytest.mark.parametrize("cell", ["wifi_bcc34.imix", "dvbs_r12.saturate_x1"])
def test_encoder_matches_the_reference_encoder(cell):
    cfg = load_cell(cell)["config"]
    term = bool(cfg["code"]["terminated"])
    bits, y = channel.received(cfg["code"], {**cfg["channel"], "ebn0_db": 60.0}, 4, 200,
                               term, channel.key(1, 0))
    coded = reference.encode(reference_code(cfg), np.asarray(bits), term)
    mask = np.asarray(y) != 0  # erasures
    assert np.array_equal((np.asarray(y) < 0)[mask], (coded == 1)[mask])


def test_block_reference_matches_the_program_oracle():
    from repro.core.viterbi import viterbi_decode

    cfg, bits, y = _data("wifi_bcc34.imix", 6, 16 + 8 * 76, True, 2**33 + 5)
    spec = codec_spec(cfg)
    want, _ = viterbi_decode(spec.code, spec.branch_metrics(jnp.asarray(y)), True)
    got = reference.decode_block(reference_code(cfg), y)
    assert np.array_equal(got, np.asarray(want))
    assert (got[:, :bits.shape[1]] != bits).mean() < 0.01  # it decodes, too


def test_windowed_reference_matches_the_program_scheduler():
    from repro.stream import StreamScheduler

    cfg, _, y = _data("dvbs_r12.saturate_x1", 3, 64 * 12, False, 7)
    sc = cfg["scheduler"]
    sched = StreamScheduler(codec_spec(cfg), n_slots=4, chunk=64, depth=sc["depth"],
                            backend="fused_packed", inputs="received")
    got = [[] for _ in range(3)]
    for i in range(3):
        sched.open_stream(str(i))
    for k in range(12):
        for i in range(3):
            sched.submit_chunk(str(i), y[i, 64 * k:64 * (k + 1)])
        for sid, b in sched.step().items():
            got[int(sid)].append(b)
    want = reference.decode_windowed(reference_code(cfg), y, chunk=64,
                                     depth=sc["committed_depth"])
    for i in range(3):
        g = np.concatenate(got[i])
        assert len(g) == 64 * 12 - sc["committed_depth"]
        assert np.array_equal(g, want[i])


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_control_differs_from_the_reference(seed):
    """The bfloat16 control decodes other bits than the exact reference,
    at a size a test run holds (the cells' own sizes are read on the chip)."""
    cfg, _, y = _data("wifi_bcc34.imix", 16, 16 + 8 * 612, True, seed)
    code = reference_code(cfg)
    exact = reference.decode_block(code, y)
    control = reference.decode_block(code, y, "bfloat16")
    assert int((exact != control).sum()) > 0
