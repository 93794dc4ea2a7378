"""Compile rehearsal: every Pallas kernel compiled for one described TPU v5e.

Interpret mode (what every other test runs) accepts block shapes and
scratch budgets that Mosaic, the TPU kernel compiler, refuses.  These tests
lower each kernel with ``interpret=False`` against a v5e chip that is
described, not attached, and compile it with the TPU compiler installed
alongside jaxlib: nothing runs, so they say nothing about results or
times, only that the chip's compiler takes the kernel at 128 lanes.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every xdist
worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.trellis import CODE_K3_STD, CODE_K7_NASA
from repro.decode import CodecSpec
from repro.kernels import bcjr, minplus, survivors, texpand, viterbi_scan
from repro.kernels.metrics import fused_metric_plan
from repro.siso.rsc import RSC_K4_LTE

LANES = 128
B = 1024  # eight 128-lane blocks: the grid's batch axis has more than one step
T = 1030  # the tpu_nasa_frame block: 1024 info bits + 6 flush bits, K=7
CODES = {"S64": CODE_K7_NASA, "S4": CODE_K3_STD}
SCAN_VARIANTS = ("tables", "received", "tables_carry", "received_carry", "window")


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 host, with the persistent compile cache off: a
    compile for a described chip is written to the cache but cannot be
    read back without one."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies

        # skip only where the TPU compiler is not installed at all; any
        # other failure to describe the chip fails every test here
        pytest.importorskip("libtpu", reason="no TPU compiler (libtpu) installed")
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def chip(topo):
    """One described v5e chip."""
    return SingleDeviceSharding(topo.devices[0])


def _sds(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compile_has_kernel(fn, *args, **kwargs):
    """Lower + compile for the described chip; assert a Mosaic kernel is in
    the compiled program (an interpret-mode fallback would have none)."""
    compiled = fn.lower(*args, **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("variant", SCAN_VARIANTS)
@pytest.mark.parametrize("states", sorted(CODES))
def test_forward_scan_compiles(chip, states, variant):
    """The one forward entry, as each caller drives it: bm tables (F = M)
    or raw received symbols (F = the soft metric plan's features — the
    wifi_bcc34 block path), from state 0 or from carried metrics (the
    dvbs_r12 tick is ``received_carry``), and the tiled window."""
    code = CODES[states]
    S = code.n_states
    if variant.startswith("received"):
        F = fused_metric_plan(code, "soft", None).n_features
    else:
        F = code.n_symbols
    data = _sds(chip, (T, F, B))
    w = (_sds(chip, (S, F)), _sds(chip, (S, F)), _sds(chip, (S, 2)))
    kw = {}
    if variant.endswith("carry") or variant == "window":
        kw["pm0"] = _sds(chip, (S, B))
    if variant == "window":
        kw["window"] = (_sds(chip, (1, B), jnp.int32), _sds(chip, (1, B), jnp.int32))
    _compile_has_kernel(viterbi_scan.viterbi_scan, code, data, *w, LANES, False, **kw)


@pytest.mark.parametrize("states", sorted(CODES))
def test_packed_tracebacks_compile(chip, states):
    code = CODES[states]
    S = code.n_states
    W = survivors.n_words(T)
    packed = _sds(chip, (W, S, B), jnp.uint32)
    row = _sds(chip, (1, B), jnp.int32)
    _compile_has_kernel(
        survivors.traceback_packed, code, packed, row, T, LANES, False
    )
    _compile_has_kernel(
        survivors.traceback_packed_window, code, packed, row, row, row, LANES,
        False,
    )


def test_texpand_compiles(chip):
    code = CODE_K7_NASA
    _compile_has_kernel(
        texpand.texpand, code, _sds(chip, (code.n_states, B)),
        _sds(chip, (code.n_symbols, B)), LANES, False,
    )


def _bcjr_shapes(chip, n_mats):
    code = RSC_K4_LTE  # S=8, the LTE turbo constituent
    S, F = code.n_states, code.n_features
    half = n_mats // 2
    mats = tuple(_sds(chip, (S, S)) for _ in range(half)) + tuple(
        _sds(chip, (S, F)) for _ in range(half)
    )
    return S, F, mats


def test_bcjr_alpha_compiles(chip):
    S, F, mats = _bcjr_shapes(chip, 4)
    assert S == 8
    _compile_has_kernel(
        bcjr.bcjr_alpha_scan, mats, _sds(chip, (512, F, LANES)), LANES, False
    )


@pytest.mark.parametrize("terminated", [False, True])
def test_bcjr_beta_llr_compiles(chip, terminated):
    S, F, mats = _bcjr_shapes(chip, 8)
    compiled = _compile_has_kernel(
        bcjr.bcjr_beta_llr_scan, mats, _sds(chip, (512, S, LANES)),
        _sds(chip, (512, F, LANES)), terminated, LANES, False,
    )
    (out,) = jax.tree.leaves(compiled.out_info)
    assert out.shape == (512, 1, LANES)


def test_lte_turbo_iteration_compiles(chip):
    """One 36.212 turbo iteration (both SISO passes with their tails, the
    interleaver gathers, the freeze) at the lte_turbo.cb6144 shape: B=256
    code blocks of K=6144, (B, K + 4, 3) LLRs in."""
    from repro.siso import TurboSpec
    from repro.siso.interleave import lte_qpp
    from repro.siso.turbo import _iteration_fn

    spec = TurboSpec(RSC_K4_LTE, lte_qpp(6144), iterations=8, tail="36.212")
    Bt, K = 256, 6144
    compiled = _compile_has_kernel(
        _iteration_fn(spec, False), _sds(chip, (Bt, K + 4, 3)), _sds(chip, (Bt, K)),
        _sds(chip, (Bt, K), jnp.int32), _sds(chip, (Bt,), jnp.bool_),
    )
    assert compiled.as_text().count("tpu_custom_call") >= 4  # alpha, beta + LLR, twice
    # the alphas' HBM round trip, (K + 3, 8, 256) float32 per pass, bounds
    # the temporaries: some hundreds of MB, well inside 16 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_minplus_matmul_compiles(chip):
    S = CODE_K7_NASA.n_states
    # the (S, S) state-map composition at B=128 maps per launch, padded the
    # way ops.minplus_matmul_op pads: I and K blocks of S, 128-lane J blocks
    a = _sds(chip, (LANES, S, S))
    b = _sds(chip, (LANES, S, LANES))
    _compile_has_kernel(minplus.minplus_matmul, a, b, S, LANES, S, False)


def _seqparallel_on(topo, n_chips: int, T: int = 65536):
    """seqparallel at the tpu_stream_64k shape (B=128, K=7) compiled for
    ``n_chips`` described chips: (HLO text, temporary bytes per chip)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.parallel.collectives import viterbi_decode_seqparallel
    from repro.parallel.mesh import make_mesh

    mesh = make_mesh((n_chips,), ("model",), devices=topo.devices[:n_chips])
    spec = CodecSpec(code=CODE_K7_NASA, metric="soft")
    bm = jax.ShapeDtypeStruct(
        (LANES, T, CODE_K7_NASA.n_symbols), jnp.float32,
        sharding=NamedSharding(mesh, PartitionSpec()),
    )
    compiled = jax.jit(
        lambda x: viterbi_decode_seqparallel(spec, x, mesh, axis="model")[0]
    ).lower(bm).compile()
    return compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes


def test_seqparallel_keeps_survivors_sharded_on_four_chips(topo):
    """On four described chips only the seam maps cross chips, never inside
    a loop, and each chip holds a quarter of the survivors: its temporaries
    are at most a third of one chip's decoding the whole block."""
    hlo, temp4 = _seqparallel_on(topo, 4)
    gathers = [ln for ln in hlo.splitlines() if " all-gather(" in ln]
    assert len(gathers) == 2, gathers  # (S, S) metric maps, exit -> entry maps
    assert not [ln for ln in gathers if "/while/" in ln], gathers
    _, temp1 = _seqparallel_on(topo, 1)
    assert temp4 * 3 <= temp1, (temp4, temp1)
