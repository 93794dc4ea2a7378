"""Unified decode API: CodecSpec, DecoderRegistry, shape-aware planner, and
the backend-equivalence golden grid.

The golden grid is the acceptance gate for the registry re-home: every
registered Viterbi ("conv"-family) backend must agree bit-exactly with
core.viterbi.viterbi_decode over (code K3/K7 x punctured/unpunctured x
hard/soft x terminated/open).  The SISO "bcjr"/"turbo" entries are a
different code family (routed by spec.family, never by shape) and are gated
in tests/test_siso.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CODE_K3_STD, CODE_K7_NASA, viterbi_decode
from repro.core.puncture import PUNCTURE_2_3
from repro.decode import (
    LONG_BLOCK_T,
    CodecSpec,
    DecodeContext,
    DecodeRequest,
    DecoderRegistry,
    decode,
    get_decoder,
    list_decoders,
    plan_decode,
)
from repro.parallel.mesh import make_mesh

GRID_CODES = {"k3": CODE_K3_STD, "k7": CODE_K7_NASA}
EXPECTED_BACKENDS = (
    "bcjr", "fused_packed", "parallel", "seqparallel", "sequential",
    "sharded_stream", "streaming", "tiled", "turbo",
)
#: the Viterbi backends the bit-exact equivalence grid sweeps (same family,
#: same algebra); SISO backends decode a different family and are excluded.
CONV_BACKENDS = tuple(
    n for n in EXPECTED_BACKENDS if n not in ("bcjr", "turbo")
)


def _grid_tables(spec: CodecSpec, key, batch=2, n_info=30):
    """bits + branch-metric tables for one golden-grid cell."""
    bits = jax.random.bernoulli(key, 0.5, (batch, n_info)).astype(jnp.int32)
    coded = spec.encode(bits)
    if spec.soft:
        rx = spec.channel(jax.random.fold_in(key, 1), coded, snr_db=4.0)
    else:
        rx = spec.channel(jax.random.fold_in(key, 1), coded, flip_prob=0.03)
    return bits, spec.branch_metrics(rx)


# --------------------------------------------------------------------------- #
# CodecSpec                                                                    #
# --------------------------------------------------------------------------- #


def test_codec_spec_is_hashable_and_normalizes_patterns():
    a = CodecSpec(code=CODE_K3_STD, puncture=PUNCTURE_2_3)
    b = CodecSpec(code=CODE_K3_STD, puncture=((1, 1), (1, 0)))
    assert a == b and hash(a) == hash(b)
    assert isinstance(a.puncture, tuple)
    np.testing.assert_array_equal(a.puncture_array, PUNCTURE_2_3)
    assert {a: "ok"}[b] == "ok"


def test_codec_spec_validation():
    with pytest.raises(ValueError):
        CodecSpec(metric="llr2")
    with pytest.raises(ValueError):
        CodecSpec(puncture=((1, 1),))  # wrong n_out rows
    with pytest.raises(TypeError):
        CodecSpec.of("k3")


def test_codec_spec_flush_accounting(rng):
    spec = CodecSpec(code=CODE_K3_STD, terminated=True)
    open_spec = dataclasses.replace(spec, terminated=False)
    bits = jax.random.bernoulli(rng, 0.5, (2, 10)).astype(jnp.int32)
    assert spec.encode(bits).shape == (2, 12, 2)  # K-1 flush steps
    assert open_spec.encode(bits).shape == (2, 10, 2)
    assert spec.n_flush == 2 and open_spec.n_flush == 0
    assert spec.strip_flush(jnp.zeros((2, 12))).shape == (2, 10)
    assert open_spec.strip_flush(jnp.zeros((2, 10))).shape == (2, 10)


def test_codec_spec_soft_channel_needs_snr(rng):
    spec = CodecSpec(metric="soft")
    with pytest.raises(ValueError):
        spec.channel(rng, jnp.zeros((1, 4, 2)))


# --------------------------------------------------------------------------- #
# registry                                                                     #
# --------------------------------------------------------------------------- #


def test_all_builtin_backends_registered():
    assert list_decoders() == tuple(sorted(EXPECTED_BACKENDS))
    for name in EXPECTED_BACKENDS:
        dec = get_decoder(name)
        assert dec.name == name and dec.summary


def test_registry_rejects_duplicates_and_unknown():
    reg = DecoderRegistry()

    @reg.register("x", summary="first")
    def _x(spec, bm, *, ctx):
        return None

    with pytest.raises(KeyError):

        @reg.register("x")
        def _x2(spec, bm, *, ctx):
            return None

    with pytest.raises(KeyError, match="registered"):
        reg.get("nope")
    with pytest.raises(KeyError, match="fused_packed"):
        get_decoder("no-such-backend")
    with pytest.raises(KeyError):
        get_decoder("fused")  # the unpacked-survivor backend is gone


def test_capability_records():
    assert get_decoder("seqparallel").capabilities.requires_mesh
    assert get_decoder("streaming").capabilities.supports_streaming
    assert get_decoder("fused_packed").capabilities.max_states is not None
    caps = get_decoder("sharded_stream").capabilities
    assert caps.sharded_stream and caps.requires_mesh and caps.supports_streaming
    for name in CONV_BACKENDS:
        assert get_decoder(name).capabilities.family == "conv"
    assert get_decoder("bcjr").capabilities.family == "rsc"
    assert get_decoder("turbo").capabilities.family == "turbo"


# --------------------------------------------------------------------------- #
# golden grid: every backend == core.viterbi_decode, bit-exact                 #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("code_name", sorted(GRID_CODES))
@pytest.mark.parametrize("punctured", [False, True], ids=["unpunct", "punct23"])
@pytest.mark.parametrize("metric", ["hard", "soft"])
@pytest.mark.parametrize("terminated", [True, False], ids=["term", "open"])
def test_backend_equivalence_grid(code_name, punctured, metric, terminated,
                                  mesh11, rng):
    code = GRID_CODES[code_name]
    spec = CodecSpec(
        code=code,
        metric=metric,
        puncture=PUNCTURE_2_3 if punctured else None,
        terminated=terminated,
    )
    # deterministic per-cell fold (hash(spec) would vary with PYTHONHASHSEED)
    cell = (
        code.constraint * 8 + punctured * 4 + (metric == "soft") * 2 + terminated
    )
    key = jax.random.fold_in(rng, cell)
    _, bm = _grid_tables(spec, key)
    T = bm.shape[1]
    ref_bits, ref_metric = viterbi_decode(code, bm, terminated=terminated)

    for name in CONV_BACKENDS:
        caps = get_decoder(name).capabilities
        ctx = DecodeContext(
            mesh=mesh11 if caps.requires_mesh else None,
            # the packed stream ring shifts whole 32-step words
            chunk=32 if caps.supports_streaming else 16,
            stream_depth=T,  # window covers the block -> exactness regime
        )
        res = get_decoder(name)(spec, bm, ctx=ctx)
        np.testing.assert_array_equal(
            np.asarray(res.bits), np.asarray(ref_bits),
            err_msg=f"backend {name!r} diverged on {spec.describe()}",
        )
        np.testing.assert_allclose(
            np.asarray(res.path_metric), np.asarray(ref_metric), rtol=1e-5,
            err_msg=f"backend {name!r} metric diverged on {spec.describe()}",
        )
        assert res.spec == spec
        assert res.diagnostics["backend"] == name


# --------------------------------------------------------------------------- #
# planner                                                                      #
# --------------------------------------------------------------------------- #


def test_planner_picks_fused_packed_for_short_batched_blocks():
    plan = plan_decode(CodecSpec(), (32, 256))
    assert plan.backend == "fused_packed"
    assert "short batched block" in plan.reason


def test_planner_picks_tiled_for_long_blocks_without_mesh():
    plan = plan_decode(CodecSpec(), (4, LONG_BLOCK_T))
    assert plan.backend == "tiled"
    assert "no mesh" in plan.reason
    assert "long-conv-tiled" in plan.reason
    assert plan.ctx.tiles is not None and plan.ctx.tiles >= 1


def test_planner_names_fused_packed_when_one_tile_wins():
    """At K=7 the tile cost model picks P=1 for a 1030-step frame; one tile
    is the plain packed pipeline, so the plan names that backend."""
    plan = plan_decode(CodecSpec(code=CODE_K7_NASA, metric="soft"), (8, 1030))
    assert plan.backend == "fused_packed"
    assert "P=1" in plan.reason and "one tile" in plan.reason
    pinned = plan_decode(CodecSpec(code=CODE_K7_NASA), (8, 1030),
                         ctx=DecodeContext(tiles=1))
    assert pinned.backend == "tiled"


@pytest.mark.parametrize("name,shape,backend,tiles", [
    ("k3", (4, 1024), "tiled", 8),
    ("k3", (4, 2048), "tiled", 16),
    ("k7", (1024, 1030), "fused_packed", 1),  # configs tpu_nasa_frame
    ("k7", (1, 8198), "tiled", 32),
    ("k7", (128, 8198), "fused_packed", 1),
    ("k7", (128, 65542), "fused_packed", 1),  # configs tpu_stream_64k
])
def test_tile_picks_of_the_cost_model(name, shape, backend, tiles):
    """The tile count the roofline cost model picks for the planner's
    shapes (soft metrics at K=7): a change to the cost walker that moves a
    pick shows up here, not only on the chip."""
    code = GRID_CODES[name]
    spec = CodecSpec(code=code, metric="soft" if name == "k7" else "hard")
    plan = plan_decode(spec, shape)
    assert (plan.backend, plan.ctx.tiles) == (backend, tiles), plan.reason


def test_planner_honors_pinned_tile_count():
    ctx = DecodeContext(tiles=4)
    plan = plan_decode(CodecSpec(), (4, LONG_BLOCK_T), ctx=ctx)
    assert plan.backend == "tiled"
    assert plan.ctx.tiles == 4
    assert "pinned by caller" in plan.reason


def test_planner_picks_seqparallel_for_long_blocks_on_mesh(mesh11):
    plan = plan_decode(CodecSpec(), (4, 2 * LONG_BLOCK_T), mesh=mesh11)
    assert plan.backend == "seqparallel"


def test_planner_falls_back_when_mesh_lacks_axis():
    """A data-parallel-only mesh (no 'model' axis) must fall back to the
    single-device time-parallel route, not crash on the axis lookup."""
    mesh = make_mesh((1,), ("data",))
    plan = plan_decode(CodecSpec(), (4, 2 * LONG_BLOCK_T), mesh=mesh)
    assert plan.backend == "tiled"
    assert "lacks axis" in plan.reason


def test_windowed_decode_defaults_terminated_from_spec(rng):
    """viterbi_decode_windowed given an open CodecSpec must trace back from
    the best frontier state by default, not silently force state 0."""
    from repro.stream import viterbi_decode_windowed

    spec = CodecSpec(terminated=False)
    bits = jax.random.bernoulli(rng, 0.5, (2, 50)).astype(jnp.int32)
    bm = spec.branch_metrics(spec.encode(bits))  # noiseless open block
    ref_bits, ref_metric = viterbi_decode(spec.code, bm, terminated=False)
    got_bits, got_metric = viterbi_decode_windowed(spec, bm, depth=bm.shape[1])
    np.testing.assert_array_equal(np.asarray(got_bits), np.asarray(ref_bits))
    np.testing.assert_allclose(np.asarray(got_metric), np.asarray(ref_metric))


def test_planner_picks_streaming_for_session_context():
    plan = plan_decode(CodecSpec(), (1, 10_000_000),
                       ctx=DecodeContext(streaming=True, stream_depth=15))
    assert plan.backend == "streaming"


def test_plan_records_the_device_kind():
    plan = plan_decode(CodecSpec(), (4, 64))
    assert plan.device_kind == jax.devices()[0].device_kind


def test_tile_pick_surfaces_an_untraceable_candidate(monkeypatch):
    """A tiled candidate that fails to trace is an error, not a quietly
    different plan (the shape default used to stand in for it)."""
    from repro.decode import planner

    monkeypatch.setattr(planner.DecodePlan, "predicted_costs", lambda self: None)
    planner._pick_tiles.cache_clear()
    try:
        with pytest.raises(RuntimeError, match="did not trace"):
            plan_decode(CodecSpec(), (2, LONG_BLOCK_T + 2))
    finally:
        planner._pick_tiles.cache_clear()


class _StubMesh:
    """Planner-only mesh stand-in: plan_decode reads nothing but
    ``mesh.shape`` (a Mapping), so routing rules for multi-device meshes are
    unit-testable on the single-CPU suite (execution runs in
    tests/multidevice on real fake devices)."""

    def __init__(self, **axes):
        self.shape = dict(axes)


def test_planner_routes_multi_device_streaming_to_sharded_stream():
    ctx = DecodeContext(streaming=True, stream_depth=15)
    plan = plan_decode(CodecSpec(), (64, 4096), mesh=_StubMesh(data=8, model=1),
                       ctx=ctx)
    assert plan.backend == "sharded_stream"
    assert "data=8" in plan.reason


def test_planner_streaming_falls_back_when_trellis_exceeds_sharded_cap():
    """S above the fused VMEM cap must fall back to the uncapped streaming
    backend, not raise (regression: the sharded route skipped max_states)."""
    from repro.core import ConvCode
    from repro.decode.backends import FUSED_MAX_STATES

    big = CodecSpec(code=ConvCode(14, (0o32721, 0o26741)))
    assert big.code.n_states > FUSED_MAX_STATES
    ctx = DecodeContext(streaming=True, stream_depth=15)
    plan = plan_decode(big, (64, 4096), mesh=_StubMesh(data=8), ctx=ctx)
    assert plan.backend == "streaming"
    assert "exceeds" in plan.reason


def test_stream_defaults_weak_scaling_rule():
    """The config's one slot-table sizing rule: per-shard load x shards."""
    from repro.configs.paper_viterbi import STREAM

    assert STREAM.mesh_axis == "data"
    assert STREAM.n_slots_for(8) == 8 * STREAM.n_slots
    assert STREAM.n_slots_for(4, slots_per_shard=16) == 64
    assert STREAM.n_slots_for(1) == STREAM.n_slots


def test_planner_keeps_streaming_on_unit_data_axis(mesh11):
    """A streaming context with a 1-device data axis stays on the plain
    streaming backend — sharding only pays for itself past one device (the
    multi-device routing positive case runs in tests/multidevice)."""
    plan = plan_decode(CodecSpec(), (8, 4096), mesh=mesh11,
                       ctx=DecodeContext(streaming=True, stream_depth=15))
    assert plan.backend == "streaming"


def test_sharded_stream_backend_validation(mesh11):
    """Explicit sharded_stream override: refuses to run without a mesh, and
    refuses a mesh lacking the batch axis; a unit data axis is accepted."""
    with pytest.raises(ValueError, match="mesh"):
        plan_decode(CodecSpec(), (8, 64), backend="sharded_stream")
    model_only = make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="data"):
        plan_decode(CodecSpec(), (8, 64), backend="sharded_stream", mesh=model_only)
    plan = plan_decode(CodecSpec(), (8, 64), backend="sharded_stream", mesh=mesh11)
    assert plan.backend == "sharded_stream"
    # the packed ring needs whole 32-step words: no silent fallback
    bm = jnp.zeros((8, 64, CodecSpec().code.n_symbols), jnp.float32)
    with pytest.raises(ValueError, match="chunk % 32"):
        get_decoder("sharded_stream")(
            CodecSpec(), bm, ctx=DecodeContext(mesh=mesh11, chunk=16)
        )


def test_planner_override_and_validation(mesh11):
    plan = plan_decode(CodecSpec(), (4, 2 * LONG_BLOCK_T), backend="sequential")
    assert plan.backend == "sequential" and "override" in plan.reason
    with pytest.raises(KeyError):
        plan_decode(CodecSpec(), (4, 64), backend="no-such-backend")
    with pytest.raises(ValueError, match="mesh"):
        plan_decode(CodecSpec(), (4, 64), backend="seqparallel")  # no mesh given
    plan_decode(CodecSpec(), (4, 64), backend="seqparallel", mesh=mesh11)  # fine


def test_planner_is_deterministic_and_explains():
    a = plan_decode(CodecSpec(), (8, 512), ctx=DecodeContext(chunk=32))
    b = plan_decode(CodecSpec(), (8, 512), ctx=DecodeContext(chunk=32))
    assert a == b
    text = a.explain()
    assert a.backend in text and "why:" in text and "caps:" in text


def test_decode_one_shot_roundtrip(rng):
    spec = CodecSpec()
    bits = jax.random.bernoulli(rng, 0.5, (4, 48)).astype(jnp.int32)
    rx = spec.channel(jax.random.fold_in(rng, 1), spec.encode(bits), flip_prob=0.01)
    res = decode(DecodeRequest(spec, received=rx))
    assert res.plan is not None and res.plan.backend == "fused_packed"
    assert res.diagnostics["metrics"] == "in-kernel"  # raw rx skipped the bm table
    assert res.info_bits.shape == bits.shape
    assert float((res.info_bits != bits).mean()) < 0.05
    # shorthand form: decode(spec, rx)
    res2 = decode(spec, rx, backend="sequential")
    np.testing.assert_array_equal(np.asarray(res.bits), np.asarray(res2.bits))


# --------------------------------------------------------------------------- #
# shim removal: repro.decode is the only decode entry point                    #
# --------------------------------------------------------------------------- #


def test_viterbi_head_shim_is_gone():
    """The deprecated serve.viterbi_head module was removed (PR 7); the
    token-packing helpers live on in repro.serve.bits."""
    with pytest.raises(ImportError):
        import repro.serve.viterbi_head  # noqa: F401
    import repro.serve as serve

    assert not hasattr(serve, "ViterbiHead")
    assert callable(serve.tokens_to_bits) and callable(serve.bits_to_tokens)


def test_open_spec_plumbs_terminated_end_to_end(rng):
    """terminated=False flows spec -> encoder (no flush bits) -> backend ->
    traceback through the decode() surface."""
    spec = CodecSpec(terminated=False)
    bits = jax.random.bernoulli(rng, 0.5, (4, 40)).astype(jnp.int32)
    coded = spec.encode(bits)
    assert coded.shape == (4, 40, 2)  # no flush steps appended
    bm = spec.branch_metrics(coded)
    res = decode(spec, coded, backend="sequential")
    assert res.info_bits.shape == bits.shape  # nothing stripped when open
    ref_bits, ref_metric = viterbi_decode(CODE_K3_STD, bm, terminated=False)
    np.testing.assert_array_equal(np.asarray(res.bits), np.asarray(ref_bits))
    np.testing.assert_allclose(
        np.asarray(res.path_metric), np.asarray(ref_metric), rtol=1e-6
    )
    # terminated spec on the same noiseless block: flush stripped, exact
    term = CodecSpec(terminated=True)
    res_t = decode(term, term.encode(bits), backend="sequential")
    assert res_t.info_bits.shape == bits.shape
    np.testing.assert_array_equal(np.asarray(res_t.info_bits), np.asarray(bits))
