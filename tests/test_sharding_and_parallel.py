"""Sharding rules, shard_map collectives (seq-parallel Viterbi, flash
decode), pipeline stage, roofline parsers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.models import common as cm
from repro.parallel.mesh import make_mesh


# --------------------------------------------------------------------------- #
# resolve_axes                                                                 #
# --------------------------------------------------------------------------- #


def test_resolve_axes_divisibility(mesh11):
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = dict(cm.DEFAULT_RULES)
    # kv_heads=2 under model=1: divisible, sharded (trivially)
    spec = cm.resolve_axes(mesh, rules, (8, 2, 64), ("batch", "kv_heads", None))
    assert spec == P(("data",), ("model",)) or spec == P("data", "model")


def test_resolve_axes_never_reuses_axis():
    mesh = make_mesh((1,), ("model",))
    rules = {"a": "model", "b": "model"}
    spec = cm.resolve_axes(mesh, rules, (4, 4), ("a", "b"))
    # second use of 'model' must drop, not duplicate
    flat = [s for s in spec if s is not None]
    assert len(flat) <= 1


def test_resolve_axes_non_dividing_drops():
    mesh = make_mesh((1,), ("model",))
    # size 3 divides 1 trivially; simulate non-division via fake rule chain
    spec = cm.resolve_axes(mesh, {"x": "missing_axis"}, (3,), ("x",))
    assert spec == P()


def test_fsdp_rules_shard_embed_dim(mesh11):
    from repro.parallel.sharding import make_rules
    from repro.configs.base import PartitionConfig

    r = make_rules(PartitionConfig(fsdp=True))
    assert r["embed"] == "data"
    r0 = make_rules(PartitionConfig(fsdp=False))
    assert r0["embed"] is None


# --------------------------------------------------------------------------- #
# sequence-parallel Viterbi (shard_map)                                        #
# --------------------------------------------------------------------------- #


def test_collectives_sum_across_shards(mesh11):
    """The sharded scheduler's scalar reduction: per-shard rows psum to the
    mesh-global total (size-1 data axis here; tests/multidevice covers 8)."""
    from repro.parallel.collectives import mesh_axis_size, sum_across_shards

    assert mesh_axis_size(mesh11, "data") == 1
    assert mesh_axis_size(mesh11, "nope") == 0
    assert mesh_axis_size(None, "data") == 0
    total = sum_across_shards(mesh11, "data", jnp.asarray([[3, 5]]))
    np.testing.assert_array_equal(np.asarray(total), [3, 5])


def test_seqparallel_viterbi_matches_sequential(mesh11, rng):
    from repro.core import CODE_K3_STD, bsc, encode, hard_branch_metrics, viterbi_decode
    from repro.parallel.collectives import viterbi_decode_seqparallel

    code = CODE_K3_STD
    bits = jax.random.bernoulli(rng, 0.5, (4, 62)).astype(jnp.int32)
    coded = encode(code, bits, terminate=True)
    rx = bsc(jax.random.fold_in(rng, 1), coded, 0.05)
    bm = hard_branch_metrics(code, rx)
    d_ref, m_ref = viterbi_decode(code, bm)
    with mesh11:
        d_sp, m_sp = viterbi_decode_seqparallel(code, bm, mesh11)
    np.testing.assert_allclose(np.asarray(m_ref), np.asarray(m_sp), rtol=1e-5)
    assert (np.asarray(d_ref) == np.asarray(d_sp)).all()


_FOUR_SHARD_SEQPARALLEL = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import CODE_K3_STD, CODE_K7_NASA, bsc, encode, hard_branch_metrics, viterbi_decode
from repro.parallel.collectives import viterbi_decode_seqparallel
from repro.parallel.mesh import make_mesh
mesh = make_mesh((4,), ("model",))
key = jax.random.PRNGKey(0)
for code in (CODE_K3_STD, CODE_K7_NASA):
    for terminated in (True, False):
        # flip 0.2 on hard decisions: many tied paths, resolved alike
        bits = jax.random.bernoulli(key, 0.5, (6, 128 - terminated * (code.constraint - 1)))
        coded = encode(code, bits.astype(jnp.int32), terminate=terminated)
        bm = hard_branch_metrics(code, bsc(jax.random.fold_in(key, 1), coded, 0.2))
        want, m_want = viterbi_decode(code, bm, terminated=terminated)
        got, m_got = viterbi_decode_seqparallel(code, bm, mesh, terminated=terminated)
        assert got.sharding.spec == jax.sharding.PartitionSpec(None, "model")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(m_got), np.asarray(m_want))
print("ok")
"""


def test_seqparallel_on_four_shards_matches_sequential():
    """Four host devices in a fresh process: each shard traces its own
    stretch of the survivors from the seam state the exit -> entry maps
    give it, and the bits equal the sequential decoder's, ties included;
    the bits come back time-sharded, never gathered."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    env = dict(
        os.environ, PYTHONPATH=str(repo / "src"), JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    out = subprocess.run(
        [sys.executable, "-c", _FOUR_SHARD_SEQPARALLEL],
        capture_output=True, text=True, timeout=600, env=env, cwd=str(repo),
    )
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr[-3000:]


# --------------------------------------------------------------------------- #
# pipeline                                                                     #
# --------------------------------------------------------------------------- #


def test_pipeline_single_stage_identity(rng):
    from repro.parallel.pipeline import bubble_fraction, pipeline_apply

    mesh = make_mesh((1,), ("stage",))
    W = jax.random.normal(rng, (1, 8, 8))

    def layer(w, h):
        return jnp.tanh(h @ w)

    x = jax.random.normal(jax.random.fold_in(rng, 1), (3, 4, 8))  # 3 microbatches
    out = pipeline_apply(layer, W, x, mesh=mesh, axis="stage")
    ref = jnp.stack([layer(W[0], x[i]) for i in range(3)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)
    assert bubble_fraction(4, 12) == pytest.approx(3 / 15)


# --------------------------------------------------------------------------- #
# roofline parsers                                                             #
# --------------------------------------------------------------------------- #


def test_roofline_peaks_are_keyed_by_device_kind():
    from repro.roofline.analysis import hardware, roofline_terms

    v5e = hardware("TPU v5 lite")
    assert (v5e.peak_flops, v5e.hbm_bw) == (197e12, 819e9)
    terms = roofline_terms(197e12, 819e9 * 2, 0.0, v5e)
    assert terms["dominant"] == "memory_s" and terms["bound_s"] == 2.0
    # a chip without published peaks is an error, never the v5e default
    with pytest.raises(ValueError, match="no published peaks"):
        hardware(jax.devices()[0].device_kind)


def test_explicit_axis_meshes_are_rejected_with_a_pointer():
    """jax.make_mesh's default Explicit axes break the sharded scan and the
    slot scatter; the entry points refuse them up front."""
    from repro.core import CODE_K3_STD
    from repro.parallel.collectives import viterbi_decode_seqparallel
    from repro.stream import StreamScheduler

    explicit = jax.make_mesh((1,), ("model",))
    bm = jnp.zeros((1, 8, 4), jnp.float32)
    with pytest.raises(ValueError, match="make_mesh"):
        viterbi_decode_seqparallel(CODE_K3_STD, bm, explicit)
    with pytest.raises(ValueError, match="make_mesh"):
        StreamScheduler(CODE_K3_STD, n_slots=1, mesh=jax.make_mesh((1,), ("data",)))
    assert make_mesh((1,), ("model",)).axis_types == (jax.sharding.AxisType.Auto,)


def test_collective_parser_shapes():
    from repro.roofline.analysis import _shape_bytes, collective_bytes

    assert _shape_bytes("f32[8,128]") == 8 * 128 * 4
    assert _shape_bytes("(bf16[4,4], f32[2])") == 4 * 4 * 2 + 2 * 4
    hlo = """
  %ag = f32[16,256]{1,0} all-gather(f32[1,256]{1,0} %x), replica_groups={}
  %ar = bf16[8,8]{1,0} all-reduce(bf16[8,8]{1,0} %y), to_apply=%add
"""
    out = collective_bytes(hlo)
    assert out["per_kind"]["all-gather"] == 16 * 256 * 4
    assert out["per_kind"]["all-reduce"] == 8 * 8 * 2
    assert out["counts"]["all-gather"] == 1


def test_while_trip_parser():
    from repro.roofline.hlo_loops import collective_bytes_with_trips

    hlo = """HloModule test

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %ar = f32[4]{0} all-reduce(%gte), to_apply=%add
}

%cond (p: (s32[], f32[4])) -> pred[] {
  %c = s32[] constant(7)
  ROOT %cmp = pred[] compare(%i, %c), direction=LT
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %w = (s32[], f32[4]) while(%init), condition=%cond, body=%body
  %ag = f32[8]{0} all-gather(%gte2), replica_groups={}
}
"""
    out = collective_bytes_with_trips(hlo)
    assert out["trip_corrected"]
    # all-reduce: 4*4 bytes * 2 (AR convention) * 7 trips; all-gather: 8*4 once
    assert out["per_kind"]["all-reduce"] == 4 * 4 * 2 * 7
    assert out["per_kind"]["all-gather"] == 8 * 4


def test_jaxpr_cost_counts_scan_trips():
    from repro.roofline.jaxpr_cost import count_fn_costs

    W = jnp.zeros((32, 32))

    def fn(x):
        def body(h, _):
            return jnp.tanh(h @ W), None

        h, _ = jax.lax.scan(body, x, None, length=10)
        return h

    out = count_fn_costs(fn, jnp.zeros((4, 32)))
    dot_flops = 2 * 4 * 32 * 32
    assert out["flops"] >= 10 * dot_flops  # 10 trips counted
    assert out["flops"] < 12 * dot_flops + 10 * 4 * 32 * 5  # no gross overcount


def test_jaxpr_cost_counts_remat():
    from repro.roofline.jaxpr_cost import count_fn_costs

    W = jnp.zeros((16, 16))

    def loss(x):
        f = jax.checkpoint(lambda h: jnp.tanh(h @ W))
        return f(f(x)).sum()

    plain = count_fn_costs(jax.grad(loss), jnp.zeros((2, 16)))
    # remat recompute present: > fwd(2 dots) + bwd(4 dots)
    assert plain["flops"] > 6 * 2 * 2 * 16 * 16


def test_model_flops_conventions():
    from repro.configs.base import SHAPES, get_arch
    from repro.roofline.analysis import model_flops

    bundle = get_arch("qwen3_4b")
    mf_train = model_flops(bundle.model, SHAPES["train_4k"])
    mf_decode = model_flops(bundle.model, SHAPES["decode_32k"])
    n = bundle.model.param_count()["active"]
    assert mf_train == pytest.approx(6 * n * 4096 * 256)
    assert mf_decode == pytest.approx(2 * n * 128)
