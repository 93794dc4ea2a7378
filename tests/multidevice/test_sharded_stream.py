"""Sharded stream scheduler/session on a real (fake-8-device) mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CODE_K3_STD, bsc, encode, hard_branch_metrics, viterbi_decode
from repro.decode import CodecSpec, DecodeContext, get_decoder, plan_decode
from repro.stream import StreamScheduler, StreamSession

CODE = CODE_K3_STD


def _noisy_bm(key, batch, info_bits, flip=0.02):
    bits = jax.random.bernoulli(key, 0.5, (batch, info_bits)).astype(jnp.int32)
    coded = encode(CODE, bits, terminate=True)
    rx = bsc(jax.random.fold_in(key, 1), coded, flip)
    return bits, hard_branch_metrics(CODE, rx)


def _run_pair(mesh, streams, *, n_slots=8, chunk=16, depth=30, backend="scan",
              mesh_axis="data"):
    """Same submissions through a single-device and a sharded scheduler."""
    single = StreamScheduler(CODE, n_slots=n_slots, chunk=chunk, depth=depth,
                             backend=backend)
    shard = StreamScheduler(CODE, n_slots=n_slots, chunk=chunk, depth=depth,
                            backend=backend, mesh=mesh, mesh_axis=mesh_axis)
    for sid, bm in streams.items():
        single.submit(sid, bm)
        shard.submit(sid, bm)
    return single.run(), shard.run(), shard


@pytest.mark.parametrize("mesh_name", ["mesh81", "mesh42"])
def test_sharded_scheduler_bit_exact_with_single_device(mesh_name, request, rng):
    """Staggered lengths + slot turnover: the sharded scheduler commits the
    same bits and metrics as the single-device one on every stream."""
    mesh = request.getfixturevalue(mesh_name)
    streams = {}
    for i in range(20):
        _, bm = _noisy_bm(jax.random.fold_in(rng, i), 1, (92, 128, 60, 198)[i % 4])
        streams[f"s{i}"] = bm[0]
    out_single, out_shard, shard = _run_pair(mesh, streams)
    assert shard.stats.streams_finished == 20
    assert shard.stats.slot_claims == 20 > shard.n_slots  # slots recycled
    for sid in streams:
        np.testing.assert_array_equal(out_shard[sid][0], out_single[sid][0])
        assert abs(out_shard[sid][1] - out_single[sid][1]) < 1e-4


def test_sharded_packed_backend_bit_exact_with_block_decoder(mesh81, rng):
    """fused_packed hot loop under shard_map, depth >= T: bit-identical to
    the full-block Viterbi decode (ring + Pallas traceback per shard)."""
    sched = StreamScheduler(CODE, n_slots=8, chunk=32, depth=224,
                            backend="fused_packed", mesh=mesh81)
    refs = {}
    for i in range(12):
        _, bm = _noisy_bm(jax.random.fold_in(rng, i), 1, (94, 130, 62)[i % 3])
        rb, rm = viterbi_decode(CODE, bm)
        refs[f"s{i}"] = (np.asarray(rb[0]), float(rm[0]))
        sched.submit(f"s{i}", bm[0])
    out = sched.run()
    for sid, (rb, rm) in refs.items():
        np.testing.assert_array_equal(out[sid][0], rb)
        assert abs(out[sid][1] - rm) < 1e-3 * max(1.0, abs(rm))


def test_sharded_received_inputs_in_kernel_metrics(mesh81, rng):
    """inputs='received' sharded: raw symbols through the per-shard arena,
    branch metrics in-kernel — exact vs the table-fed block decode."""
    bits = jax.random.bernoulli(rng, 0.5, (4, 94)).astype(jnp.int32)
    coded = encode(CODE, bits, terminate=True)
    rx = bsc(jax.random.fold_in(rng, 1), coded, 0.03)
    ref_bits, _ = viterbi_decode(CODE, hard_branch_metrics(CODE, rx))
    sched = StreamScheduler(CODE, n_slots=8, chunk=32, depth=96,
                            backend="fused_packed", inputs="received", mesh=mesh81)
    for i in range(4):
        sched.submit(f"s{i}", rx[i])
    out = sched.run()
    for i in range(4):
        np.testing.assert_array_equal(out[f"s{i}"][0], np.asarray(ref_bits[i]))


def test_sharded_arena_compaction_with_live_sharded_slots(mesh81, rng):
    """Compaction rebuilds every shard's slab mid-run without disturbing
    live sharded streams (the single-device regression, on the mesh)."""
    sched = StreamScheduler(CODE, n_slots=8, chunk=16, depth=15, backend="scan",
                            mesh=mesh81)
    sched._compact_floor = 0
    sched._compact_ratio = 2
    refs = {}
    for i in range(24):
        _, bm = _noisy_bm(jax.random.fold_in(rng, i), 1, 62, 0.01)
        rb, _ = viterbi_decode(CODE, bm)
        refs[f"s{i}"] = np.asarray(rb[0])
        sched.submit(f"s{i}", bm[0])
    out = sched.run()
    assert sched.stats.arena_compactions > 0
    for sid, rb in refs.items():
        np.testing.assert_array_equal(out[sid][0], rb)


def test_sharded_state_layout_and_load_report(mesh81, rng):
    """The slot table is partitioned contiguously: state rows live on the
    shard owning the slot, and the collective load report agrees with the
    host-side bookkeeping."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    sched = StreamScheduler(CODE, n_slots=16, chunk=16, depth=30, backend="scan",
                            mesh=mesh81)
    assert sched.n_shards == 8 and sched.slots_per_shard == 2
    assert sched.state.pm.sharding.is_equivalent_to(
        NamedSharding(mesh81, P("data", None)), sched.state.pm.ndim
    )
    assert sched.state.ring.sharding.is_equivalent_to(
        NamedSharding(mesh81, P(None, "data", None)), sched.state.ring.ndim
    )
    for i in range(5):
        _, bm = _noisy_bm(jax.random.fold_in(rng, i), 1, 92)
        sched.submit(f"s{i}", bm[0])
    sched.step()
    report = sched.load_report()
    assert report["n_shards"] == 8
    assert report["active_total"] == sum(report["per_shard_active"]) == 5
    assert report["utilization"] == pytest.approx(5 / 16)
    sched.run()


def test_sharded_session_matches_single_device(mesh81, rng):
    """Mesh-sharded StreamSession (per-shard carried pytrees): same bits and
    metric as the unsharded session, chunk by chunk."""
    _, bm = _noisy_bm(rng, 8, 124, 0.02)
    ref_bits, ref_metric = viterbi_decode(CODE, bm)
    sess = StreamSession(CODE, batch=8, chunk=32, depth=128, backend="scan",
                         mesh=mesh81)
    bits, metric = sess.decode_all(bm)
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(ref_bits))
    np.testing.assert_allclose(np.asarray(metric), np.asarray(ref_metric), rtol=1e-5)


def test_session_batch_must_divide_over_shards(mesh81):
    with pytest.raises(ValueError, match="divide evenly"):
        StreamSession(CODE, batch=3, chunk=32, mesh=mesh81)
    with pytest.raises(ValueError, match="divide evenly"):
        StreamScheduler(CODE, n_slots=12, chunk=16, mesh=mesh81)


def test_planner_routes_streaming_mesh_to_sharded_stream(mesh81, mesh42):
    """ctx.streaming + a multi-device data axis -> sharded_stream; the same
    context without a mesh stays on the single-device streaming backend."""
    spec = CodecSpec(code=CODE)
    ctx = DecodeContext(streaming=True, chunk=32, stream_depth=128)
    assert plan_decode(spec, (8, 128), mesh=mesh81, ctx=ctx).backend == "sharded_stream"
    assert plan_decode(spec, (8, 128), mesh=mesh42, ctx=ctx).backend == "sharded_stream"
    assert plan_decode(spec, (8, 128), ctx=ctx).backend == "streaming"


def test_sharded_stream_backend_executes_bit_exact(mesh81, rng):
    """The registry backend end-to-end: (B, T, M) block through the sharded
    scheduler, bit-exact vs the sequential oracle at depth >= T."""
    _, bm = _noisy_bm(rng, 8, 126, 0.02)
    ref_bits, ref_metric = viterbi_decode(CODE, bm)
    res = get_decoder("sharded_stream")(
        CodecSpec(code=CODE), bm,
        ctx=DecodeContext(mesh=mesh81, streaming=True, chunk=32, stream_depth=128),
    )
    np.testing.assert_array_equal(np.asarray(res.bits), np.asarray(ref_bits))
    np.testing.assert_allclose(
        np.asarray(res.path_metric), np.asarray(ref_metric), rtol=1e-4
    )
    assert res.diagnostics["shards"] == 8


def test_sharded_online_chunk_fed_with_starvation(mesh81, rng):
    """Chunk-fed sharded scheduler: producer-fed streams with bursty arrival
    starve their slots across shards; results stay bit-exact with the block
    decoder and the per-shard queue accounting reduces coherently."""
    sched = StreamScheduler(CODE, n_slots=8, chunk=16, depth=300,
                            backend="scan", mesh=mesh81, max_buffered=64)
    refs = {}
    for i in range(10):
        _, bm = _noisy_bm(jax.random.fold_in(rng, i), 1, (92, 60, 128)[i % 3])
        rb, _ = viterbi_decode(CODE, bm)
        refs[f"s{i}"] = np.asarray(rb[0])
        table = np.asarray(bm[0])
        sched.open_stream(f"s{i}",
                          producer=iter([table[k : k + 29]
                                         for k in range(0, len(table), 29)]))
    report_seen = {"queued": 0, "starved": 0}
    while sched.pending_work():
        sched.step()
        report = sched.load_report()
        assert report["queued_rows_total"] == sum(report["per_shard_queued_rows"])
        report_seen["queued"] = max(report_seen["queued"], report["queued_rows_total"])
        report_seen["starved"] = max(report_seen["starved"], report["starved_active"])
    assert report_seen["queued"] > 0  # the accounting actually saw live queues
    for sid, rb in refs.items():
        np.testing.assert_array_equal(sched.results[sid][0], rb)


def test_sharded_submit_adapter_over_chunk_path(mesh81, rng):
    """The sharded scheduler's submit() rides the same chunk ingestion path
    (open + submit_chunk + close) — and stays bit-exact with it."""
    _, bm = _noisy_bm(rng, 8, 92)
    ref_bits, _ = viterbi_decode(CODE, bm)
    via_submit = StreamScheduler(CODE, n_slots=8, chunk=16, depth=128,
                                 backend="scan", mesh=mesh81)
    via_chunks = StreamScheduler(CODE, n_slots=8, chunk=16, depth=128,
                                 backend="scan", mesh=mesh81)
    for i in range(8):
        via_submit.submit(f"s{i}", bm[i])
        via_chunks.open_stream(f"s{i}",
                               max_buffered=max(via_chunks.max_buffered,
                                                bm.shape[1]))
        table = np.asarray(bm[i])
        via_chunks.submit_chunk(f"s{i}", table[:37])
        via_chunks.submit_chunk(f"s{i}", table[37:], close=True)
    out_a, out_b = via_submit.run(), via_chunks.run()
    for i in range(8):
        sid = f"s{i}"
        np.testing.assert_array_equal(out_a[sid][0], np.asarray(ref_bits[i]))
        np.testing.assert_array_equal(out_b[sid][0], out_a[sid][0])
        assert abs(out_a[sid][1] - out_b[sid][1]) < 1e-3


def test_sharded_staged_writes_match_single_device_and_keep_zero_prefix(mesh81, rng):
    """Pieces that do not divide the chunk, staged on the host and written
    into all eight slabs by one scatter at a time: the same bits as the
    single-device scheduler, and every shard's zero prefix stays zero."""
    from repro.stream import StreamBusy

    kw = dict(n_slots=8, chunk=16, depth=30, backend="scan")
    single = StreamScheduler(CODE, **kw)
    shard = StreamScheduler(CODE, mesh=mesh81, **kw)
    tables = {
        f"s{i}": np.asarray(
            _noisy_bm(jax.random.fold_in(rng, 500 + i), 1, (92, 61, 128, 45)[i % 4])[1][0]
        )
        for i in range(12)
    }
    sizes = (23, 7, 41, 5)
    for sched in (single, shard):
        cursor = dict.fromkeys(tables, 0)
        for sid in tables:
            sched.open_stream(sid)
        tick = 0
        while any(cursor[sid] < len(t) for sid, t in tables.items()):
            for i, (sid, table) in enumerate(tables.items()):
                c = cursor[sid]
                if c < len(table):
                    piece = table[c : c + sizes[(tick + i) % len(sizes)]]
                    try:
                        sched.submit_chunk(sid, piece, close=c + len(piece) == len(table))
                    except StreamBusy:
                        continue
                    cursor[sid] = c + len(piece)
            writes, finished = sched.stats.arena_writes, sched.stats.streams_finished
            sched.step()
            # one write before the gather, and at most one more per retiring
            # stream whose tail rows were still staged
            assert sched.stats.arena_writes - writes <= (
                1 + sched.stats.streams_finished - finished
            )
            tick += 1
        sched.run()
    assert shard.stats.arena_writes > 0
    for sid in tables:
        np.testing.assert_array_equal(shard.results[sid][0], single.results[sid][0])
        assert abs(shard.results[sid][1] - single.results[sid][1]) < 1e-4
    arena = np.asarray(shard._read_arena())
    for s, n in enumerate(shard._arena_len):
        assert not arena[s, : shard.chunk].any(), s
        assert not arena[s, n:].any(), s


def test_sharded_arena_write_holds_no_collective(mesh81):
    """Compiled for the mesh, the write of the staged rows fills each
    shard's slab on its own device (no collective) and updates the donated
    arena in place."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.stream.scheduler import _arena_writer

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh81, spec))

    compiled = _arena_writer(mesh81, "data").lower(
        sds((8, 4096, 2), jnp.float32, P("data", None, None)),
        sds((8,), jnp.int32, P("data")),
        sds((8, 512, 2), jnp.float32, P("data", None, None)),
    ).compile()
    hlo = compiled.as_text()
    for op in ("all-gather", "all-reduce", "all-to-all", "collective-permute",
               "reduce-scatter"):
        assert op not in hlo, op
    assert "input_output_alias" in hlo


def test_sharded_arena_compaction_holds_no_collective(mesh81):
    """Compiled for the mesh, the compaction gathers each shard's slab by
    its own index row on its own device (no collective) and rebuilds the
    donated arena in its own buffer."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.stream.scheduler import _arena_compactor

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh81, spec))

    compiled = _arena_compactor(mesh81, "data").lower(
        sds((8, 4096, 2), jnp.float32, P("data", None, None)),
        sds((8, 4096), jnp.int32, P("data", None)),
    ).compile()
    hlo = compiled.as_text()
    for op in ("all-gather", "all-reduce", "all-to-all", "collective-permute",
               "reduce-scatter"):
        assert op not in hlo, op
    assert "input_output_alias" in hlo
