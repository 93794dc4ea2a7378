"""shard_map collectives: the sequence-parallel Viterbi decoder.

Beyond-paper distribution of the paper's technique: the Viterbi forward pass
is a product in the (min,+) semiring, which is associative, so a length-T
decode can be split across the ``model`` mesh axis:

  1. each shard runs the fused local forward over its T/n chunk, producing a
     chunk transfer matrix (S, S) — all shards in parallel;
  2. one all-gather of the (small: S×S) chunk matrices;
  3. every shard computes the exclusive (min,+) prefix locally (n is the mesh
     axis size, so this is O(n·S^3) scalar work — negligible);
  4. each shard re-scans its chunk from the now-known boundary metrics to
     recover backpointers, which stay on the shard that made them;
  5. each shard traces all S exit states back through its chunk at once,
     giving a map exit state -> entry state; one all-gather of these (small:
     S ints) maps lets every shard chain them back from the final state to
     its own exit state, and trace its chunk's bits from there.

Communication = n · (S² floats + S ints) per batch element — independent of
T — and each shard holds only its own T/n steps of survivors.  This is
the TPU-mesh analogue of the paper's "execute the custom instruction in
parallel to other independent instructions" future-work note.

The seam calculus here (per-chunk state maps composed with (min,+) prefixes)
is the shared algebra of kernels/minplus.py; the single-device analogue of
this decoder is the ``tiled`` backend (kernels/ops.viterbi_decode_tiled_op),
which folds the tiles into one Pallas launch's lane axis instead of across
a mesh — prefer it when no model-axis mesh is available.
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.acs import acs_step
from repro.core.trellis import NEG_UNREACHABLE, ConvCode
from repro.core.viterbi import _traceback
from repro.decode.spec import CodecSpec
from repro.kernels.minplus import compose_maps, identity_map
from repro.parallel.mesh import check_auto_mesh, shard_map


def _local_transfer_and_bps(code: ConvCode, bm_local: jnp.ndarray):
    """Per-shard chunk pass.  bm_local: (B, C, M).
    Returns transfer matrix (B, S, S): [i, s] = best metric entering in state
    i and leaving in state s."""
    S = code.n_states
    B = bm_local.shape[0]
    pm0 = jnp.where(jnp.eye(S, dtype=bool), 0.0, NEG_UNREACHABLE)
    pm0 = jnp.broadcast_to(pm0, (B, S, S))

    def step(pm, bm_t):  # pm: (B, S_init, S); bm_t: (B, M)
        new_pm, _ = acs_step(code, pm, bm_t[:, None, :])
        return jnp.minimum(new_pm, NEG_UNREACHABLE), None

    mat, _ = jax.lax.scan(step, pm0, bm_local.swapaxes(0, 1))
    return mat


def _entry_state_of_exit(code: ConvCode, bps: jnp.ndarray) -> jnp.ndarray:
    """Trace every exit state back through a chunk's backpointers at once.
    bps: (C, B, S).  Returns (B, S): [b, s] = the state the surviving path
    into exit state s had when it entered the chunk — the same steps as
    core.viterbi._traceback, on S lanes and keeping only the last state."""
    half = code.n_states // 2
    B, S = bps.shape[1], bps.shape[2]

    def step(s, bp_t):  # s: (B, S) current state of each lane
        v = s & (half - 1) if half > 1 else jnp.zeros_like(s)
        # bp_t[b, s[b, k]] as a one-hot select: a gather here makes the
        # compiler keep a second, lane-padded copy of the survivors
        hit = s[:, :, None] == jnp.arange(S, dtype=jnp.int32)
        j = jnp.where(hit, bp_t[:, None, :].astype(jnp.int32), 0).sum(axis=-1)
        return 2 * v + j, None

    lanes = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    entry, _ = jax.lax.scan(step, lanes, bps, reverse=True)
    return entry


def viterbi_decode_seqparallel(
    code: Union[ConvCode, CodecSpec],
    bm_tables: jnp.ndarray,
    mesh,
    axis: str = "model",
    terminated: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sequence-parallel Viterbi.  bm_tables: (B, T, M) with T divisible by
    the mesh axis size.  Matches the sequential decoder's metric exactly.
    ``code`` may be a bare ConvCode or a CodecSpec (whose ``terminated`` flag
    is the default when the ``terminated`` argument is omitted)."""
    check_auto_mesh(mesh)
    spec = CodecSpec.of(code)
    code = spec.code
    if terminated is None:
        terminated = spec.terminated
    n = mesh.shape[axis]
    B, T, M = bm_tables.shape
    S = code.n_states
    assert T % n == 0, (T, n)

    def shard_fn(bm_loc):  # (B, T/n, M) on each shard
        idx = jax.lax.axis_index(axis)
        mat = _local_transfer_and_bps(code, bm_loc)  # (B, S, S)
        mats = jax.lax.all_gather(mat, axis)  # (n, B, S, S)

        # exclusive (min,+) prefix over shards, computed redundantly per
        # shard — the shared state-map algebra of kernels/minplus.py
        eye = identity_map(S, (B,))

        def pref_step(acc, m):
            return compose_maps(acc, m), acc  # emit the *exclusive* prefix

        total, excl = jax.lax.scan(pref_step, eye, mats)
        my_excl = excl[idx]  # (B, S, S)
        boundary_pm = my_excl[:, 0, :]  # start state 0 -> (B, S)

        # local re-scan for backpointers
        def bp_step(pm, bm_t):
            new_pm, bp = acs_step(code, pm, bm_t)
            # one byte per backpointer bit: a quarter of int32's memory
            return jnp.minimum(new_pm, NEG_UNREACHABLE), bp.astype(jnp.int8)

        _, bps_loc = jax.lax.scan(bp_step, boundary_pm, bm_loc.swapaxes(0, 1))
        final_pm = total[:, 0, :]  # (B, S) full-sequence metrics from state 0
        if terminated:
            final_state = jnp.zeros((B,), jnp.int32)
            metric = final_pm[:, 0]
        else:
            final_state = jnp.argmin(final_pm, axis=-1).astype(jnp.int32)
            metric = final_pm.min(axis=-1)

        # the survivors never leave the shard: chain the per-shard exit ->
        # entry maps back from the final state to find where this shard's
        # stretch of the surviving path ends, then trace it from there
        entries = jax.lax.all_gather(_entry_state_of_exit(code, bps_loc), axis)

        def seam_step(exit_state, entry_map):  # walks shards n-1 .. 0
            prev = jnp.take_along_axis(entry_map, exit_state[:, None], axis=-1)[:, 0]
            return prev, exit_state

        _, exits = jax.lax.scan(seam_step, final_state, entries, reverse=True)
        bits_loc, _ = _traceback(code, bps_loc, exits[idx])  # (B, T/n)
        return bits_loc, metric

    return shard_map(
        shard_fn, mesh=mesh,
        in_specs=P(None, axis, None),
        out_specs=(P(None, axis), P()),
    )(bm_tables)


def psum_scalar(x, axis: str):
    return jax.lax.psum(x, axis)


def mesh_axis_size(mesh, axis: str) -> int:
    """Size of a named mesh axis, 0 when the mesh lacks it (the planner and
    the stream scheduler both branch on this)."""
    if mesh is None:
        return 0
    return int(mesh.shape.get(axis, 0))


def reduce_across_shards(
    mesh, axis: str, per_shard: jnp.ndarray, op: str = "sum"
) -> jnp.ndarray:
    """Reduce a per-shard leading-axis array to a mesh-global scalar view.

    The sharded stream scheduler keeps admission/eviction bookkeeping
    host-side per shard; the few scalars that need a global view —
    utilization, pending-work counts, committed-bit totals, telemetry
    aggregates like the worst per-shard merge depth — reduce across the
    ``data`` axis here instead of gathering any decode state.  This is the
    same collective a multi-controller deployment (one host per shard) would
    issue over its own shard-local metrics.

    ``per_shard``: (n_shards, ...) with row i owned by shard i;
    ``op``: 'sum' | 'max' | 'min'; returns the reduced (...) value,
    replicated on every shard.
    """
    try:
        local_reduce, collective = {
            "sum": (jnp.sum, jax.lax.psum),
            "max": (jnp.max, jax.lax.pmax),
            "min": (jnp.min, jax.lax.pmin),
        }[op]
    except KeyError:
        raise ValueError(f"op must be 'sum', 'max' or 'min', got {op!r}") from None

    def local_fn(x):  # x: (1, ...) — this shard's row
        return collective(local_reduce(x, axis=0), axis)

    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(),
    )(jnp.asarray(per_shard))


def sum_across_shards(mesh, axis: str, per_shard: jnp.ndarray) -> jnp.ndarray:
    """reduce_across_shards with op='sum' — the common scheduler case."""
    return reduce_across_shards(mesh, axis, per_shard, op="sum")
