"""GPipe-style pipeline parallelism over a 'stage' mesh axis.

Not used by the production dry-run meshes (the pod axis there is data-
parallel: DP×TP covers 512 chips for every assigned arch), but provided as a
first-class scheme for deeper scaling.  The schedule is the classic
fill/steady/drain: with n stages and M microbatches, step t has stage s
processing microbatch (t - s); activations hop stages via ppermute.

Bubble fraction = (n-1)/(M+n-1) — reported by :func:`bubble_fraction` so
launch configs can budget microbatches.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from repro.parallel.mesh import shard_map
from jax.sharding import PartitionSpec as P


def bubble_fraction(n_stages: int, microbatches: int) -> float:
    return (n_stages - 1) / (microbatches + n_stages - 1)


def pipeline_apply(
    layer_fn: Callable,
    stage_params,
    x_mb: jnp.ndarray,
    *,
    mesh,
    axis: str = "stage",
):
    """Run ``layer_fn(params_s, h)`` across pipeline stages.

    Args:
      stage_params: pytree whose leaves have leading dim n_stages.
      x_mb: (M, mb, ...) microbatched input (replicated).
    Returns:
      (M, mb, ...) outputs (replicated).
    """
    n = mesh.shape[axis]
    M = x_mb.shape[0]
    steps = M + n - 1

    def shard_fn(params_s, xs):
        # params_s: this stage's params (leading stage dim stripped by
        # shard_map); xs: full microbatch stream (replicated).
        params_s = jax.tree_util.tree_map(lambda a: a[0], params_s)
        s = jax.lax.axis_index(axis)
        h0 = jnp.zeros_like(xs[0])

        def body(h_in, t):
            mb_idx = t - s  # microbatch this stage works on at step t
            valid = (mb_idx >= 0) & (mb_idx < M)
            # stage 0 reads fresh input; others use the hopped-in activation
            x_t = xs[jnp.clip(t, 0, M - 1)]
            h = jnp.where(s == 0, x_t, h_in)
            y = layer_fn(params_s, h)
            y = jnp.where(valid, y, h_in)
            # hop to the next stage (ring; the wraparound value is ignored)
            y_next = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n) for i in range(n)])
            return y_next, y  # emit this stage's freshly computed activation

        _, ys = jax.lax.scan(body, h0, jnp.arange(steps))
        return ys[None]  # (1, steps, mb, ...): stage-major for stitching

    ys = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(axis),
    )(stage_params, x_mb)
    # ys: (n, steps, mb, ...); microbatch m exits the last stage at step m+n-1
    return ys[n - 1, n - 1 : n - 1 + M]
