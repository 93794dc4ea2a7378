"""Long-stream decoding two ways (the technique the paper's future-work
section gestures at — parallel execution of the custom instruction):

1. a 64k-bit coded stream decoded by the (min,+) associative scan
   (log-depth, the block-parallel form of the paper's ACS recurrence);
2. the same decode distributed over a mesh axis with shard_map
   (sequence-parallel Viterbi — communication independent of T);
3. an SSM-family LM (xlstm) decoding with O(1) state, the architectural
   cousin of the same recurrence trick.

  PYTHONPATH=src python examples/long_context.py
"""
import time

import jax
import jax.numpy as jnp

from repro.core import CODE_K3_STD, bsc, encode, hard_branch_metrics
from repro.core.viterbi import viterbi_decode, viterbi_decode_parallel
from repro.parallel.mesh import make_mesh


def main():
    code = CODE_K3_STD
    key = jax.random.PRNGKey(0)
    T = 65536
    bits = jax.random.bernoulli(key, 0.5, (1, T)).astype(jnp.int32)
    rx = bsc(jax.random.fold_in(key, 1), encode(code, bits, terminate=True), 0.01)
    bm = hard_branch_metrics(code, rx)

    seq = jax.jit(lambda b: viterbi_decode(code, b))
    par = jax.jit(lambda b: viterbi_decode_parallel(code, b, chunk=512))
    d1, m1 = seq(bm)
    d2, m2 = par(bm)
    jax.block_until_ready((d1, d2))
    assert jnp.allclose(m1, m2) and (d1 == d2).all()

    t0 = time.perf_counter(); jax.block_until_ready(seq(bm)[1]); t_seq = time.perf_counter() - t0
    t0 = time.perf_counter(); jax.block_until_ready(par(bm)[1]); t_par = time.perf_counter() - t0
    ber = float((d2[:, :T] != bits).mean())
    print(f"64k-bit stream: sequential {t_seq*1e3:.0f}ms, "
          f"assoc-scan {t_par*1e3:.0f}ms, BER={ber:.5f}")

    # 2: mesh-distributed (single device here -> axis size 1, same numerics)
    mesh = make_mesh((1,), ("model",))
    from repro.parallel.collectives import viterbi_decode_seqparallel

    with mesh:
        d3, m3 = viterbi_decode_seqparallel(code, bm, mesh)
    assert jnp.allclose(m3, m1)
    print("sequence-parallel shard_map decode matches (comm = n·S² floats, "
          "independent of T)")

    # 3: the same recurrence idea as an LM: xlstm decodes with O(1) state
    from repro.configs.base import get_smoke_arch
    from repro.models.model_zoo import build

    model = build(get_smoke_arch("xlstm_350m"))
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 32
    caches = model.init_cache(B, S)
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, model.cfg.vocab)
    logits, caches = model.prefill(params, {"tokens": toks}, caches)
    state_bytes = sum(c.size * c.dtype.itemsize
                      for c in jax.tree_util.tree_leaves(caches))
    print(f"xlstm decode state: {state_bytes/1e3:.0f} kB — constant in context "
          f"length (the 500k-token dry-run cell decodes with the same state)")


if __name__ == "__main__":
    main()
