"""Quickstart: the paper's technique in five minutes.

  PYTHONPATH=src python examples/quickstart.py

1. Build a rate-1/2 convolutional code (the paper's K=3 trellis).
2. Encode a batch of messages, push them through a noisy channel.
3. Decode with the fused Pallas `Texpand` pipeline (the paper's custom
   instruction, TPU-native) and with the plain decoder — same answer.
4. Decode a long stream with the beyond-paper (min,+) parallel scan.
"""
import jax
import jax.numpy as jnp

from repro.core import (
    CODE_K3_STD,
    bsc,
    encode,
    hard_branch_metrics,
    paper_expansion_calls,
    viterbi_decode,
    viterbi_decode_parallel,
)
from repro.kernels import viterbi_decode_packed


def main():
    code = CODE_K3_STD
    key = jax.random.PRNGKey(0)

    # --- 1-2: encode + channel ------------------------------------------- #
    bits = jax.random.bernoulli(key, 0.5, (8, 64)).astype(jnp.int32)
    coded = encode(code, bits, terminate=True)  # (8, 66, 2) — rate 1/2
    received = bsc(jax.random.fold_in(key, 1), coded, flip_prob=0.02)
    print(f"coded bits per stream: {coded.shape[1] * coded.shape[2]} "
          f"(paper counts {paper_expansion_calls(coded.shape[1]*2)} ACS calls)")

    # --- 3: decode (fused kernel == reference) ---------------------------- #
    bm = hard_branch_metrics(code, received)
    dec_ref, metric_ref = viterbi_decode(code, bm)
    dec_fused, metric_fused = viterbi_decode_packed(code, bm)
    assert (dec_ref == dec_fused).all() and jnp.allclose(metric_ref, metric_fused)
    ber = float((dec_fused[:, :64] != bits).mean())
    print(f"fused Texpand decode: BER={ber:.4f}  "
          f"path metrics {metric_fused[:4].tolist()}")

    # --- 4: beyond-paper parallel decode ----------------------------------- #
    long_bits = jax.random.bernoulli(key, 0.5, (2, 4096)).astype(jnp.int32)
    long_rx = bsc(jax.random.fold_in(key, 2),
                  encode(code, long_bits, terminate=True), 0.02)
    long_bm = hard_branch_metrics(code, long_rx)
    dec_par, m_par = viterbi_decode_parallel(code, long_bm, chunk=256)
    dec_seq, m_seq = viterbi_decode(code, long_bm)
    assert jnp.allclose(m_par, m_seq)
    print(f"4096-bit stream: (min,+) associative-scan decode matches "
          f"sequential (metric {float(m_par[0]):.0f}) at log-depth")


if __name__ == "__main__":
    main()
