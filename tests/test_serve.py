"""Serving engine + decode-API transport end-to-end."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_arch
from repro.decode import CodecSpec, decode
from repro.models.model_zoo import build
from repro.serve import ServeEngine, bits_to_tokens, tokens_to_bits
from repro.serve.kv_cache import SlotAllocator, cache_bytes, pick_bucket


def test_engine_generates(rng):
    model = build(get_smoke_arch("qwen2_5_3b"))
    params = model.init(rng)
    engine = ServeEngine(model, params, max_len=24)
    prompts = jax.random.randint(jax.random.fold_in(rng, 1), (2, 8), 1,
                                 model.cfg.vocab)
    out = engine.generate(prompts, max_new_tokens=8)
    assert out["tokens"].shape == (2, 8)
    assert bool((out["tokens"] >= 0).all())


def test_engine_greedy_is_deterministic(rng):
    model = build(get_smoke_arch("qwen3_4b"))
    params = model.init(rng)
    engine = ServeEngine(model, params, max_len=20, temperature=0.0)
    prompts = jax.random.randint(jax.random.fold_in(rng, 1), (2, 6), 1,
                                 model.cfg.vocab)
    a = engine.generate(prompts, 6)["tokens"]
    b = engine.generate(prompts, 6)["tokens"]
    assert (a == b).all()


@pytest.mark.parametrize("backend", ["fused_packed", "sequential", "parallel"])
def test_decode_transport_roundtrip(backend, rng):
    spec = CodecSpec()
    bits = jax.random.bernoulli(rng, 0.5, (8, 64)).astype(jnp.int32)
    rx = spec.channel(jax.random.fold_in(rng, 1), spec.encode(bits),
                      flip_prob=0.01)
    res = decode(spec, rx, backend=backend)
    assert res.info_bits.shape == bits.shape
    assert float((res.info_bits != bits).mean()) < 0.05


def test_decode_transport_soft(rng):
    spec = CodecSpec(metric="soft")
    bits = jax.random.bernoulli(rng, 0.5, (8, 64)).astype(jnp.int32)
    rx = spec.channel(jax.random.fold_in(rng, 1), spec.encode(bits), snr_db=4.0)
    res = decode(spec, rx)
    assert float((res.info_bits != bits).mean()) < 0.03


def test_lm_to_viterbi_pipeline(rng):
    """The paper's serving scenario end-to-end: LM output -> bitstream ->
    conv encode -> noisy channel -> fused Viterbi decode -> exact recovery."""
    model = build(get_smoke_arch("qwen2_5_3b"))
    params = model.init(rng)
    engine = ServeEngine(model, params, max_len=16)
    prompts = jax.random.randint(jax.random.fold_in(rng, 1), (2, 8), 1,
                                 model.cfg.vocab)
    toks = engine.generate(prompts, 8)["tokens"]
    bits = tokens_to_bits(toks, bits_per_token=9)  # vocab 512
    spec = CodecSpec()
    rx = spec.channel(jax.random.fold_in(rng, 2), spec.encode(bits),
                      flip_prob=0.005)
    res = decode(spec, rx)
    dec = res.info_bits
    exact = bool((dec == bits).all())
    assert exact or float((np.asarray(dec) != np.asarray(bits)).mean()) < 0.01
    recovered = bits_to_tokens(dec, 9)
    if exact:
        assert (recovered == toks).all()


def test_bits_tokens_roundtrip(rng):
    toks = jax.random.randint(rng, (3, 10), 0, 512)
    assert (bits_to_tokens(tokens_to_bits(toks, 9), 9) == toks).all()


def test_kv_cache_utils():
    assert pick_bucket(100, 200) == 1024
    assert pick_bucket(4000, 96) == 4096
    assert pick_bucket(4000, 100) == 16384  # 4100 > 4096 -> next bucket
    with pytest.raises(ValueError):
        pick_bucket(600000, 1)
    model = build(get_smoke_arch("qwen3_4b"))
    b = cache_bytes(model, B=2, S=64)
    assert b > 0
    alloc = SlotAllocator(2)
    s0 = alloc.claim("a")
    alloc.claim("b")
    assert alloc.claim("c") is None
    alloc.release(s0)
    assert alloc.claim("c") is not None
    assert alloc.utilization() == 1.0
