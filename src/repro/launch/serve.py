"""Serving launcher: batched generation + the Viterbi decode path.

  python -m repro.launch.serve --arch qwen2_5_3b --smoke --tokens 32
  python -m repro.launch.serve --viterbi --bits 256 --batch 64 --backend fused_packed
  python -m repro.launch.serve --viterbi --backend auto   # planner picks
"""
from __future__ import annotations

import argparse
import json
import time

from repro.obs.log import get_logger

log = get_logger("launch.serve")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_5_3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    # Viterbi decode path
    ap.add_argument("--viterbi", action="store_true")
    ap.add_argument("--bits", type=int, default=256)
    ap.add_argument("--backend", "--mode", dest="backend", default="auto",
                    help="registry backend name, or 'auto' for the planner")
    ap.add_argument("--flip-prob", type=float, default=0.02)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    if args.viterbi:
        from repro.configs.paper_viterbi import DECODE_SPEC
        from repro.decode import DecodeRequest, decode

        spec = DECODE_SPEC
        backend = None if args.backend == "auto" else args.backend
        key = jax.random.PRNGKey(0)
        bits = jax.random.bernoulli(key, 0.5, (args.batch, args.bits)).astype(jnp.int32)
        coded = spec.encode(bits)
        rx = spec.channel(jax.random.PRNGKey(1), coded, flip_prob=args.flip_prob)
        t0 = time.perf_counter()
        res = decode(DecodeRequest(spec, received=rx), backend=backend)
        jax.block_until_ready(res.bits)
        dt = time.perf_counter() - t0
        ber = float((res.info_bits != bits).mean())
        log.info(res.plan.explain(costs=True))
        log.info(json.dumps({
            "backend": res.plan.backend, "batch": args.batch, "bits": args.bits,
            "ber": ber, "exact": bool((res.info_bits == bits).all()),
            "throughput_bits_per_s": args.batch * args.bits / dt,
        }, indent=1))
        return

    from repro.configs.base import get_arch, get_smoke_arch
    from repro.models.model_zoo import build
    from repro.serve.engine import ServeEngine

    bundle = get_smoke_arch(args.arch) if args.smoke else get_arch(args.arch)
    model = build(bundle)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params,
                         max_len=args.prompt_len + args.tokens,
                         temperature=args.temperature)
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, model.cfg.vocab)
    t0 = time.perf_counter()
    out = engine.generate(prompts, args.tokens)
    dt = time.perf_counter() - t0
    log.info(json.dumps({
        "arch": model.cfg.name, "batch": args.batch,
        "new_tokens": int(out["tokens"].shape[1]),
        "tokens_per_s": args.batch * out["tokens"].shape[1] / dt,
        "sample": out["tokens"][0, :8].tolist(),
    }, indent=1))


if __name__ == "__main__":
    main()
