import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines, before ANY other import: jax locks the
#   device count on first backend init.  512 placeholder host devices let
#   jax.make_mesh build the production meshes.  This is set ONLY here —
#   smoke tests and benchmarks see the real single device.

"""Multi-pod dry-run: lower + compile every (architecture × input-shape ×
mesh) cell and extract memory / cost / collective evidence.

Per cell:
  with mesh:
      lowered  = jax.jit(step, in_shardings=..., out_shardings=...) \
                     .lower(**input_specs(arch, shape))
      compiled = lowered.compile()
      print(compiled.memory_analysis())   # proves it fits per-chip HBM
      print(compiled.cost_analysis())     # FLOPs / bytes for the roofline

Results land in benchmarks/results/dryrun/<cell>.json, consumed by
benchmarks/roofline_report.py and EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch qwen3_4b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--jobs 1]
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

from repro.obs.log import get_logger

RESULTS = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"

log = get_logger("launch.dryrun")


def _build_step(model, shape, mesh, overrides=None):
    """Returns (fn, kwargs of ShapeDtypeStructs-with-shardings)."""
    import jax

    from repro.models import common as cm
    from repro.parallel.sharding import shard_batch_tree
    from repro.train.optimizer import cosine_warmup, get_optimizer

    rules = overrides or None
    specs = model.input_specs(shape)

    def attach(tree, shardings):
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            tree, shardings)

    abs_params = attach(model.abstract_params(), model.param_shardings(mesh, rules))
    if shape.kind != "train":
        # serving runs on bf16 weights (standard practice): halves the
        # per-chip param footprint the decode/prefill cells must hold
        import jax.numpy as jnp

        abs_params = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape,
                jnp.bfloat16 if s.dtype == jnp.dtype(jnp.float32) else s.dtype,
                sharding=s.sharding),
            abs_params)

    if shape.kind == "train":
        from repro.train.train_loop import build_step_fn

        optimizer = get_optimizer(model.part.optimizer)
        lr_fn = cosine_warmup(3e-4, 100, 10000)
        opt_abs = optimizer.state_specs(model.param_specs)
        opt_abs_sds = cm.abstract(opt_abs)
        opt_sh = cm.shardings(opt_abs, mesh, model._rules(rules, for_opt=True))
        abs_opt = attach(opt_abs_sds, opt_sh)
        batch = attach(specs["batch"], shard_batch_tree(mesh, specs["batch"]))
        train_step = build_step_fn(model, optimizer, lr_fn, mesh, rules)

        kwargs = {
            "params": abs_params,
            "opt_state": abs_opt,
            "batch": batch,
            "step_idx": jax.ShapeDtypeStruct((), jax.numpy.int32),
        }
        # params/opt_state are donated (aliased in->out), as in the real
        # training loop: the update is in-place, not double-buffered
        return train_step, kwargs, ("params", "opt_state")

    if shape.kind == "prefill":
        B, S = shape.global_batch, shape.seq_len
        caches = attach(specs["caches"], model.cache_shardings(mesh, B, S, rules))
        batch = attach(specs["batch"], shard_batch_tree(mesh, specs["batch"]))

        def prefill_step(params, batch, caches):
            return model.prefill(params, batch, caches, mesh=mesh, rules=rules)

        return (prefill_step,
                {"params": abs_params, "batch": batch, "caches": caches},
                ("caches",))

    # decode
    B, S = shape.global_batch, shape.seq_len
    caches = attach(specs["caches"], model.cache_shardings(mesh, B, S, rules))
    toks = attach(
        {"tokens": specs["tokens"], "positions": specs["positions"]},
        shard_batch_tree(mesh, {"tokens": specs["tokens"],
                                "positions": specs["positions"]}))

    def serve_step(params, tokens, positions, caches):
        return model.decode_step(params, tokens, positions, caches,
                                 mesh=mesh, rules=rules)

    return (serve_step,
            {"params": abs_params, "tokens": toks["tokens"],
             "positions": toks["positions"], "caches": caches},
            ("caches",))


def run_cell(arch_id: str, shape_name: str, mesh_kind: str,
             overrides=None, tag: str = "", partition=None) -> dict:
    import dataclasses

    import jax

    from repro.configs.base import SHAPES, get_arch
    from repro.launch.mesh import make_production_mesh
    from repro.roofline.analysis import collective_bytes, model_flops
    from repro.roofline.hlo_loops import collective_bytes_with_trips
    from repro.roofline.jaxpr_cost import count_fn_costs

    bundle = get_arch(arch_id)
    if partition:  # perf-iteration knobs, e.g. '{"zero_stage": 1}'
        bundle = dataclasses.replace(
            bundle, partition=dataclasses.replace(bundle.partition, **partition))
    shape = SHAPES[shape_name]
    skip = bundle.skips(shape_name)
    if skip:
        return {"arch": arch_id, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": skip}

    from repro.models.model_zoo import build

    model = build(bundle)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = 1
    for v in dict(mesh.shape).values():
        chips *= v

    t0 = time.time()
    with mesh:
        fn, kwargs, donate = _build_step(model, shape, mesh, overrides)
        lowered = jax.jit(fn, donate_argnames=donate).lower(**kwargs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):  # older jax: one dict per computation
            cost = cost[0] if cost else {}
        log.info(str(mem))
        log.info(str({k: v for k, v in cost.items()
                      if k in ("flops", "bytes accessed")}))
        hlo = compiled.as_text()
        # trip-count-aware GLOBAL costs (XLA's cost_analysis counts loop
        # bodies once — see roofline/jaxpr_cost.py)
        jx = count_fn_costs(fn, **kwargs)
    coll_raw = collective_bytes(hlo)
    coll = collective_bytes_with_trips(hlo)

    mem_rec = {
        k: getattr(mem, k)
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes")
        if hasattr(mem, k)
    }
    cell = {
        "arch": arch_id, "shape": shape_name, "mesh": mesh_kind,
        "tag": tag, "status": "ok", "chips": chips,
        # the production meshes are TPU v5e pods; the host devices only
        # stand in for them, so the cell names the chip it was sized for
        "device_kind": "TPU v5 lite",
        "mesh_shape": dict(mesh.shape),
        "step_kind": shape.kind,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": mem_rec,
        "cost_analysis": {k: cost[k] for k in ("flops", "bytes accessed")
                          if k in cost},
        "jaxpr_cost": {
            "flops_global": jx["flops"],
            "bytes_global": jx["bytes"],
            "input_bytes_global": jx.get("input_bytes", 0.0),
            "flops_per_device": jx["flops"] / chips,
            "bytes_per_device": jx["bytes"] / chips,
        },
        "collectives": coll,
        "collectives_raw_once": coll_raw,
        "model_flops": model_flops(model.cfg, shape),
        "hlo_sizes": {"n_lines": hlo.count("\n")},
    }
    return cell


ARCHS = (
    "qwen3_moe_30b_a3b", "deepseek_v2_lite_16b", "xlstm_350m", "qwen1_5_110b",
    "qwen3_4b", "gemma3_12b", "qwen2_5_3b", "internvl2_26b",
    "seamless_m4t_large_v2", "jamba_v0_1_52b",
)
SHAPE_NAMES = ("train_4k", "prefill_32k", "decode_32k", "long_500k")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=("single", "multi", "both"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=1)
    ap.add_argument("--tag", default="")
    ap.add_argument("--override", default="",
                    help="JSON dict of sharding-rule overrides (perf knobs)")
    ap.add_argument("--partition", default="",
                    help="JSON dict of PartitionConfig overrides (perf knobs)")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    RESULTS.mkdir(parents=True, exist_ok=True)

    if args.all:
        # orchestrate one subprocess per cell (device count is locked per
        # process; separate processes also bound compile-memory blowups)
        meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
        jobs = []
        for arch in ARCHS:
            for shp in SHAPE_NAMES:
                for mk in meshes:
                    out = RESULTS / f"{arch}--{shp}--{mk}{args.tag}.json"
                    if out.exists() and not args.force:
                        continue
                    jobs.append((arch, shp, mk))
        log.info("cells to run", n=len(jobs))
        running = []
        while jobs or running:
            while jobs and len(running) < args.jobs:
                arch, shp, mk = jobs.pop(0)
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shp, "--mesh", mk,
                       "--tag", args.tag]
                if args.override:
                    cmd += ["--override", args.override]
                log.info("LAUNCH", arch=arch, shape=shp, mesh=mk)
                running.append(((arch, shp, mk), subprocess.Popen(cmd)))
            done = [(c, p) for c, p in running if p.poll() is not None]
            running = [(c, p) for c, p in running if p.poll() is None]
            for c, p in done:
                arch, shp, mk = c
                if p.returncode == 0:
                    log.info("DONE", arch=arch, shape=shp, mesh=mk)
                else:
                    log.error("FAIL", arch=arch, shape=shp, mesh=mk,
                              returncode=p.returncode)
            time.sleep(2)
        return

    overrides = json.loads(args.override) if args.override else None
    partition = json.loads(args.partition) if args.partition else None
    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    for mk in meshes:
        out = RESULTS / f"{args.arch}--{args.shape}--{mk}{args.tag}.json"
        try:
            cell = run_cell(args.arch, args.shape, mk, overrides, args.tag,
                            partition)
        except Exception as e:  # record the failure — failures are bugs
            cell = {"arch": args.arch, "shape": args.shape, "mesh": mk,
                    "tag": args.tag, "status": "error",
                    "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]}
        out.write_text(json.dumps(cell, indent=1, default=float))
        log.info(json.dumps({k: cell.get(k) for k in
                             ("arch", "shape", "mesh", "status")}, indent=None))
        if cell["status"] == "error":
            sys.exit(1)


if __name__ == "__main__":
    main()
