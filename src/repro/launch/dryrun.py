import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks device
count at first init). Each cell:

    with 512 host devices:
        mesh = make_production_mesh(multi_pod=...)
        jit(step, in_shardings=..., out_shardings=...)
            .lower(**input_specs(arch, shape))   # ShapeDtypeStruct only
            .compile()
        -> memory_analysis()  (fits-per-device proof)
        -> cost_analysis()    (raw XLA numbers)
        -> analysis.hlo       (loop-corrected FLOPs/bytes/collectives)
        -> analysis.roofline  (the three terms, §Roofline)

Results land in experiments/dryrun/<mesh>/<arch>__<shape>.json.

Usage:
    python -m repro.launch.dryrun --arch dbrx-132b --shape train_4k
    python -m repro.launch.dryrun --all [--multipod-only|--singlepod-only]
"""

import argparse
import json
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.analysis import hlo as hlo_lib
from repro.analysis import roofline as roof_lib
from repro.configs import ARCH_IDS, SHAPES, cell_is_runnable, get_config
from repro.dist.sharding import logical_to_sharding
from repro.launch.mesh import make_production_mesh
from repro.models import model_zoo
from repro.optim import adamw
from repro.serve import engine


def input_specs(cfg, shape, rules, mesh):
    """ShapeDtypeStruct stand-ins + NamedShardings for every model input."""
    B, S = shape.global_batch, shape.seq_len
    batch_ok = B % _batch_shards(mesh) == 0
    bspec = ("batch",) if batch_ok else (None,)
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    specs, shards = {}, {}
    if shape.kind == "train":
        specs = {"tokens": tok, "labels": tok}
        shards = {"tokens": bspec + (None,), "labels": bspec + (None,)}
        if cfg.family == "audio":
            specs["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.n_frames, cfg.d_model), jnp.bfloat16)
            shards["frames"] = bspec + (None, None)
        if cfg.family == "vlm":
            specs["patches"] = jax.ShapeDtypeStruct(
                (B, cfg.n_patches, cfg.d_model), jnp.bfloat16)
            shards["patches"] = bspec + (None, None)
    elif shape.kind == "prefill":
        specs = {"tokens": tok}
        shards = {"tokens": bspec + (None,)}
        if cfg.family == "audio":
            specs["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.n_frames, cfg.d_model), jnp.bfloat16)
            shards["frames"] = bspec + (None, None)
        if cfg.family == "vlm":
            specs["patches"] = jax.ShapeDtypeStruct(
                (B, cfg.n_patches, cfg.d_model), jnp.bfloat16)
            shards["patches"] = bspec + (None, None)
    else:  # decode
        specs = {"token": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
        shards = {"token": bspec + (None,)}
    sharding_tree = {
        k: rules.sharding(v, mesh) for k, v in shards.items()}
    return specs, sharding_tree, bspec


def _batch_shards(mesh):
    n = mesh.shape.get("data", 1)
    n *= mesh.shape.get("pod", 1)
    return n


def _bf16_abstract(tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
        if jnp.issubdtype(s.dtype, jnp.floating) else s, tree)


def lower_cell(arch: str, shape_name: str, multi_pod: bool):
    """Returns a dict of analysis results for one cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = model_zoo.make_rules(cfg, mesh)
    n_dev = mesh.size

    t0 = time.time()
    axes = model_zoo.param_axes(cfg)
    abs_params = model_zoo.abstract_params(cfg)
    param_sh = logical_to_sharding(axes, rules, mesh)
    specs, in_sh, bspec = input_specs(cfg, shape, rules, mesh)
    batch_ok = bspec[0] is not None

    cache_len = shape.seq_len + (cfg.n_patches if cfg.family == "vlm"
                                 else 0)
    if shape.kind == "train":
        opt_cfg = adamw.AdamWConfig()
        abs_opt = jax.eval_shape(adamw.init, abs_params)
        opt_sh = adamw.AdamWState(
            step=rules.sharding((), mesh),
            mu=param_sh, nu=param_sh)

        from repro.train.train_loop import make_train_step
        step_fn = make_train_step(cfg, opt_cfg, rules)

        def train_step(params, opt_state, batch):
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            return params, opt_state, metrics["loss"]

        jitted = jax.jit(
            train_step,
            in_shardings=(param_sh, opt_sh, in_sh),
            out_shardings=(param_sh, opt_sh, rules.sharding((), mesh)),
            donate_argnums=(0, 1))
        lowered = jitted.lower(abs_params, abs_opt, specs)
    elif shape.kind == "prefill":
        serve_params = _bf16_abstract(abs_params)
        cache_abs = engine.make_cache(cfg, shape.global_batch, cache_len,
                                      mode="abstract")
        cache_sh = engine.cache_shardings(cfg, rules, mesh,
                                          batch_sharded=batch_ok)

        def prefill_step(params, batch, cache):
            return engine.prefill(params, cfg, batch["tokens"], cache, rules,
                                  prefix_embeds=batch.get("patches"),
                                  frames=batch.get("frames"))

        jitted = jax.jit(prefill_step,
                         in_shardings=(param_sh, in_sh, cache_sh),
                         donate_argnums=(2,))
        lowered = jitted.lower(serve_params, specs, cache_abs)
    else:  # decode
        serve_params = _bf16_abstract(abs_params)
        cache_abs = engine.make_cache(cfg, shape.global_batch, cache_len,
                                      mode="abstract")
        cache_sh = engine.cache_shardings(cfg, rules, mesh,
                                          batch_sharded=batch_ok)

        def serve_step(params, token, cache, cur_len):
            return engine.decode_step(params, cfg, token, cache, cur_len,
                                      rules)

        jitted = jax.jit(serve_step,
                         in_shardings=(param_sh, in_sh["token"], cache_sh,
                                       rules.sharding((), mesh)),
                         donate_argnums=(2,))
        lowered = jitted.lower(serve_params, specs["token"], cache_abs,
                               jax.ShapeDtypeStruct((), jnp.int32))
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo_text = compiled.as_text()
    cost = hlo_lib.analyze(hlo_text)

    n_active = model_zoo.count_active_params(cfg)
    mf = roof_lib.model_flops(cfg, shape, n_active)
    rt = roof_lib.terms(
        flops_per_device=cost.flops,
        hbm_bytes_per_device=cost.hbm_bytes,
        collective_bytes_per_device=cost.total_collective_bytes,
        model_flops_total=mf, n_devices=n_dev)

    result = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "status": "ok",
        "mesh": dict(mesh.shape),
        "n_devices": n_dev,
        "params_total": model_zoo.count_params(cfg),
        "params_active": n_active,
        "time_lower_s": round(t_lower, 1),
        "time_compile_s": round(t_compile, 1),
        "memory_analysis": {
            "argument_bytes_per_device": ma.argument_size_in_bytes,
            "output_bytes_per_device": ma.output_size_in_bytes,
            "temp_bytes_per_device": ma.temp_size_in_bytes,
            "peak_estimate_gib": round(
                (ma.argument_size_in_bytes + ma.temp_size_in_bytes)
                / 2**30, 3),
            # The CPU backend does not implement buffer donation, so the
            # donated params/opt/cache update is double-buffered in temp;
            # on the TPU target the outputs alias the donated inputs.
            "peak_estimate_donated_gib": round(
                (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                 - min(ma.output_size_in_bytes,
                       ma.argument_size_in_bytes)) / 2**30, 3),
        },
        "cost_analysis_raw": {
            "flops": ca.get("flops", -1.0),
            "bytes_accessed": ca.get("bytes accessed", -1.0),
        },
        "hlo_analyzer": {
            "flops_per_device": cost.flops,
            "hbm_bytes_per_device": cost.hbm_bytes,
            "collective_bytes_per_device": cost.total_collective_bytes,
            "collectives_by_kind": cost.collective_bytes,
            "while_trip_counts": cost.trip_counts,
        },
        "roofline": rt.as_dict(),
        "note": roof_lib.what_would_move_it(rt),
    }
    return result


def run_cell_and_save(arch, shape_name, multi_pod, out_dir):
    sub = "multipod" if multi_pod else "singlepod"
    os.makedirs(os.path.join(out_dir, sub), exist_ok=True)
    fname = os.path.join(out_dir, sub, f"{arch}__{shape_name}.json")
    try:
        result = lower_cell(arch, shape_name, multi_pod)
    except Exception as e:  # noqa
        result = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                  "status": "error", "error": str(e)[:2000],
                  "traceback": traceback.format_exc()[-4000:]}
    with open(fname, "w") as f:
        json.dump(result, f, indent=1)
    status = result["status"]
    extra = ""
    if status == "ok":
        extra = (f" compile={result['time_compile_s']}s "
                 f"mem/dev={result['memory_analysis']['peak_estimate_gib']}GiB "
                 f"dominant={result['roofline']['dominant']}")
    print(f"[dryrun] {sub} {arch} {shape_name}: {status}{extra}",
          flush=True)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--subprocess-per-cell", action="store_true",
                    help="isolate each cell in a child process")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    if args.all:
        cells = []
        for arch in ARCH_IDS:
            for shape_name in SHAPES:
                for mp in (False, True):
                    cells.append((arch, shape_name, mp))
        for arch, shape_name, mp in cells:
            if args.subprocess_per_cell:
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape_name,
                       "--out", args.out]
                if mp:
                    cmd.append("--multipod")
                subprocess.run(cmd, check=False)
            else:
                run_cell_and_save(arch, shape_name, mp, args.out)
        return

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    result = run_cell_and_save(args.arch, args.shape, args.multipod,
                               args.out)
    if result["status"] == "error":
        print(result["traceback"])
        sys.exit(1)


if __name__ == "__main__":
    main()
