"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \\
        --smoke --steps 50 --ckpt-dir /tmp/ckpt

On a real TPU cluster, each host runs this under its own process (JAX
distributed init is keyed off the standard TPU env vars); on this CPU
container it runs single-process with the full production code path:
logical-axis sharded params, microbatched train step, async sharded
checkpoints with auto-resume, straggler watchdog, SIGTERM-safe exit.

XLA flags set here are the TPU latency-hiding defaults (compute/comm
overlap — DESIGN.md §9); they are no-ops on CPU.
"""

import argparse
import os

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.dist.sharding import logical_to_sharding
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_zoo
from repro.optim import adamw, schedule
from repro.train import train_loop

# compute/communication overlap: XLA's latency-hiding scheduler and
# async collectives on the TPU target (harmless on CPU).
TPU_FLAGS = ("--xla_enable_async_all_gather=true",
             "--xla_enable_async_collective_permute=true",
             "--xla_tpu_enable_data_parallel_all_reduce_opt=true")


def append_tpu_flags() -> str:
    """Append ``TPU_FLAGS`` to ``LIBTPU_INIT_ARGS``; return the result.

    The TPU host may already hold flags there (some work round compiler
    faults), so nothing it set is dropped, and a flag it already names
    keeps its value. Must run before the TPU backend initializes."""
    have = os.environ.get("LIBTPU_INIT_ARGS", "").split()
    names = {f.split("=", 1)[0] for f in have}
    add = [f for f in TPU_FLAGS if f.split("=", 1)[0] not in names]
    os.environ["LIBTPU_INIT_ARGS"] = " ".join(have + add)
    return os.environ["LIBTPU_INIT_ARGS"]


def main():
    append_tpu_flags()
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default=None,
                    help="e.g. '4,2' => (data,model) or '2,2,2' => "
                         "(data,model,stage); with a stage axis > 1 and "
                         "--grad-accum > 1, microbatches route through "
                         "the dist.pipeline schedule (DESIGN.md §6.2); "
                         "default single device")
    ap.add_argument("--grad-accum", type=int, default=0,
                    help="microbatches per step (0 = config default)")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.grad_accum:
        import dataclasses
        cfg = dataclasses.replace(cfg, grad_accum=args.grad_accum)
    print(f"[launch.train] {cfg.name}: "
          f"{model_zoo.count_params(cfg) / 1e6:.1f}M params, "
          f"{len(jax.devices())} device(s)")

    rules = None
    param_sh = None
    if args.mesh:
        from repro.launch.mesh import make_mesh
        shape = tuple(int(x) for x in args.mesh.split(","))
        mesh = make_mesh(shape, ("data", "model", "stage")[:len(shape)])
        if len(shape) > 2 and shape[2] > 1:
            if cfg.grad_accum > 1:
                print("[launch.train] stage axis: grad-accum microbatches "
                      "route through the dist.pipeline schedule "
                      "(train_loop accum='auto')")
            else:
                print("[launch.train] note: stage axis without "
                      "--grad-accum > 1 holds replicas; pass --grad-accum "
                      "to pipeline microbatches over it")
        rules = model_zoo.make_rules(cfg, mesh)
        param_sh = logical_to_sharding(model_zoo.param_axes(cfg), rules,
                                       mesh)

    key = jax.random.PRNGKey(0)
    params = model_zoo.init_params(cfg, key)
    if param_sh is not None:
        params = jax.device_put(params, param_sh)
    opt_cfg = adamw.AdamWConfig(
        lr=args.lr, schedule=schedule.warmup_cosine(
            max(args.steps // 20, 1), args.steps))
    opt_state = adamw.init(params)

    data = SyntheticLM(cfg.vocab, args.seq, args.batch, seed=0,
                       frames=((cfg.n_frames, cfg.d_model)
                               if cfg.family == "audio" else None),
                       patches=((cfg.n_patches, cfg.d_model)
                                if cfg.family == "vlm" else None))

    step_fn = jax.jit(train_loop.make_train_step(cfg, opt_cfg, rules),
                      donate_argnums=(0, 1))
    trainer = train_loop.Trainer(
        step_fn, data,
        train_loop.TrainerConfig(ckpt_dir=args.ckpt_dir,
                                 ckpt_every=args.ckpt_every, log_every=10))
    start, params, opt_state = trainer.maybe_resume(params, opt_state)
    if start >= args.steps:
        print("[launch.train] checkpoint is already past --steps; done")
        return
    params, opt_state, metrics = trainer.run(
        params, opt_state, start_step=start, steps=args.steps - start)
    print(f"[launch.train] finished at loss {float(metrics['loss']):.4f}; "
          f"stragglers flagged: {len(trainer.straggler_steps)}")


if __name__ == "__main__":
    main()
