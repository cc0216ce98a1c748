"""Training launcher.

Single-host (CPU/virtual devices) or multi-host (real cluster):

    PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-1.8b \
        --shape train_4k --steps 1000 --grad-comms hier --ckpt /ckpt/run1

Multi-host initialization is driven by the standard env variables
(COORDINATOR_ADDRESS, NUM_PROCESSES, PROCESS_ID) or Slurm via
``jax.distributed.initialize()`` auto-detection — see slurm_train.sbatch.
"""
import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    from repro.train.steps import GRAD_COMMS_MODES
    ap.add_argument("--grad-comms", default="auto",
                    choices=GRAD_COMMS_MODES,
                    help="'auto' = GSPMD; otherwise the transport a "
                         "CommSpec binds to the batch-axis Communicator; "
                         "'<transport>_overlap' double-buffers the "
                         "exchange behind the next microbatch's compute")
    ap.add_argument("--moe-comms", default="",
                    choices=("", "native", "tree", "serial", "hier",
                             "hier_int8"),
                    help="transport for the expert-parallel MoE "
                         "dispatch/combine all-to-all (default: the "
                         "arch config's moe_comms, usually 'native')")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-scale smoke)")
    ap.add_argument("--mesh", default="",
                    help="'data,model[,pod]' (default: production mesh "
                         "when enough devices, else auto-factored)")
    ap.add_argument("--distributed", action="store_true",
                    help="call jax.distributed.initialize() (multi-host)")
    args = ap.parse_args()

    if args.distributed:
        import jax
        jax.distributed.initialize()

    import jax
    from repro.configs.base import SHAPES, ShapeSpec, get_config, reduced
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import (make_local_mesh, make_production_mesh,
                                   mesh_for_devices)
    from repro.train.trainer import Trainer, TrainerConfig

    enable_compile_cache()
    cfg = get_config(args.arch)
    shape = SHAPES[args.shape]
    if args.reduced:
        cfg = reduced(cfg)
        shape = ShapeSpec("reduced", "train", 128, 8)
    if args.moe_comms:
        import dataclasses
        cfg = dataclasses.replace(cfg, moe_comms=args.moe_comms)

    n = len(jax.devices())
    if args.mesh:
        parts = [int(x) for x in args.mesh.split(",")]
        mesh = make_local_mesh(*parts)
    elif n >= 512:
        mesh = make_production_mesh(multi_pod=True)
    elif n >= 256:
        mesh = make_production_mesh()
    else:
        mesh = mesh_for_devices(n)
    print(f"[launch] devices={n} mesh={dict(mesh.shape)}")

    trainer = Trainer(cfg, shape, mesh, TrainerConfig(
        total_steps=args.steps, checkpoint_every=args.checkpoint_every,
        ckpt_dir=args.ckpt, grad_comms=args.grad_comms))
    out = trainer.run(resume=True)
    print(f"[launch] done; final loss {out['final_loss']}")


if __name__ == "__main__":
    main()
