"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
        --steps 50 --ckpt-dir /tmp/ckpt

On this CPU container ``--smoke`` shrinks the config to the reduced family
variant; on a real fleet the same entry point runs the full config on the
production mesh (``--mesh data,model=16,16``).  Fault tolerance, checkpoint
auto-resume, straggler detection and (optionally) gradient compression are
in the loop itself (runtime/train_loop.py).
"""
from __future__ import annotations

import argparse
import json

import jax

from repro.configs import ARCH_IDS, SHAPES_BY_NAME, get_config, smoke_variant
from repro.configs.base import ShapeConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh, single_device_mesh
from repro.optim import AdamWConfig
from repro.parallel.sharding import NAMED_RULES
from repro.runtime.train_loop import TrainLoop, TrainLoopConfig


def parse_mesh(spec: str):
    """'data,model=2,2' -> mesh with those axes/sizes."""
    axes, sizes = spec.split("=")
    axes = tuple(a.strip() for a in axes.split(","))
    sizes = tuple(int(s) for s in sizes.split(","))
    return make_mesh(sizes, axes)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--shape", default="train_4k", choices=sorted(SHAPES_BY_NAME))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config + small shape (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--mesh", default=None, help="e.g. 'data,model=16,16'")
    ap.add_argument("--rules", default="fsdp_tp", choices=sorted(NAMED_RULES))
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--metrics", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--crash-at", type=int, default=None,
                    help="failure-injection drill: crash at this step")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    shape = SHAPES_BY_NAME[args.shape]
    if args.smoke:
        cfg = smoke_variant(cfg)
        shape = ShapeConfig(
            "smoke",
            seq_len=args.seq_len or 128,
            global_batch=args.batch or 8,
            kind="train",
        )
    elif args.seq_len or args.batch:
        shape = ShapeConfig(
            shape.name,
            seq_len=args.seq_len or shape.seq_len,
            global_batch=args.batch or shape.global_batch,
            kind="train",
        )

    mesh = parse_mesh(args.mesh) if args.mesh else single_device_mesh()
    rules = NAMED_RULES[args.rules]

    loop = TrainLoop(
        cfg,
        shape,
        mesh,
        rules,
        TrainLoopConfig(
            steps=args.steps,
            ckpt_every=args.ckpt_every,
            ckpt_dir=args.ckpt_dir,
            metrics_path=args.metrics,
            crash_at_step=args.crash_at,
            seed=args.seed,
        ),
        opt_cfg=AdamWConfig(lr=args.lr, total_steps=max(args.steps, 10)),
    )
    result = loop.run()
    print(json.dumps({
        "arch": args.arch,
        "final_step": result["final_step"],
        "final_loss": result["final_loss"],
        "straggler_events": result["straggler_events"],
        "devices": jax.device_count(),
    }, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
