"""Distributed training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --steps 200 --batch 8 --seq 256 [--reduced] [--host-mesh]

On a real TPU slice this runs the same FSDP+TP rules the dry-run proves out
(make_production_mesh); on the CPU container use --reduced --host-mesh for
an end-to-end (if small) distributed run over host devices.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.data.pipeline import make_train_batches
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.sharding import TRAIN_RULES, set_rules
from repro.training.train_loop import TrainLoopConfig, train


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true",
                    help="use the CPU-smoke reduced config")
    ap.add_argument("--host-mesh", action="store_true",
                    help="mesh over host devices instead of production mesh")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    loop_cfg = TrainLoopConfig(
        num_steps=args.steps, batch_size=args.batch, seq_len=args.seq,
        lr=args.lr, ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)

    batches = make_train_batches(cfg, args.batch, args.seq)

    if args.host_mesh or jax.device_count() > 1:
        mesh = (make_host_mesh() if args.host_mesh
                else make_production_mesh(multi_pod=args.multi_pod))
        with jax.set_mesh(mesh):
            set_rules(TRAIN_RULES)
            try:
                out = train(cfg, loop_cfg, batches)
            finally:
                set_rules(None)
    else:
        out = train(cfg, loop_cfg, batches)

    final = out["history"][-1] if out["history"] else {}
    print("final:", final)


if __name__ == "__main__":
    main()
