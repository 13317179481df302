"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On a real cluster: run under the production mesh (--mesh 16x16) with one
process per host; this CPU container runs 1x1.
"""
from __future__ import annotations

import argparse

import jax

from ..compile_cache import enable_compile_cache
from ..configs import get_config, list_archs, smoke_config
from ..data import MMapTokens, SyntheticTokens
from ..distributed.sharding import ShardingPolicy
from ..models import build_model
from ..optim import AdamW, AdamW8bit, warmup_cosine
from ..train import TrainConfig, Trainer
from .mesh import make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 16x16")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--sp", action="store_true")
    ap.add_argument("--opt", default="adamw", choices=["adamw", "adamw8bit"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--data", default="synthetic",
                    help="'synthetic' or a path to a flat token file")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-step-time", type=float, default=None)
    args = ap.parse_args()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    print(f"[train] {cfg.name}: {model.n_params/1e6:.1f}M params")
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    policy = ShardingPolicy(fsdp=args.fsdp, sp=args.sp)
    opt_cls = {"adamw": AdamW, "adamw8bit": AdamW8bit}[args.opt]
    opt = opt_cls(lr=warmup_cosine(args.lr, args.warmup, args.steps))
    if args.data == "synthetic":
        data = SyntheticTokens(cfg, args.batch, args.seq, seed=args.seed)
    else:
        data = MMapTokens(args.data, cfg, args.batch, args.seq,
                          seed=args.seed)
    tc = TrainConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                     ckpt_every=args.ckpt_every,
                     max_step_time=args.max_step_time)
    trainer = Trainer(model, opt, policy, mesh, data, tc)
    _, log = trainer.run()
    print(f"[train] done: {log[-1]}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
