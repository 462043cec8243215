"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch olmoe-mini --steps 200

On the CPU container this drives the reduced configs; on a real cluster
the same entrypoint runs under the production mesh (--mesh single|multi)
with pjit sharding from distributed/sharding.py.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp

from ..configs import get_config
from ..data.synthetic import ClusterLM, SyntheticConfig
from ..models.runtime import Runtime
from ..training.checkpoint import save_checkpoint
from ..training.optim import OptConfig
from ..training.trainer import melinoe_finetune, pretrain
from .compile_cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmoe-mini")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mode", choices=["pretrain", "finetune", "both"], default="both")
    ap.add_argument("--ft-steps", type=int, default=100)
    ap.add_argument("--out", default="checkpoints")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    rt = Runtime()
    lm = ClusterLM(SyntheticConfig(vocab=cfg.vocab, seq_len=args.seq, seed=args.seed))
    out = Path(args.out)

    res = None
    if args.mode in ("pretrain", "both"):
        res = pretrain(
            cfg, lm.batches(args.batch, seed=args.seed + 1), steps=args.steps,
            opt_cfg=OptConfig(peak_lr=args.lr, total_steps=args.steps, weight_decay=0.01),
            rt=rt, seed=args.seed,
        )
        save_checkpoint(out / f"{cfg.name}_base.ckpt", res.params, step=args.steps,
                        metadata={"arch": cfg.name, "stage": "pretrain"})
        (out / f"{cfg.name}_base_history.json").write_text(json.dumps(res.history))

    if args.mode in ("finetune", "both") and cfg.has_router:
        assert res is not None, "finetune mode requires --mode both here"
        ft = melinoe_finetune(
            cfg, res.params, lm.batches(args.batch, seed=args.seed + 2),
            steps=args.ft_steps, rt=rt, seed=args.seed,
        )
        save_checkpoint(out / f"{cfg.name}_melinoe.ckpt", (ft.params, ft.lora),
                        step=args.ft_steps, metadata={"arch": cfg.name, "stage": "melinoe"})
        (out / f"{cfg.name}_melinoe_history.json").write_text(json.dumps(ft.history))
    print("done")


if __name__ == "__main__":
    main()
