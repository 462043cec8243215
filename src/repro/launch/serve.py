"""Offloaded serving launcher (post-deployment stage, Sec 3.2).

    PYTHONPATH=src python -m repro.launch.serve --arch olmoe-mini \
        --ckpt checkpoints/olmoe-mini_melinoe.ckpt --capacity 8 --policy gamma

Loads a checkpoint, optionally trains/loads the activation predictor,
and serves batched greedy requests through the offloaded expert cache,
reporting transfers and Eq.-3 modeled throughput.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..core.offload_engine import OffloadedMoEEngine
from ..core.predictor import (
    PromptEmbedder,
    init_predictor,
    predict_scores,
    train_predictor,
)
from ..data.synthetic import ClusterLM, SyntheticConfig
from ..inference.engine import routing_trace
from ..models.model import init_params
from ..training.checkpoint import load_checkpoint
from .compile_cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmoe-mini")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--capacity", type=int, default=0, help="0 => E/4")
    ap.add_argument("--policy", default="gamma", choices=["lru", "lfu", "gamma"])
    ap.add_argument("--quantized", action="store_true")
    ap.add_argument("--predictor", action="store_true", help="train + use Psi prefetch")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--n-train-prompts", type=int, default=32)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    assert cfg.has_router, "offloaded serving applies to MoE architectures"
    if args.ckpt:
        from ..models.model import param_shapes

        like = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg, jnp.float32))
        params, _, meta = load_checkpoint(args.ckpt, like)
        print(f"loaded {args.ckpt} ({meta})")
    else:
        params = init_params(jax.random.key(0), cfg, jnp.float32)
        print("using randomly initialized weights (demo mode)")

    capacity = args.capacity or cfg.melinoe_cache_capacity()
    lm = ClusterLM(SyntheticConfig(vocab=cfg.vocab, seq_len=args.prompt_len, seed=3))
    rng = np.random.default_rng(0)
    prompts = np.stack(
        [lm.sample_sequence(rng)[0] for _ in range(args.batch)]
    ).astype(np.int32)

    engine = OffloadedMoEEngine(
        cfg, params, capacity=capacity, policy=args.policy,
        quantized=args.quantized,
    )

    if args.predictor:
        emb = PromptEmbedder(cfg.vocab)
        tr_prompts = np.stack(
            [lm.sample_sequence(rng)[0] for _ in range(args.n_train_prompts)]
        ).astype(np.int32)
        _, probs = routing_trace(cfg, params, tr_prompts, max_new=16)
        targets = jnp.asarray(probs.mean(axis=2))  # (N, L, E)
        embs = jnp.stack([emb(jnp.asarray(p)) for p in tr_prompts])
        pp = init_predictor(jax.random.key(1), targets.shape[1], targets.shape[2])
        pp, hist = train_predictor(pp, embs, targets)
        print(f"predictor KL {hist[0]:.4f} -> {hist[-1]:.4f}")
        scores = predict_scores(pp, emb(jnp.asarray(prompts)).mean(0))
        engine.prefetch(scores)

    res = engine.generate(prompts, max_new_tokens=args.max_new)
    m = res["metrics"]
    print(f"generated {m.decode_tokens} tokens x batch {args.batch}")
    print(f"transfers={m.transfers} ({res['transfers_per_layer']:.1f}/layer), "
          f"prefetch={m.prefetch_transfers}")
    print(f"hit rate={res['cache_stats'].hit_rate:.3f}")
    print(f"modeled throughput={res['throughput_tok_s']:.2f} tok/s "
          f"(hw={engine.hw.name}, Eq. 3)")


if __name__ == "__main__":
    main()
