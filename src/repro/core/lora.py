"""LoRA adapters for expert up/down projections + the MELINOE trainable
partition (paper Sec 3.1.1: full updates on router weights and expert
gate projections; LoRA rank-32 on expert up/down; everything else frozen).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..configs.base import MelinoeSpec, ModelConfig
from ..models.common import dense_init

LORA_TARGETS = ("wu", "wd")  # expert up / down projections


def lora_scale(spec: MelinoeSpec) -> float:
    return spec.lora_alpha / spec.lora_rank


def init_lora(key, cfg: ModelConfig, spec: MelinoeSpec, dtype=jnp.float32):
    """Returns a pytree mirroring params["groups"], containing adapters
    only at MoE positions: {g: {p: {"wu": {"a","b"}, "wd": {"a","b"}}}}.

    a ~ N(0, 1/d); b = 0 (standard LoRA init: delta starts at zero)."""
    r = spec.lora_rank
    tree: Dict[str, Any] = {}
    for gi, g in enumerate(cfg.layout):
        gtree: Dict[str, Any] = {}
        for pi, bname in enumerate(g.pattern):
            b = cfg.block_defs[bname]
            if b.moe is None:
                continue
            E, d, f = b.moe.held, cfg.d_model, b.moe.d_ff  # experts held here
            dims = {"wu": (d, f), "wd": (f, d)}
            ptree = {}
            for t in LORA_TARGETS:
                din, dout = dims[t]
                k1 = jax.random.fold_in(key, hash((gi, pi, t)) % (2**31))
                ks = jax.random.split(k1, g.repeats * E).reshape(g.repeats, E)
                a = jax.vmap(jax.vmap(lambda kk: dense_init(kk, din, r, dtype)))(ks)
                ptree[t] = {
                    "a": a,  # (R, E, din, r)
                    "b": jnp.zeros((g.repeats, E, r, dout), dtype),
                }
            gtree[f"p{pi}"] = ptree
        tree[f"g{gi}"] = gtree
    return tree


# ---------------------------------------------------------------------------
# Trainable partition for MELINOE fine-tuning
# ---------------------------------------------------------------------------


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def melinoe_trainable_mask(params) -> Any:
    """Bool pytree: True for router weights and expert gate projections
    (full update); everything else in the base params is frozen.
    LoRA params are trained in full (handled as a separate tree)."""

    def mark(path, leaf):
        s = _path_str(path)
        if "/ffn/router" in s:
            return True
        # expert gate projection: ffn/wg (stacked per expert). Exclude the
        # dense-MLP wg (non-MoE blocks) by requiring 3+ dims (E, d, f).
        if s.endswith("/ffn/wg") and hasattr(leaf, "ndim") and leaf.ndim >= 4:
            return True
        return False

    return jax.tree_util.tree_map_with_path(mark, params)


def apply_mask(tree, mask, frozen_value=0.0):
    """Zero (or replace) leaves where mask is False — used to freeze grads."""
    return jax.tree.map(
        lambda g, m: g if m else jnp.zeros_like(g) if frozen_value == 0.0 else g * frozen_value,
        tree,
        mask,
    )


def extract_base_routers(params, cfg: ModelConfig):
    """Stacked frozen router weights per group/position for the
    same_trajectory rank-matching mode."""
    out = {}
    for gi, g in enumerate(cfg.layout):
        gname = f"g{gi}"
        gout = {}
        for pi, bname in enumerate(g.pattern):
            if cfg.block_defs[bname].moe is None:
                continue
            gout[f"p{pi}"] = jax.lax.stop_gradient(
                params["groups"][gname][f"p{pi}"]["ffn"]["router"]
            )
        out[gname] = gout
    return out
