"""Block zoo: init/apply for each block kind, full-sequence and decode."""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..configs.base import BlockSpec, MLASpec, ModelConfig
from . import attention as attn_mod
from . import mamba2 as mamba_mod
from .attention import KVCache, attend_full, cache_from_prefill, decode_attend, init_attn
from .common import rms_norm, rms_norm_init
from .mamba2 import MambaState, apply_mamba_decode, apply_mamba_full, init_mamba
from .mla import (init_latent_cache, init_mla, latent_cache_from_prefill,
                  mla_decode_step, mla_full)
from .mlp import apply_mlp, init_mlp
from .moe import apply_moe, init_moe, router_probs
from .runtime import Runtime


def init_block(key, cfg: ModelConfig, b: BlockSpec, dtype):
    """``shared_attn`` blocks are NOT initialized here (they live in the
    model's shared subtree and are referenced by every occurrence)."""
    ks = jax.random.split(key, 2)
    p: dict = {"ln1": rms_norm_init(cfg.d_model, dtype)}
    if b.kind == "mamba":
        p["mixer"] = init_mamba(ks[0], cfg.d_model, b.ssm, dtype)
        return p
    init_mixer = init_mla if isinstance(b.attn, MLASpec) else init_attn
    p["mixer"] = init_mixer(ks[0], cfg.d_model, b.attn, dtype)
    p["ln2"] = rms_norm_init(cfg.d_model, dtype)
    if b.kind == "attn_moe":
        p["ffn"] = init_moe(ks[1], cfg.d_model, b.moe, dtype)
    else:
        p["ffn"] = init_mlp(ks[1], cfg.d_model, b.d_ff, dtype)
    return p


def effective_window(b: BlockSpec, window_override: Optional[int]) -> Optional[int]:
    if b.attn is None:
        return None
    if isinstance(b.attn, MLASpec):
        if window_override is not None:
            raise NotImplementedError("MLASpec attention has no sliding window")
        return None
    w = b.attn.window
    if window_override is not None:
        w = min(w, window_override) if w is not None else window_override
    return w


class BlockAux(NamedTuple):
    probs: Optional[jax.Array] = None  # router distribution (B, T, E)
    moe_h: Optional[jax.Array] = None  # hidden states fed to the router
    kv: Optional[Any] = None  # KVCache / MambaState for prefill


def apply_block_full(
    params,
    cfg: ModelConfig,
    b: BlockSpec,
    x,
    positions,
    rt: Runtime,
    *,
    window_override: Optional[int] = None,
    want_cache: bool = False,
    cache_slots: int = 0,
    want_probs: bool = False,
    lora=None,
    lora_scale: float = 1.0,
) -> tuple:
    """Full-sequence (train / prefill) application. x (B, T, d)."""
    aux = {}
    h = rms_norm(params["ln1"], x, cfg.norm_eps)
    if b.kind == "mamba":
        if want_cache:
            y, state = apply_mamba_full(params["mixer"], h, b.ssm,
                                        return_state=True, rt=rt)
            aux["kv"] = state
        else:
            y = apply_mamba_full(params["mixer"], h, b.ssm, rt=rt)
        x = x + y
        return x, aux

    w = effective_window(b, window_override)
    if isinstance(b.attn, MLASpec):
        y, (c_kv, k_pe) = mla_full(params["mixer"], b.attn, h, positions, rt)
        if want_cache:
            aux["kv"] = latent_cache_from_prefill(c_kv, k_pe, b.attn,
                                                  cache_slots or c_kv.shape[1])
    elif want_cache:
        y, (k, v) = attend_full(params["mixer"], b.attn, h, positions, w,
                                return_kv=True, rt=rt)
        aux["kv"] = cache_from_prefill(k, v, b.attn, cache_slots or k.shape[1])
    else:
        y = attend_full(params["mixer"], b.attn, h, positions, w, rt=rt)
    x = x + y
    x = rt.constrain(x, rt.batch_spec_entry())

    h2 = rms_norm(params["ln2"], x, cfg.norm_eps)
    if b.kind == "attn_moe":
        B, T, dm = h2.shape
        h2f = h2.reshape(B * T, dm)
        probs = router_probs(params["ffn"], h2f, b.moe)
        y2, _ = apply_moe(params["ffn"], h2f, b.moe, rt, lora=lora,
                          lora_scale=lora_scale, probs=probs)
        y2 = y2.reshape(B, T, dm)
        if want_probs:
            aux["probs"] = probs.reshape(B, T, -1)
            aux["moe_h"] = h2
    else:
        y2 = apply_mlp(params["ffn"], h2)
    x = x + y2
    return rt.constrain(x, rt.batch_spec_entry()), aux


def apply_block_decode(
    params,
    cfg: ModelConfig,
    b: BlockSpec,
    x,
    cache,
    pos,
    rt: Runtime,
    *,
    window_override: Optional[int] = None,
    want_probs: bool = False,
    lora=None,
    lora_scale: float = 1.0,
) -> tuple:
    """Single-token step. x (B, 1, d); cache is this block's state."""
    aux = {}
    h = rms_norm(params["ln1"], x, cfg.norm_eps)
    if b.kind == "mamba":
        y, new_state = apply_mamba_decode(params["mixer"], h, cache, b.ssm)
        return x + y, new_state, aux

    w = effective_window(b, window_override)
    if isinstance(b.attn, MLASpec):
        y, new_cache = mla_decode_step(params["mixer"], b.attn, h, cache, pos, rt)
    else:
        y, new_cache = decode_attend(params["mixer"], b.attn, h, cache, pos, w)
    x = x + y
    h2 = rms_norm(params["ln2"], x, cfg.norm_eps)
    if b.kind == "attn_moe":
        B, T, dm = h2.shape
        h2f = h2.reshape(B * T, dm)
        probs = router_probs(params["ffn"], h2f, b.moe)
        rt_d = rt if rt.zero_drop else dataclasses.replace(rt, zero_drop=True)
        y2, _ = apply_moe(params["ffn"], h2f, b.moe, rt_d, lora=lora,
                          lora_scale=lora_scale, probs=probs)
        y2 = y2.reshape(B, T, dm)
        if want_probs:
            aux["probs"] = probs.reshape(B, T, -1)
    else:
        y2 = apply_mlp(params["ffn"], h2)
    return x + y2, new_cache, aux


def init_block_cache(cfg: ModelConfig, b: BlockSpec, batch: int, n_slots: int,
                     window_override: Optional[int], dtype):
    if b.kind == "mamba":
        s = b.ssm
        return MambaState(
            conv=jnp.zeros((batch, s.d_conv - 1, mamba_mod.conv_dim(s, cfg.d_model)), dtype),
            ssm=jnp.zeros((batch, s.n_heads(cfg.d_model), s.head_dim, s.d_state), jnp.float32),
        )
    if isinstance(b.attn, MLASpec):
        return init_latent_cache(batch, n_slots, b.attn, dtype)
    w = effective_window(b, window_override)
    slots = min(n_slots, w) if w is not None else n_slots
    return attn_mod.init_kv_cache(batch, slots, b.attn, dtype)
