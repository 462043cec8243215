"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434), without a
query LoRA.

Per token: ``x @ wq`` gives each head's ``q_nope`` and ``q_pe``;
``x @ wkv_a`` gives a latent ``c_kv`` (normalised by its own RMSNorm,
``kv_norm``) and one ``k_pe`` that every head shares; ``c_kv @ wkv_b``
gives each head's ``k_nope`` and ``v``. Rotary positions (YaRN-scaled
where the spec says so) act on ``q_pe`` and ``k_pe`` only, in the
repo's rotate-half layout.

The cache keeps only the latent: the normalised ``c_kv`` and the rotated
``k_pe``, ``latent_dim`` floats per token per layer (576 in DeepSeek-V2,
where grouped-query attention at its widths would keep 16 x 320).

Prefill expands ``k_nope`` and ``v`` per head for the prompt and runs
causal flash attention at q/k width ``qk_head_dim``, v width
``v_head_dim`` and the spec's softmax scale. Decode absorbs the key
half of ``wkv_b`` (W_UK) into the query and its value half (W_UV) into
the output, so that a step reads only the latent cache:
``scores = (q_nope W_UK^T) c_kv + q_pe k_pe`` and
``out = (softmax(scores) c_kv) W_UV``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..configs.base import MLASpec
from .common import (apply_rope, dense_init, rms_norm, rms_norm_init,
                     rope_freqs, yarn_freqs, yarn_mscale)


class LatentCache(NamedTuple):
    c_kv: jax.Array  # (B, S, kv_lora_rank), normalised
    k_pe: jax.Array  # (B, S, qk_rope_head_dim), rotated
    slot_pos: jax.Array  # (B, S) int32 per row; -1 = empty


def init_mla(key, d_model: int, spec: MLASpec, dtype):
    ks = jax.random.split(key, 4)
    H, R = spec.n_heads, spec.kv_lora_rank
    return {
        "wq": dense_init(ks[0], d_model, H * spec.qk_head_dim, dtype),
        "wkv_a": dense_init(ks[1], d_model, spec.latent_dim, dtype),
        "kv_norm": rms_norm_init(R, dtype),
        "wkv_b": dense_init(ks[2], R, H * (spec.qk_nope_head_dim + spec.v_head_dim),
                            dtype),
        "wo": dense_init(ks[3], H * spec.v_head_dim, d_model, dtype),
    }


def softmax_scale(spec: MLASpec) -> float:
    """``qk_head_dim**-0.5``, times YaRN's ``m**2`` where it applies."""
    scale = spec.qk_head_dim**-0.5
    if spec.yarn is not None and spec.yarn.mscale_all_dim:
        scale *= yarn_mscale(spec.yarn.factor, spec.yarn.mscale_all_dim) ** 2
    return scale


def rope_inv_freq(spec: MLASpec):
    d = spec.qk_rope_head_dim
    if spec.yarn is None:
        return rope_freqs(d, spec.rope_theta)
    return yarn_freqs(d, spec.rope_theta, spec.yarn)


def _rope(x, positions, spec: MLASpec):
    """x (B, T, H, P) at positions (B, T); YaRN scales cos and sin by
    mscale(mscale) / mscale(mscale_all_dim), which is 1 in DeepSeek-V2."""
    out = apply_rope(x, positions, spec.rope_theta, inv_freq=rope_inv_freq(spec))
    y = spec.yarn
    if y is not None:
        c = yarn_mscale(y.factor, y.mscale) / yarn_mscale(y.factor, y.mscale_all_dim)
        if c != 1.0:
            out = out * jnp.asarray(c, out.dtype)
    return out


def _project(params, spec: MLASpec, x, positions):
    """x (B, T, d) -> q_nope (B,T,H,N), rotated q_pe (B,T,H,P), normalised
    c_kv (B,T,R), rotated k_pe (B,T,P)."""
    B, T, _ = x.shape
    N, R = spec.qk_nope_head_dim, spec.kv_lora_rank
    q = (x @ params["wq"]).reshape(B, T, spec.n_heads, spec.qk_head_dim)
    q_nope, q_pe = q[..., :N], _rope(q[..., N:], positions, spec)
    ckv = x @ params["wkv_a"]
    c_kv = rms_norm(params["kv_norm"], ckv[..., :R])
    k_pe = _rope(ckv[..., None, R:], positions, spec)[:, :, 0]
    return q_nope, q_pe, c_kv, k_pe


def latent_len(n_slots: int) -> int:
    """Cache positions for ``n_slots``: a multiple of 8, and past one
    decode-kernel block a whole number of blocks."""
    from ..kernels.mla_decode.ops import BLOCK

    align = BLOCK if n_slots > BLOCK else 8
    return -(-n_slots // align) * align


def init_latent_cache(batch: int, n_slots: int, spec: MLASpec, dtype) -> LatentCache:
    S = latent_len(n_slots)
    return LatentCache(
        c_kv=jnp.zeros((batch, S, spec.kv_lora_rank), dtype),
        k_pe=jnp.zeros((batch, S, spec.qk_rope_head_dim), dtype),
        slot_pos=jnp.full((batch, S), -1, jnp.int32),
    )


def latent_cache_from_prefill(c_kv, k_pe, spec: MLASpec, n_slots: int) -> LatentCache:
    """The prompt's latents at positions 0..T-1 of a cache of
    ``latent_len(n_slots)`` positions, which must hold them all."""
    B, T, _ = c_kv.shape
    cache = init_latent_cache(B, n_slots, spec, c_kv.dtype)
    if T > cache.c_kv.shape[1]:
        raise ValueError(f"a {T}-token prompt does not fit a latent cache of "
                         f"{cache.c_kv.shape[1]} positions")
    return LatentCache(
        c_kv=cache.c_kv.at[:, :T].set(c_kv),
        k_pe=cache.k_pe.at[:, :T].set(k_pe),
        slot_pos=cache.slot_pos.at[:, :T].set(jnp.arange(T)),
    )


def mla_full(params, spec: MLASpec, x, positions, rt):
    """Causal self-attention over the sequence: x (B, T, d) -> (B, T, d),
    and the (c_kv, k_pe) latents that a cache keeps."""
    from ..kernels import dispatch
    from ..kernels.flash_attn import ops as flash_ops
    from ..kernels.flash_attn.ref import attention_ref

    B, T, _ = x.shape
    H, N, V = spec.n_heads, spec.qk_nope_head_dim, spec.v_head_dim
    q_nope, q_pe, c_kv, k_pe = _project(params, spec, x, positions)
    kv = (c_kv @ params["wkv_b"]).reshape(B, T, H, N + V)
    q = jnp.concatenate([q_nope, q_pe], -1)  # (B, T, H, qk)
    k = jnp.concatenate(
        [kv[..., :N], jnp.broadcast_to(k_pe[:, :, None], (B, T, H, k_pe.shape[-1]))], -1)
    v = kv[..., N:]
    qg = q[:, :, :, None]  # one query head per key head: (B, T, H, 1, qk)
    scale = softmax_scale(spec)
    choice = rt.kernel_choice("flash_attn")
    if choice.use_pallas and not flash_ops.supported(q.shape, k.shape,
                                                     choice.interpret, v.shape):
        choice = dispatch.KernelChoice("ref", False)
    dispatch.record("flash_attn", choice)
    if choice.use_pallas:
        o = flash_ops.flash_pallas(qg, k, v, interpret=choice.interpret, scale=scale)
    else:
        o = attention_ref(qg, k, v, scale=scale)
    return o.reshape(B, T, H * V) @ params["wo"], (c_kv, k_pe)


def mla_decode_step(params, spec: MLASpec, x, cache: LatentCache, pos, rt):
    """x (B, 1, d); ``pos`` the new token's position, a scalar or one per
    row. Writes each row's latent at its own position (its ring slot
    ``pos % S``) and attends over the latent cache alone. Returns
    (out (B, 1, d), updated cache)."""
    from ..kernels import dispatch
    from ..kernels.mla_decode import ops as mla_ops
    from ..kernels.mla_decode.ref import mla_decode_ref

    B = x.shape[0]
    H, N, V, R = (spec.n_heads, spec.qk_nope_head_dim, spec.v_head_dim,
                  spec.kv_lora_rank)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q_nope, q_pe, c_new, kpe_new = _project(params, spec, x, pos[:, None])
    rows, slot = jnp.arange(B), pos % cache.c_kv.shape[1]
    cache = LatentCache(
        c_kv=cache.c_kv.at[rows, slot].set(c_new[:, 0]),
        k_pe=cache.k_pe.at[rows, slot].set(kpe_new[:, 0]),
        slot_pos=cache.slot_pos.at[rows, slot].set(pos),
    )
    w = params["wkv_b"].reshape(R, H, N + V)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w[..., :N])
    choice = rt.kernel_choice("mla_decode")
    dispatch.record("mla_decode", choice)
    args = (q_lat, q_pe[:, 0], cache.c_kv, cache.k_pe, cache.slot_pos, pos)
    if choice.use_pallas:
        o_lat = mla_ops.mla_decode_pallas(*args, scale=softmax_scale(spec),
                                          interpret=choice.interpret)
    else:
        o_lat = mla_decode_ref(*args, scale=softmax_scale(spec))
    o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(x.dtype), w[..., N:])
    return o.reshape(B, 1, H * V) @ params["wo"], cache
