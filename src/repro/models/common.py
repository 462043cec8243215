"""Shared layer primitives: RMSNorm, RoPE, init helpers, dtype policy."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def cdtype(cfg_dtype: str):
    return jnp.dtype(cfg_dtype)


# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------


def dense_init(key, in_dim: int, out_dim: int, dtype) -> Array:
    """Truncated-normal fan-in init (stddev 1/sqrt(in_dim))."""
    std = in_dim**-0.5
    return (jax.random.truncated_normal(key, -3, 3, (in_dim, out_dim), jnp.float32) * std).astype(
        dtype
    )


def embed_init(key, vocab: int, dim: int, dtype) -> Array:
    return (jax.random.truncated_normal(key, -3, 3, (vocab, dim), jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rms_norm_init(dim: int, dtype) -> Array:
    return jnp.zeros((dim,), dtype)  # (1 + w) parameterization, gemma-style


def rms_norm(w: Array, x: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction ``0.1 * mscale * ln(factor) + 1``
    (1 where the context is not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(head_dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN inverse frequencies (``yarn`` a ``YarnSpec``): the plain
    rotary frequencies below the correction range, divided by
    ``yarn.factor`` above it, and a linear blend in between. The range
    runs from the dimension that turns ``beta_fast`` times over the
    original context (floored) to the one that turns ``beta_slow``
    times (ceiled)."""
    def dim_for(n_rot):
        return (head_dim * math.log(yarn.original_max_position_embeddings
                                    / (n_rot * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(dim_for(yarn.beta_fast)), 0)
    hi = min(math.ceil(dim_for(yarn.beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    extra = rope_freqs(head_dim, theta)
    return (extra * (1.0 - ramp) + extra / yarn.factor * ramp).astype(np.float32)


def apply_rope(x: Array, positions: Array, theta: float,
               rotate_in_input_dtype: bool = False,
               inv_freq: Optional[np.ndarray] = None) -> Array:
    """x: (..., T, H, head_dim); positions: broadcastable to (..., T).

    Angles are always computed in f32 (bf16 cannot represent large
    positions). ``rotate_in_input_dtype`` performs the rotation itself in
    x.dtype so no f32 copy of the rotated tensor ever exists — used by
    the decode path to stop XLA promoting the KV-cache update to f32
    (EXPERIMENTS.md §Perf). ``inv_freq`` replaces the plain frequencies
    (YaRN)."""
    head_dim = x.shape[-1]
    if inv_freq is None:
        inv_freq = rope_freqs(head_dim, theta)
    freqs = jnp.asarray(inv_freq)  # (hd/2,)
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # (..., T, 1, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if rotate_in_input_dtype:
        cos = cos.astype(x.dtype)
        sin = sin.astype(x.dtype)
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(x: Array, cap: Optional[float]) -> Array:
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


def silu(x: Array) -> Array:
    return x * jax.nn.sigmoid(x)
