"""Pure-jnp oracle for the flash-attention kernel: materialized-scores
causal attention with GQA, softcap and sliding window."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def attention_ref(q, k, v, *, softcap: Optional[float] = None,
                  window: Optional[int] = None, scale: Optional[float] = None):
    """q (B,T,Hkv,G,hd); k (B,S,Hkv,hd); v (B,S,Hkv,hv) ->
    (B,T,Hkv,G,hv). Scores are scaled by ``scale``, default hd**-0.5."""
    B, T, Hkv, G, hd = q.shape
    S = k.shape[1]
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (hd**-0.5 if scale is None else scale)
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    qp = jnp.arange(T)[:, None]
    kp = jnp.arange(S)[None, :]
    mask = kp <= qp
    if window is not None:
        mask &= kp > qp - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)
