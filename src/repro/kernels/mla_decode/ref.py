"""Pure-jnp oracle for the latent-attention decode kernel: the whole
score row per head, masked, and a plain softmax."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def mla_decode_ref(q_lat, q_pe, c_kv, k_pe, slot_pos, pos, *, scale: float):
    """q_lat (B,H,R), q_pe (B,H,P); c_kv (B,S,R), k_pe (B,S,P);
    slot_pos (B,S) (-1 = never written); pos (B,) -> (B,H,R) float32."""
    f32 = jnp.float32
    s = (jnp.einsum("bhr,bsr->bhs", q_lat.astype(f32), c_kv.astype(f32))
         + jnp.einsum("bhp,bsp->bhs", q_pe.astype(f32), k_pe.astype(f32))) * scale
    valid = (slot_pos >= 0) & (slot_pos <= pos[:, None])  # (B, S)
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bsr->bhr", p, c_kv.astype(f32))
