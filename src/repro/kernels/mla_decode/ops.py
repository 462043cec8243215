"""Jitted entry point of the latent-attention decode kernel (the model
dispatches between it and ``ref.py`` through ``rt.kernel_choice``).

``BLOCK`` positions are read per grid step; a cache longer than that
must hold a whole number of blocks (``models/mla.latent_len`` sizes the
latent cache so)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import mla_decode_fwd

BLOCK = 512


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_decode_pallas(q_lat, q_pe, c_kv, k_pe, slot_pos, pos, *, scale: float,
                      interpret: bool = False):
    """The kernel over each row's positions 0 .. pos[b] (all S once a
    row's position has passed the cache's length)."""
    B, S = slot_pos.shape
    lens = jnp.minimum(pos + 1, S).astype(jnp.int32)
    return mla_decode_fwd(q_lat, q_pe, c_kv, jnp.swapaxes(k_pe, 1, 2),
                          slot_pos.reshape(B, 1, S),
                          lens, pos.astype(jnp.int32), scale=scale,
                          bs=min(BLOCK, S), interpret=interpret)

