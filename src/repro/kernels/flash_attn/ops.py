"""Dispatching wrapper for the flash-attention kernel.

NOTE: this kernel keeps the full K/V for one kv-head resident in VMEM
(block = (1, 1, S, hd)) — correct and MXU-aligned for S*hd*4B within the
~16 MB VMEM budget (S <= ~8k at hd=128, <= ~16k at hd=64). Longer
sequences use the pure-JAX blockwise path in models/attention.py, which
streams KV from HBM; a production double-buffered DMA variant is the
natural next kernel iteration. ``supported()`` encodes that envelope so
``auto`` dispatch can bail out to the reference path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..dispatch import KernelChoice, record, resolve
from .kernel import flash_attention_fwd
from .ref import attention_ref

# VMEM envelope for the compiled kernel: one kv-head's K+V in fp32 plus
# headroom for q/out/scratch must fit in ~16 MB.
_VMEM_KV_BUDGET = 8 * 1024 * 1024


def supported(q_shape, k_shape, interpret: bool) -> bool:
    """Can the kernel handle these shapes? (interpret mode: always;
    compiled: KV for one head must fit the VMEM residency budget)."""
    if interpret:
        return True
    B, S, Hkv, hd = k_shape
    return 2 * S * hd * 4 <= _VMEM_KV_BUDGET


def _tile(n: int, pref: int):
    """(tile, padded length): the tile is a multiple of 8 and at most
    ``pref``; the length is padded up to a whole number of tiles."""
    n8 = -(-n // 8) * 8
    t = min(pref, n8)
    return t, -(-n8 // t) * t


@functools.partial(jax.jit, static_argnames=("softcap", "window", "bq", "bk",
                                             "interpret"))
def flash_pallas(q, k, v, softcap: Optional[float] = None,
                 window: Optional[int] = None, bq: int = 256, bk: int = 256,
                 interpret: bool = False):
    """The kernel at any T <= S: q and k start at the same position
    (self-attention prefill). T and S are padded to whole tiles; the
    padded keys sit past every real query, so the causal mask hides
    them, and the padded query rows are sliced off."""
    T, S = q.shape[1], k.shape[1]
    if T > S or bq % 8 or bk % 8:
        raise ValueError(f"flash_pallas needs T <= S and tiles that are "
                         f"multiples of 8: T={T} S={S} bq={bq} bk={bk}")
    bq, Tp = _tile(T, bq)
    bk, Sp = _tile(S, bk)
    q = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    out = flash_attention_fwd(q, k, v, softcap=softcap, window=window,
                              bq=bq, bk=bk, interpret=interpret)
    return out[:, :T]


def flash(q, k, v, *, softcap: Optional[float] = None,
          window: Optional[int] = None, bq: int = 256, bk: int = 256,
          interpret: Optional[bool] = None, use_ref: bool = False,
          backend: Optional[str] = None):
    """Causal GQA attention. q (B,T,Hkv,G,hd); k/v (B,S,Hkv,hd)."""
    choice = resolve("flash_attn", backend or ("ref" if use_ref else "pallas"),
                     interpret=interpret)
    if choice.use_pallas and not supported(q.shape, k.shape, choice.interpret):
        choice = KernelChoice("ref", False)
    record("flash_attn", choice)
    if not choice.use_pallas:
        return attention_ref(q, k, v, softcap=softcap, window=window)
    return flash_pallas(q, k, v, softcap, window, bq, bk, choice.interpret)
