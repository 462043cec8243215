"""Pallas TPU kernel: causal flash attention (forward), GQA-aware.

Grid (B, Hkv, nq) with the q-chunk dimension parallel and an inner
fori_loop over KV chunks; online-softmax running stats (m, l) and the
output accumulator live in VMEM scratch. Only the causally-visible KV
chunks are visited per q chunk (no masked-rectangle waste — unlike the
pure-JAX fallback, which computes the full rectangle under scan).

Supports: GQA (G q-heads per kv head processed together as a (G*bq, hd)
block), score softcap (gemma2), sliding-window masking.

Layouts: q (B, T, Hkv, G, hd); k/v (B, S, Hkv, hd); out like q. Inside
the call the kv-head axis leads the token axis, so every block's last
two dims are (rows, hd): the rows are a multiple of 8 and hd is the
whole dim, as the TPU's (8, 128) tiling requires. ``T`` and ``S`` must
be multiples of ``bq`` and ``bk`` (``ops.py`` pads and slices back).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, G: int, n_kv: int, scale: float,
            softcap: Optional[float], window: Optional[int], out_dtype):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)  # (bq*G, hd), row = t*G + g

    m_ref[...] = jnp.full_like(m_ref, NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = jax.lax.broadcasted_iota(jnp.int32, (bq * G, 1), 0)
    q_pos = qi * bq + rows // G  # (bq*G, 1) token position of each row
    # visit kv chunks up to the causal frontier (and within the window)
    hi = jnp.minimum((qi + 1) * bq, n_kv * bk)
    n_vis = pl.cdiv(hi, bk)
    lo = 0
    if window is not None:
        lo = jnp.maximum((qi * bq - window + 1) // bk, 0)

    def body(j, _):
        start = pl.multiple_of(j * bk, bk)
        k = k_ref[0, 0, pl.ds(start, bk), :].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, 0, pl.ds(start, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # (bq*G, bk)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)  # (1,bk)
        mask = k_pos <= q_pos  # causal, per q row
        if window is not None:
            mask &= k_pos > (q_pos - window)
        s = jnp.where(mask, s, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        return 0

    jax.lax.fori_loop(lo, n_vis, body, 0)
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    o_ref[0, 0] = out.astype(out_dtype)


def flash_attention_fwd(
    q: jax.Array,  # (B, T, Hkv, G, hd)
    k: jax.Array,  # (B, S, Hkv, hd)
    v: jax.Array,
    *,
    softcap: Optional[float] = None,
    window: Optional[int] = None,
    bq: int = 256,
    bk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, T, Hkv, G, hd = q.shape
    S = k.shape[1]
    if T % bq or S % bk or bk % 8 or (bq * G) % 8:
        raise ValueError(f"unaligned flash tiles: T={T} bq={bq} S={S} "
                         f"bk={bk} G={G}")
    n_q = T // bq
    n_kv = S // bk
    scale = hd**-0.5
    # kv-head-major, token-major rows: the MXU sees one (bq*G, hd) matmul
    # per chunk; row index = t*G + g
    qf = q.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, T * G, hd)
    kf = k.transpose(0, 2, 1, 3)  # (B, Hkv, S, hd)
    vf = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _kernel, bq=bq, bk=bk, G=G, n_kv=n_kv, scale=scale, softcap=softcap,
        window=window, out_dtype=q.dtype,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, n_q),
        in_specs=[
            pl.BlockSpec((1, 1, bq * G, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, S, hd), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, S, hd), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq * G, hd), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, T * G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq * G, 1), jnp.float32),
            pltpu.VMEM((bq * G, 1), jnp.float32),
            pltpu.VMEM((bq * G, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="flash_attn",
    )(qf, kf, vf)
    # (B, Hkv, T*G, hd) -> (B, T, Hkv, G, hd)
    return out.reshape(B, Hkv, T, G, hd).transpose(0, 2, 1, 3, 4)
