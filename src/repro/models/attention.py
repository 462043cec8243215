"""Grouped-query attention with blockwise online-softmax (pure-JAX flash).

Supports: GQA, RoPE, qk-RMSNorm (qwen3/olmoe), score softcap (gemma2),
sliding-window masking, and a *banded* path that only touches the KV
chunks inside the window (so windowed layers don't pay quadratic FLOPs).

Layouts: x (B, T, d); q (B, T, Hq, hd); k/v (B, S, Hkv, hd).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import AttnSpec
from .common import apply_rope, dense_init, rms_norm, rms_norm_init, softcap

NEG = -1e30

# §Perf optimization toggles (baseline = False; flipped by the hillclimb
# harness via repro.models.attention.set_opt_flags or REPRO_OPT env)
import os as _os

_OPT_DECODE_NO_F32_CACHE = "decode_no_f32_cache" in _os.environ.get("REPRO_OPT", "")


def set_opt_flags(**kw):
    g = globals()
    for k, v in kw.items():
        key = "_OPT_" + k.upper()
        assert key in g, key
        g[key] = v


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init_attn(key, d_model: int, spec: AttnSpec, dtype):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, spec.q_dim, dtype),
        "wk": dense_init(ks[1], d_model, spec.kv_dim, dtype),
        "wv": dense_init(ks[2], d_model, spec.kv_dim, dtype),
        "wo": dense_init(ks[3], spec.q_dim, d_model, dtype),
    }
    if spec.qk_norm:
        p["q_norm"] = rms_norm_init(spec.head_dim, dtype)
        p["k_norm"] = rms_norm_init(spec.head_dim, dtype)
    return p


def _project_qkv(params, spec: AttnSpec, x, positions, rope_in_dtype: bool = False):
    B, T, _ = x.shape
    q = (x @ params["wq"]).reshape(B, T, spec.n_heads, spec.head_dim)
    k = (x @ params["wk"]).reshape(B, T, spec.n_kv_heads, spec.head_dim)
    v = (x @ params["wv"]).reshape(B, T, spec.n_kv_heads, spec.head_dim)
    if spec.qk_norm:
        q = rms_norm(params["q_norm"], q)
        k = rms_norm(params["k_norm"], k)
    q = apply_rope(q, positions, spec.rope_theta, rotate_in_input_dtype=rope_in_dtype)
    k = apply_rope(k, positions, spec.rope_theta, rotate_in_input_dtype=rope_in_dtype)
    return q, k, v


# ---------------------------------------------------------------------------
# Blockwise flash attention (full sequence: train / prefill)
# ---------------------------------------------------------------------------


def _chunk_attend(q, k, v, q_pos, k_pos, spec: AttnSpec, window: Optional[int], carry):
    """One (q-chunk x kv-chunk) online-softmax update.

    q: (B, bq, Hkv, G, hd); k/v: (B, bk, Hkv, hd); carry = (m, l, acc).
    """
    m, l, acc = carry
    scale = spec.head_dim**-0.5
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    s = softcap(s, spec.attn_softcap)
    mask = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= k_pos[None, :] > (q_pos[:, None] - window)
    s = jnp.where(mask[None, None, None], s, NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
    return (m_new, l_new, acc_new)


def _flash_q_chunk(q, k, v, q_pos, k_pos, spec: AttnSpec, window, bk: int):
    """Attend one q chunk against all of k/v, scanning kv chunks."""
    B, bq, Hkv, G, hd = q.shape
    S = k.shape[1]
    nk = -(-S // bk)
    pad = nk * bk - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # padded slots get a huge *positive* position so the causal test fails
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=2 * 10**9)
    k = k.reshape(B, nk, bk, Hkv, hd).transpose(1, 0, 2, 3, 4)
    v = v.reshape(B, nk, bk, Hkv, hd).transpose(1, 0, 2, 3, 4)
    k_pos = k_pos.reshape(nk, bk)
    m0 = jnp.full((B, Hkv, G, bq), NEG, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, bq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, bq, hd), jnp.float32)

    def body(carry, xs):
        kj, vj, kpj = xs
        return _chunk_attend(q, kj, vj, q_pos, kpj, spec, window, carry), None

    (m, l, acc), _ = lax.scan(body, (m0, l0, a0), (k, v, k_pos))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4)  # (B, bq, Hkv, G, hd)


def flash_attention(
    q, k, v, spec: AttnSpec, *, q_offset: int | jax.Array = 0, window: Optional[int] = None,
    bq: int = 512, bk: int = 1024,
):
    """Causal blockwise attention. q (B,T,Hq,hd), k/v (B,S,Hkv,hd).

    ``q_offset``: position of q[0] relative to k[0] (prefix decode).
    Windowed layers take the *banded* path: each q chunk only sees the
    ``window+bq`` KV slice that can pass the mask.
    """
    B, T, Hq, hd = q.shape
    S = k.shape[1]
    G = Hq // spec.n_kv_heads
    q = q.reshape(B, T, spec.n_kv_heads, G, hd)
    bq = min(bq, T)
    nq = -(-T // bq)
    padq = nq * bq - T
    q_pos_full = q_offset + jnp.arange(T)
    if padq:
        q = jnp.pad(q, ((0, 0), (0, padq), (0, 0), (0, 0), (0, 0)))
        q_pos_full = jnp.pad(q_pos_full, (0, padq), constant_values=2 * (10**9))
    qs = q.reshape(B, nq, bq, spec.n_kv_heads, G, hd).transpose(1, 0, 2, 3, 4, 5)
    q_pos = q_pos_full.reshape(nq, bq)

    banded = window is not None and S > (window + bq)
    if banded:
        wb = window + bq
        kp = jnp.pad(k, ((0, 0), (wb, 0), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (wb, 0), (0, 0), (0, 0)))
        kpos_pad = jnp.concatenate([jnp.full((wb,), 2 * 10**9), jnp.arange(S)])

        def body(_, xs):
            qi, qpi, idx = xs
            # highest kv position this chunk can see is its last q position
            end = jnp.clip((idx + 1) * bq - q_offset, 0, S) + wb  # exclusive, in padded coords
            start = end - wb
            kj = lax.dynamic_slice_in_dim(kp, start, wb, axis=1)
            vj = lax.dynamic_slice_in_dim(vp, start, wb, axis=1)
            kpj = lax.dynamic_slice_in_dim(kpos_pad, start, wb, axis=0)
            o = _flash_q_chunk(qi, kj, vj, qpi, kpj, spec, window, bk)
            return None, o

        _, outs = lax.scan(body, None, (qs, q_pos, jnp.arange(nq)))
    else:
        k_pos = jnp.arange(S)

        def body(_, xs):
            qi, qpi = xs
            o = _flash_q_chunk(qi, k, v, qpi, k_pos, spec, window, bk)
            return None, o

        _, outs = lax.scan(body, None, (qs, q_pos))

    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * bq, Hq, hd)
    return out[:, :T].astype(k.dtype)


# ---------------------------------------------------------------------------
# Single-token decode against a (ring-buffer) KV cache
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    k: jax.Array  # (B, W, Hkv, hd)
    v: jax.Array  # (B, W, Hkv, hd)
    slot_pos: jax.Array  # (B, W) int32 per-row; -1 = empty


def init_kv_cache(batch: int, n_slots: int, spec: AttnSpec, dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, n_slots, spec.n_kv_heads, spec.head_dim), dtype),
        v=jnp.zeros((batch, n_slots, spec.n_kv_heads, spec.head_dim), dtype),
        slot_pos=jnp.full((batch, n_slots), -1, jnp.int32),
    )


def cache_from_prefill(k, v, spec: AttnSpec, n_slots: int) -> KVCache:
    """Build a (possibly ring) cache from prefill K/V of length T."""
    B, T, H, hd = k.shape
    if T <= n_slots:
        cache = init_kv_cache(B, n_slots, spec, k.dtype)
        return KVCache(
            k=cache.k.at[:, :T].set(k),
            v=cache.v.at[:, :T].set(v),
            slot_pos=cache.slot_pos.at[:, :T].set(jnp.arange(T)),
        )
    pos = jnp.arange(T - n_slots, T)
    slots = pos % n_slots
    return KVCache(
        k=jnp.zeros((B, n_slots, H, hd), k.dtype).at[:, slots].set(k[:, -n_slots:]),
        v=jnp.zeros((B, n_slots, H, hd), k.dtype).at[:, slots].set(v[:, -n_slots:]),
        slot_pos=jnp.broadcast_to(
            jnp.full((n_slots,), -1, jnp.int32).at[slots].set(pos), (B, n_slots)
        ),
    )


def decode_attend(params, spec: AttnSpec, x, cache: KVCache, pos, window: Optional[int]):
    """x: (B, 1, d); pos: int32 position of the new token — a scalar
    (whole batch in lockstep) or a (B,) vector (continuous batching:
    every row decodes at its own position).

    Returns (out (B,1,d), updated cache)."""
    B = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    lockstep = pos.ndim == 0
    if lockstep:
        pos = jnp.broadcast_to(pos, (B,))
    positions = pos[:, None]  # (B, 1)
    # rope rotation in the cache dtype under the opt flag: with an f32
    # rotated value in scope, XLA promotes the whole stacked KV cache to
    # f32 inside the layer loop (§Perf deepseek decode hillclimb)
    q, k_new, v_new = _project_qkv(params, spec, x, positions,
                                   rope_in_dtype=_OPT_DECODE_NO_F32_CACHE)
    W = cache.k.shape[1]
    slot = pos % W  # (B,)
    if lockstep:  # hot path: one dynamic-update-slice, no scatter
        k_c = lax.dynamic_update_slice_in_dim(cache.k, k_new, slot[0], axis=1)
        v_c = lax.dynamic_update_slice_in_dim(cache.v, v_new, slot[0], axis=1)
        slot_pos = lax.dynamic_update_slice_in_dim(
            cache.slot_pos, positions, slot[0], axis=1
        )
    else:  # continuous batching: every row writes its own ring slot
        rows = jnp.arange(B)
        k_c = cache.k.at[rows, slot].set(k_new[:, 0])
        v_c = cache.v.at[rows, slot].set(v_new[:, 0])
        slot_pos = cache.slot_pos.at[rows, slot].set(pos)

    G = spec.n_heads // spec.n_kv_heads
    qg = q.reshape(B, 1, spec.n_kv_heads, G, spec.head_dim)
    scale = spec.head_dim**-0.5
    if _OPT_DECODE_NO_F32_CACHE:
        # §Perf decode hillclimb: preferred_element_type accumulates in fp32
        # WITHOUT materializing an fp32 copy of the whole cache
        s = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qg, k_c, preferred_element_type=jnp.float32
        ) * scale
    else:  # paper-faithful baseline path (fp32 upcast of K before the dot)
        s = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k_c.astype(jnp.float32)
        ) * scale
    s = softcap(s, spec.attn_softcap)
    valid = (slot_pos >= 0) & (slot_pos <= pos[:, None])  # (B, W)
    if window is not None:
        valid &= slot_pos > (pos[:, None] - window)
    s = jnp.where(valid[:, None, None, None, :], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    if _OPT_DECODE_NO_F32_CACHE:
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v_c.dtype), v_c,
                       preferred_element_type=jnp.float32)
    else:
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_c.astype(jnp.float32))
    o = o.reshape(B, 1, spec.q_dim).astype(x.dtype)
    out = o @ params["wo"]
    return out, KVCache(k_c, v_c, slot_pos)


# ---------------------------------------------------------------------------
# Full attention layer (train / prefill)
# ---------------------------------------------------------------------------


def attend_full(params, spec: AttnSpec, x, positions, window: Optional[int],
                return_kv=False, rt=None):
    """x (B,T,d) -> (B,T,d). positions (B,T) absolute.

    ``rt``: Runtime for kernel dispatch — under the "pallas"/"auto"
    backends the prefill attention runs the fused Pallas kernel
    (kernels/flash_attn) instead of the pure-JAX blockwise path, when
    the shapes fit its VMEM-resident-KV envelope."""
    q, k, v = _project_qkv(params, spec, x, positions)
    o = None
    if rt is not None:
        from ..kernels import dispatch
        from ..kernels.flash_attn import ops as flash_ops

        choice = rt.kernel_choice("flash_attn")
        if choice.use_pallas and not flash_ops.supported(q.shape, k.shape,
                                                         choice.interpret):
            choice = dispatch.KernelChoice("ref", False)
        dispatch.record("flash_attn", choice)
        if choice.use_pallas:
            B, T, Hq, hd = q.shape
            G = Hq // spec.n_kv_heads
            qg = q.reshape(B, T, spec.n_kv_heads, G, hd)
            o = flash_ops.flash_pallas(
                qg, k, v, softcap=spec.attn_softcap, window=window,
                interpret=choice.interpret,
            ).reshape(B, T, Hq, hd)
    if o is None:
        o = flash_attention(q, k, v, spec, window=window)
    out = o.reshape(*x.shape[:2], spec.q_dim) @ params["wo"]
    if return_kv:
        return out, (k, v)
    return out
