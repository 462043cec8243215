"""Pallas TPU kernel: one decode step of multi-head latent attention
over a latent cache.

Every query head of a row attends to the row's cached positions through
one shared key of width R + P (the latent ``c_kv`` and the rotary
``k_pe``) and one shared value, the latent itself (width R): the heads'
own key and value projections are absorbed into the query and the
output outside the kernel.

Grid (B, S / bs): rows are parallel, position blocks are the reduction,
with the online-softmax statistics and the (H, R) accumulator in VMEM
scratch. Two scalar-prefetched vectors steer it: ``lens[b]``, how many
positions row b reads (its position plus one, at most S), and
``pos[b]``, its position. A block past ``lens[b]`` is neither computed
nor fetched (its index map repeats the row's last block, so the
pipeline issues no copy), and a position whose ``slot_pos`` is -1
(never written) or past ``pos[b]`` is masked.

Layouts: q_lat (B, H, R); q_pe (B, H, P); c_kv (B, S, R); k_pe_t
(B, P, S), the rotary keys with positions minor, so that the 64-wide
keys fill the 128 lanes of a tile (XLA lays a (B, S, 64) array out so
anyway, and the transpose is then free); slot_pos (B, 1, S) int32; out
(B, H, R) float32. S is a whole number of ``bs`` blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(lens_ref, pos_ref, q_ref, qpe_ref, c_ref, kpe_ref, sp_ref, o_ref,
            m_ref, l_ref, acc_ref, *, bs: int, scale: float):
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bs < lens_ref[b])
    def _step():
        c = c_ref[0].astype(jnp.float32)  # (bs, R)
        dims = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(q_ref[0].astype(jnp.float32), c, dims,
                                preferred_element_type=jnp.float32)
        s += jax.lax.dot_general(qpe_ref[0].astype(jnp.float32),
                                 kpe_ref[0].astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        sp = sp_ref[0]  # (1, bs)
        valid = (sp >= 0) & (sp <= pos_ref[b])
        s = jnp.where(valid, s * scale, NEG)  # (H, bs)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, c, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def mla_decode_fwd(q_lat, q_pe, c_kv, k_pe_t, slot_pos, lens, pos, *,
                   scale: float, bs: int, interpret: bool = False) -> jax.Array:
    B, H, R = q_lat.shape
    S, P = c_kv.shape[1], k_pe_t.shape[1]
    if S % bs or (bs % 128 and bs != S):
        raise ValueError(f"unaligned latent blocks: S={S} bs={bs}")

    def blk(b, j, lens_ref, pos_ref):
        # past the row's last block, repeat it: no copy is issued
        return jnp.minimum(j, (lens_ref[b] - 1) // bs)

    kernel = functools.partial(_kernel, bs=bs, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, S // bs),
        in_specs=[
            pl.BlockSpec((1, H, R), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec((1, H, P), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec((1, bs, R), lambda b, j, *r: (b, blk(b, j, *r), 0)),
            pl.BlockSpec((1, P, bs), lambda b, j, *r: (b, 0, blk(b, j, *r))),
            pl.BlockSpec((1, 1, bs), lambda b, j, *r: (b, 0, blk(b, j, *r))),
        ],
        out_specs=pl.BlockSpec((1, H, R), lambda b, j, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, R), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_decode",
    )(lens, pos, q_lat, q_pe, c_kv, k_pe_t, slot_pos)
