"""Pallas TPU kernel: grouped (per-expert) matmul for the expert-parallel
MoE FFN — y[e] = a[e] @ b[e] for e in [E_local].

This is the compute hot-spot after the dispatch all_to_all: each model
shard runs its E/ms experts over the gathered (ms * cap) token rows.
Grid (E, M/bm, N/bn, K/bk), K innermost, fp32 VMEM accumulator;
MXU-aligned 128x128 output tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int, out_dtype):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[0]  # (bm, bk)
    b = b_ref[0]  # (bk, bn)
    acc_ref[...] += jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(3) == n_k - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(out_dtype)


def _kernel_ragged(s_ref, a_ref, b_ref, o_ref, acc_ref, *, n_k: int, bm: int,
                   out_dtype):
    """Ragged-group variant: ``s_ref`` (E,) scalar-prefetched row counts.
    M-tiles entirely past group e's row count skip the MXU work (rows
    >= size are required to be zero in ``a``, as the slot-dispatch
    buffers guarantee, so the zero accumulator IS the right output)."""
    e, i = pl.program_id(0), pl.program_id(1)

    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i * bm < s_ref[e])
    def _compute():
        acc_ref[...] += jax.lax.dot_general(
            a_ref[0].astype(jnp.float32), b_ref[0].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(pl.program_id(3) == n_k - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(out_dtype)


def gmm(
    a: jax.Array,  # (E, M, K)
    b: jax.Array,  # (E, K, N)
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
    group_sizes: jax.Array | None = None,  # (E,) valid rows per group
) -> jax.Array:
    """Grouped matmul. With ``group_sizes``, rows >= group_sizes[e] of
    ``a[e]`` MUST be zero (slot-dispatch buffers are zero-padded); the
    kernel then skips every M-tile past the group's row count — empty
    cache slots cost no MXU work.

    ``bm`` must be a multiple of 8 or cover the whole of M; M is padded
    to a whole number of ``bm`` tiles and the output sliced back."""
    E, M, K = a.shape
    _, _, N = b.shape
    assert b.shape == (E, K, N)
    bm = min(bm, M)
    bn = min(bn, N)
    bk = min(bk, K)
    if (bm % 8 and bm != M) or N % bn or K % bk:
        raise ValueError(f"unaligned gmm tiles: M={M} bm={bm} N={N} bn={bn} "
                         f"K={K} bk={bk}")
    # pad M to a tile multiple (caps are often ragged)
    padm = (-M) % bm
    if padm:
        a = jnp.pad(a, ((0, 0), (0, padm), (0, 0)))
        M = M + padm
    n_k = K // bk
    grid = (E, M // bm, N // bn, n_k)
    out_dtype = a.dtype
    out_shape = jax.ShapeDtypeStruct((E, M, N), out_dtype)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))
    if group_sizes is None:
        kernel = functools.partial(_kernel, n_k=n_k, out_dtype=out_dtype)
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm, bk), lambda e, i, j, k: (e, i, k)),
                pl.BlockSpec((1, bk, bn), lambda e, i, j, k: (e, k, j)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn), lambda e, i, j, k: (e, i, j)),
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            compiler_params=params,
            interpret=interpret,
            name="moe_gmm",
        )(a, b)
    else:
        kernel = functools.partial(_kernel_ragged, n_k=n_k, bm=bm,
                                   out_dtype=out_dtype)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm, bk), lambda e, i, j, k, s: (e, i, k)),
                pl.BlockSpec((1, bk, bn), lambda e, i, j, k, s: (e, k, j)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn), lambda e, i, j, k, s: (e, i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            compiler_params=params,
            interpret=interpret,
            name="moe_gmm_ragged",
        )(jnp.asarray(group_sizes, jnp.int32), a, b)
    return out[:, : M - padm] if padm else out
