"""Pallas TPU kernel: fused INT4-dequant matmul  y = x @ dequant(Wq).

The paper keeps resident experts in HQQ INT4 (Sec 3.2); on TPU the
dequantization must be fused into the matmul so the MXU streams bf16
tiles straight out of VMEM instead of materializing the full-precision
weight in HBM.

Storage layout (see ops.quantize_matmul_weight):
  packed (K//2, N) uint8 — two 4-bit codes per byte along K
  scale/zero (K//group, N) f32 — per-group affine along K

Tiling: grid (M/bm, N/bn, K/bk), K innermost; fp32 accumulator in VMEM
scratch; MXU-aligned defaults bm=bn=128, bk=512 (bk multiple of 2*group).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, packed_ref, scale_ref, zero_ref, o_ref, acc_ref, *,
            group: int, n_k: int, out_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # (bm, bk)
    packed = packed_ref[...]  # (bk//2, bn) uint8
    lo = (packed & 0x0F).astype(jnp.float32)
    hi = (packed >> 4).astype(jnp.float32)
    bk2, bn = packed.shape
    q = jnp.stack([lo, hi], axis=1).reshape(bk2 * 2, bn)  # (bk, bn)
    scale = scale_ref[...]  # (bk//group, bn)
    zero = zero_ref[...]
    scale_full = jnp.repeat(scale, group, axis=0)  # (bk, bn)
    zero_full = jnp.repeat(zero, group, axis=0)
    w = (q - zero_full) * scale_full  # fp32 dequant
    acc_ref[...] += jax.lax.dot_general(
        x.astype(jnp.float32), w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def int4_matmul(
    x: jax.Array,  # (M, K)
    packed: jax.Array,  # (K//2, N) uint8
    scale: jax.Array,  # (K//group, N) f32
    zero: jax.Array,  # (K//group, N) f32
    *,
    group: int = 64,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    M, K = x.shape
    N = packed.shape[1]
    assert packed.shape[0] == K // 2 and K % group == 0
    bm = min(bm, M)
    bn = min(bn, N)
    bk = min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    assert bk % (2 * group) == 0 or bk == K, "bk must cover whole groups"
    n_k = K // bk
    grid = (M // bm, N // bn, n_k)
    out_dtype = x.dtype
    kernel = functools.partial(_kernel, group=group, n_k=n_k, out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // group, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // group, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, packed, scale, zero)
