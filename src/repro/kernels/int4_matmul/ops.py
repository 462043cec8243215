"""Dispatching wrapper + weight preparation for the INT4 dequant matmul."""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..dispatch import pick_tile, record, resolve
from .kernel import int4_matmul as _kernel_call
from .ref import int4_matmul_ref


class MatmulQWeight(NamedTuple):
    packed: jax.Array  # (K//2, N) uint8
    scale: jax.Array  # (K//group, N) f32
    zero: jax.Array  # (K//group, N) f32
    group: int


def quantize_matmul_weight(w: jax.Array, group: int = 64) -> MatmulQWeight:
    """w (K, N) -> per-(group-of-K, column) affine int4 codes (min/max init;
    HQQ refinement lives in core.quant — this layout is the kernel's)."""
    K, N = w.shape
    assert K % group == 0 and K % 2 == 0
    wg = w.astype(jnp.float32).reshape(K // group, group, N)
    wmin = wg.min(1)
    wmax = wg.max(1)
    scale = jnp.maximum((wmax - wmin) / 15.0, 1e-8)  # (K//group, N)
    zero = -wmin / scale
    q = jnp.clip(
        jnp.round(wg / scale[:, None] + zero[:, None]), 0, 15
    ).astype(jnp.uint8).reshape(K, N)
    packed = (q[0::2] | (q[1::2] << 4)).astype(jnp.uint8)
    return MatmulQWeight(packed, scale, zero, group)


@functools.partial(jax.jit, static_argnames=("group", "bm", "bn", "bk", "interpret"))
def _int4_pallas(x2, packed, scale, zero, group, bm, bn, bk, interpret):
    return _kernel_call(x2, packed, scale, zero, group=group, bm=bm, bn=bn,
                        bk=bk, interpret=interpret)


def int4_matmul(x, packed, scale, zero, *, group: int = 64,
                bm: Optional[int] = None, bn: Optional[int] = None,
                bk: Optional[int] = None, interpret: Optional[bool] = None,
                use_ref: bool = False, backend: Optional[str] = None):
    """y = x @ dequant(Wq). x (M, K) or (..., K) (leading dims flattened).

    Tile sizes default to the largest MXU-friendly divisors; ``bk`` is
    rounded to whole quantization groups."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    group = int(group)  # static jit arg; reject stray 0-d arrays
    choice = resolve("int4_matmul", backend or ("ref" if use_ref else "pallas"),
                     interpret=interpret)
    record("int4_matmul", choice)
    if not choice.use_pallas:
        out = int4_matmul_ref(x2, packed, scale, zero, group)
        return out.reshape(*lead, -1)
    M = x2.shape[0]
    N = packed.shape[1]
    if bm is None:
        bm = pick_tile(max(M, 1), 128, 8)
    if bn is None:
        bn = pick_tile(N, 128, 128)
    if bk is None:
        # bk must cover whole (pairs of) groups: step in 2*group units
        step = 2 * group
        bk = step * pick_tile(K // step, max(512 // step, 1), 1) if K % step == 0 else K
    out = _int4_pallas(x2, packed, scale, zero, group, bm, bn, bk,
                       choice.interpret)
    return out.reshape(*lead, -1)
