"""HQQ-style INT4 group quantization (paper Sec 3.2: resident experts are
kept in HQQ INT4 to raise effective cache capacity).

Weights are quantized per *group* along the contraction dimension
(group_size consecutive elements share a scale and zero-point). The
HQQ-lite solver runs a few proximal iterations optimizing the zero-point
under an l_p (p<1) sparsity prior on the reconstruction residual —
jnp-only, so it runs inside jit.

Packed storage: two int4 codes per uint8 along the grouped axis.

The dequant-matmul path (:func:`qmatmul`) routes through the fused
Pallas INT4 kernel (kernels/int4_matmul) under the "pallas"/"auto"
backends: quantize the *transposed* weight with :func:`quantize_linear`
so the HQQ groups lie along the contraction axis, then
:func:`matmul_layout` repacks the identical codes into the kernel's
(K//2, N) storage — the reference and kernel paths dequantize the exact
same values.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class QTensor(NamedTuple):
    packed: jax.Array  # uint8 (..., K//2) two nibbles per byte
    scale: jax.Array  # f32 (..., K//group, 1)
    zero: jax.Array  # f32 (..., K//group, 1)
    shape: tuple  # original shape
    group: int


def _shrink_lp(x, beta: float, p: float):
    """Proximal operator of the l_p norm (HQQ eq. 4): soft-threshold with
    |x|^(p-1) reweighting."""
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - (jnp.abs(x) ** (p - 1.0)) / beta, 0.0)


def quantize(w: jax.Array, *, group: int = 64, iters: int = 10, p: float = 0.7,
             beta: float = 10.0) -> QTensor:
    """Quantize along the LAST axis to int4 codes in [0, 15]."""
    orig_shape = w.shape
    K = orig_shape[-1]
    assert K % group == 0 and group % 2 == 0, (K, group)
    wg = w.astype(jnp.float32).reshape(*orig_shape[:-1], K // group, group)
    wmin = wg.min(-1, keepdims=True)
    wmax = wg.max(-1, keepdims=True)
    scale = jnp.maximum((wmax - wmin) / 15.0, 1e-8)
    zero = -wmin / scale

    def step(carry, _):
        zero, beta_t = carry
        q = jnp.clip(jnp.round(wg / scale + zero), 0, 15)
        e = wg - (q - zero) * scale
        e_s = _shrink_lp(e, beta_t, p)
        zero_new = jnp.mean(q - (wg - e_s) / scale, axis=-1, keepdims=True)
        return (zero_new, beta_t * 1.01), None

    (zero, _), _ = jax.lax.scan(step, (zero, jnp.asarray(beta, jnp.float32)),
                                None, length=iters)
    q = jnp.clip(jnp.round(wg / scale + zero), 0, 15).astype(jnp.uint8)
    q = q.reshape(*orig_shape[:-1], K)
    packed = (q[..., 0::2] | (q[..., 1::2] << 4)).astype(jnp.uint8)
    return QTensor(
        packed=packed,
        scale=scale.reshape(*orig_shape[:-1], K // group, 1),
        zero=zero.reshape(*orig_shape[:-1], K // group, 1),
        shape=orig_shape,
        group=group,
    )


def unpack_codes(qt: QTensor) -> jax.Array:
    lo = qt.packed & 0x0F
    hi = qt.packed >> 4
    q = jnp.stack([lo, hi], axis=-1).reshape(*qt.shape[:-1], qt.shape[-1])
    return q


def dequantize(qt: QTensor, dtype=jnp.bfloat16) -> jax.Array:
    q = unpack_codes(qt).astype(jnp.float32)
    K = qt.shape[-1]
    qg = q.reshape(*qt.shape[:-1], K // qt.group, qt.group)
    w = (qg - qt.zero) * qt.scale
    return w.reshape(qt.shape).astype(dtype)


def quant_bytes(qt: QTensor) -> int:
    n = qt.packed.size + 4 * qt.scale.size + 4 * qt.zero.size
    return int(n)


# ---------------------------------------------------------------------------
# Fused dequant-matmul (kernels/int4_matmul wiring)
# ---------------------------------------------------------------------------


def quantize_linear(w: jax.Array, *, group: int = 64, **hqq_kw) -> QTensor:
    """Quantize a matmul weight w (K, N) for ``y = x @ dequant(w)``.

    Stores the HQQ codes of ``w.T`` (N, K) so groups run along the
    contraction axis K — the layout both the reference dequant and the
    fused kernel agree on."""
    assert w.ndim == 2, w.shape
    return quantize(w.T, group=group, **hqq_kw)


def dequantize_linear(ql: QTensor, dtype=jnp.float32) -> jax.Array:
    """QTensor from :func:`quantize_linear` -> the original-layout (K, N)."""
    return dequantize(ql, dtype).T


def matmul_layout(ql: QTensor):
    """Repack a :func:`quantize_linear` QTensor (codes of w.T, (N, K))
    into the kernel storage: packed (K//2, N), scale/zero (K//group, N).
    Bit-exact — the same int4 codes, transposed and repacked."""
    from ..kernels.int4_matmul.ops import MatmulQWeight

    # shape/group may have round-tripped through np.asarray (host stores
    # tree-map whole QTensors) — force back to python ints, they feed
    # static jit args downstream
    N, K = (int(s) for s in ql.shape)
    group = int(ql.group)
    q = unpack_codes(ql).T  # (K, N) int4 codes
    packed = (q[0::2] | (q[1::2] << 4)).astype(jnp.uint8)
    scale = ql.scale.reshape(N, K // group).T  # (K//group, N)
    zero = ql.zero.reshape(N, K // group).T
    return MatmulQWeight(packed, scale.astype(jnp.float32),
                         zero.astype(jnp.float32), group)


def qmatmul(x: jax.Array, ql, *, backend: Optional[str] = None,
            interpret: Optional[bool] = None) -> jax.Array:
    """y = x @ dequant(ql). ``ql``: QTensor from :func:`quantize_linear`
    or a prepacked ``MatmulQWeight`` (precompute via :func:`matmul_layout`
    to repack once per weight, not per call).

    backend "ref" multiplies by the dequantized weight; "pallas"/"auto"
    runs the fused dequant matmul kernel (interpret mode off-TPU)."""
    from ..kernels.dispatch import record, resolve
    from ..kernels.int4_matmul.ops import int4_matmul

    choice = resolve("int4_matmul", backend or "auto", interpret=interpret)
    if isinstance(ql, QTensor):
        if not choice.use_pallas:
            record("int4_matmul", choice)
            return x @ dequantize_linear(ql, jnp.float32).astype(x.dtype)
        mq = matmul_layout(ql)
    else:
        mq = ql
    return int4_matmul(x, mq.packed, mq.scale, mq.zero, group=mq.group,
                       backend=choice.backend, interpret=choice.interpret)


def quant_error(w: jax.Array, qt: QTensor) -> float:
    return float(jnp.abs(w.astype(jnp.float32) - dequantize(qt, jnp.float32)).mean())
