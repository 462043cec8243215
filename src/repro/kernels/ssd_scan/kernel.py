"""Pallas TPU kernel: Mamba2 SSD chunked scan (arXiv:2405.21060, Sec 6).

Grid (B, H, n_chunks); the chunk dimension is sequential ("arbitrary")
and the inter-chunk SSM state (P, N) lives in VMEM scratch, carried
across chunk iterations — the TPU-native shape of the SSD recurrence:
intra-chunk duality runs on the MXU as (cl x cl) matmuls, the state
update is a rank-cl outer-product accumulation.

Inputs:
  x    (B, T, H, P)     dt (B, T, H)   post-softplus
  A    (H,) negative    Bm/Cm (B, T, G, N) per-group (head h uses group
                        h // (H//G); G=1 reproduces the shared layout)
  init (B, H, P, N)     initial SSM state (zeros for a fresh sequence)
Outputs: y (B, T, H, P), final state (B, H, P, N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, init_ref, y_ref, fin_ref,
            state_ref, *, n_chunks: int, out_dtype):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = init_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, :, 0].astype(jnp.float32)  # (cl, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)  # (cl,)
    a = a_ref[0].astype(jnp.float32)  # scalar
    bm = b_ref[0, :, 0].astype(jnp.float32)  # (cl, N)
    cm = c_ref[0, :, 0].astype(jnp.float32)  # (cl, N)

    da = dt * a  # (cl,)
    ca = jnp.cumsum(da)  # (cl,)

    # intra-chunk (dual) term: scores[i,j] = (C_i . B_j) * exp(ca_i - ca_j) * dt_j, i >= j
    cl = x.shape[0]
    seg = ca[:, None] - ca[None, :]
    tri = jnp.tril(jnp.ones((cl, cl), jnp.float32))
    lmat = jnp.exp(seg) * tri
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (cl, cl)
    scores = cb * lmat * dt[None, :]
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (cl, P)

    # inter-chunk contribution from the carried state
    state = state_ref[...]  # (P, N)
    y += jnp.exp(ca)[:, None] * jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    # state update: S <- exp(sum dA) * S + sum_j exp(ca_last - ca_j) dt_j x_j B_j^T
    decay_out = jnp.exp(ca[-1] - ca) * dt  # (cl,)
    outer = jax.lax.dot_general(x * decay_out[:, None], bm,
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (P, N)
    state_ref[...] = jnp.exp(ca[-1]) * state + outer

    y_ref[0, :, 0] = y.astype(out_dtype)

    @pl.when(ci == n_chunks - 1)
    def _fin():
        fin_ref[0, 0] = state_ref[...]


def ssd_scan(
    x: jax.Array,  # (B, T, H, P)
    dt: jax.Array,  # (B, T, H)
    A: jax.Array,  # (H,)
    Bm: jax.Array,  # (B, T, G, N)
    Cm: jax.Array,  # (B, T, G, N)
    init: jax.Array,  # (B, H, P, N) initial state
    *,
    chunk: int = 128,
    interpret: bool = False,
):
    B, T, H, P = x.shape
    G, N = Bm.shape[-2:]
    assert Bm.shape == (B, T, G, N) and Cm.shape == Bm.shape, (Bm.shape, Cm.shape)
    assert H % G == 0, (H, G)
    hpg = H // G
    assert init.shape == (B, H, P, N), init.shape
    cl = min(chunk, T)
    assert T % cl == 0, (T, cl)
    n_chunks = T // cl
    grid = (B, H, n_chunks)
    out_dtype = x.dtype
    kernel = functools.partial(_kernel, n_chunks=n_chunks, out_dtype=out_dtype)
    y, fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, cl, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, cl, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, cl, 1, N), lambda b, h, c: (b, c, h // hpg, 0)),
            pl.BlockSpec((1, cl, 1, N), lambda b, h, c: (b, c, h // hpg, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, cl, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, H, P), out_dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, A, Bm, Cm, init.astype(jnp.float32))
    return y, fin
