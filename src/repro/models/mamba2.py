"""Mamba2 (SSD, state-space duality) mixer — arXiv:2405.21060.

Chunked dual form for train/prefill; O(1)-state recurrent step for
decode. The chunked scan is also available as a Pallas kernel
(kernels/ssd_scan) — this module is the reference path and owns the
projections/conv around the scan.

Shapes: x_in (B, T, d); inner x (B, T, H, P); B/C (B, T, G, N);
state (B, H, P, N).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import SSMSpec
from .common import dense_init, rms_norm, rms_norm_init, silu


class MambaState(NamedTuple):
    conv: jax.Array  # (B, d_conv-1, conv_dim) last inputs to the causal conv
    ssm: jax.Array  # (B, H, P, N) fp32


def conv_dim(spec: SSMSpec, d_model: int) -> int:
    return spec.d_inner(d_model) + 2 * spec.n_groups * spec.d_state


def init_mamba(key, d_model: int, spec: SSMSpec, dtype):
    di = spec.d_inner(d_model)
    nh = spec.n_heads(d_model)
    cd = conv_dim(spec, d_model)
    ks = jax.random.split(key, 4)
    proj_out = 2 * di + 2 * spec.n_groups * spec.d_state + nh
    return {
        "in_proj": dense_init(ks[0], d_model, proj_out, dtype),
        "conv_w": (jax.random.normal(ks[1], (spec.d_conv, cd), jnp.float32) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((cd,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh, dtype=jnp.float32)),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((nh,), 0.01, jnp.float32))),  # softplus^-1
        "norm_w": rms_norm_init(di, dtype),
        "out_proj": dense_init(ks[3], di, d_model, dtype),
    }


def _split_proj(zxbcdt, spec: SSMSpec, d_model: int):
    di = spec.d_inner(d_model)
    gn = spec.n_groups * spec.d_state
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di : di + di + 2 * gn]
    dt = zxbcdt[..., di + di + 2 * gn :]
    return z, xbc, dt


def _causal_conv(xbc, w, b, init: Optional[jax.Array] = None):
    """Depthwise causal conv. xbc (B, T, cd); w (dc, cd); returns (out, tail).

    ``init``: (B, dc-1, cd) carried context (decode/prefill chaining)."""
    B, T, cd = xbc.shape
    dc = w.shape[0]
    if init is None:
        init = jnp.zeros((B, dc - 1, cd), xbc.dtype)
    xp = jnp.concatenate([init, xbc], axis=1)  # (B, T+dc-1, cd)
    out = sum(xp[:, i : i + T] * w[i][None, None] for i in range(dc)) + b[None, None]
    tail = xp[:, -(dc - 1) :] if dc > 1 else jnp.zeros((B, 0, cd), xbc.dtype)
    return silu(out), tail


def _segsum(ca):
    """ca (..., cl) cumulative dA within chunk -> decay matrix (..., cl, cl):
    M[i, j] = exp(ca_i - ca_j) for i >= j else 0."""
    diff = ca[..., :, None] - ca[..., None, :]
    cl = ca.shape[-1]
    mask = jnp.tril(jnp.ones((cl, cl), bool))
    return jnp.where(mask, jnp.exp(diff), 0.0)


def ssd_chunked(x, dt, A, Bm, Cm, spec: SSMSpec, init_state=None):
    """Chunked SSD scan (pure-jnp oracle; mirrors kernels/ssd_scan).

    x (B,T,H,P); dt (B,T,H) post-softplus; A (H,) negative;
    Bm/Cm (B,T,G,N). Returns (y (B,T,H,P), final_state (B,H,P,N))."""
    Bsz, T, H, Pd = x.shape
    G, N = Bm.shape[-2:]
    hpg = H // G
    cl = min(spec.chunk, T)
    nc = -(-T // cl)
    pad = nc * cl - T
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Tp = nc * cl

    xc = x.reshape(Bsz, nc, cl, H, Pd).astype(jnp.float32)
    dtc = dt.reshape(Bsz, nc, cl, H).astype(jnp.float32)
    Bc = Bm.reshape(Bsz, nc, cl, G, N).astype(jnp.float32)
    Cc = Cm.reshape(Bsz, nc, cl, G, N).astype(jnp.float32)

    dA = dtc * A[None, None, None, :]  # (B,nc,cl,H)
    ca = jnp.cumsum(dA, axis=2)

    # intra-chunk (dual/quadratic) term
    Lmat = _segsum(ca.transpose(0, 1, 3, 2))  # (B,nc,H,cl,cl)
    cb = jnp.einsum("bnigs,bnjgs->bngij", Cc, Bc)  # (B,nc,G,cl,cl)
    cb = jnp.repeat(cb, hpg, axis=2)  # (B,nc,H,cl,cl)
    scores = cb * Lmat * dtc.transpose(0, 1, 3, 2)[:, :, :, None, :]
    y_diag = jnp.einsum("bnhij,bnjhp->bnihp", scores, xc)

    # per-chunk outgoing state
    decay_out = jnp.exp(ca[:, :, -1:, :] - ca)  # (B,nc,cl,H)
    Bh = jnp.repeat(Bc, hpg, axis=3)  # (B,nc,cl,H,N)
    s_loc = jnp.einsum("bnchs,bnchp->bnhps", Bh * (decay_out * dtc)[..., None], xc)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(ca[:, :, -1, :])  # (B,nc,H)
    s0 = (
        jnp.zeros((Bsz, H, Pd, N), jnp.float32)
        if init_state is None
        else init_state.astype(jnp.float32)
    )

    def body(s, xs):
        dec, sl = xs  # dec (B,H), sl (B,H,P,N)
        s_new = s * dec[:, :, None, None] + sl
        return s_new, s

    scan_dec = chunk_decay.transpose(1, 0, 2)  # (nc,B,H)
    scan_sl = s_loc.transpose(1, 0, 2, 3, 4)  # (nc,B,H,P,N)
    final, s_prev = lax.scan(body, s0, (scan_dec, scan_sl))
    s_prev = s_prev.transpose(1, 0, 2, 3, 4)  # (B,nc,H,P,N) state entering chunk

    # inter-chunk contribution
    Ch = jnp.repeat(Cc, hpg, axis=3)  # (B,nc,cl,H,N)
    in_decay = jnp.exp(ca)  # (B,nc,cl,H)
    y_off = jnp.einsum("bnchs,bnhps->bnchp", Ch, s_prev) * in_decay[..., None]

    y = (y_diag.transpose(0, 1, 2, 3, 4) + y_off).reshape(Bsz, Tp, H, Pd)
    return y[:, :T], final


def apply_mamba_full(params, x_in, spec: SSMSpec, *, init_state: Optional[MambaState] = None,
                     return_state: bool = False, rt=None):
    """x_in (B, T, d) -> (B, T, d).

    ``rt``: Runtime for kernel dispatch — under "pallas"/"auto" the
    chunked scan runs the Pallas SSD kernel (kernels/ssd_scan), which
    handles n_groups >= 1 and a carried initial state."""
    B, T, d_model = x_in.shape
    di = spec.d_inner(d_model)
    nh = spec.n_heads(d_model)
    gn = spec.n_groups * spec.d_state
    zxbcdt = x_in @ params["in_proj"]
    z, xbc, dt_raw = _split_proj(zxbcdt, spec, d_model)
    conv_init = init_state.conv if init_state is not None else None
    xbc, conv_tail = _causal_conv(xbc, params["conv_w"], params["conv_b"], conv_init)
    xs = xbc[..., :di].reshape(B, T, nh, spec.head_dim)
    Bm = xbc[..., di : di + gn].reshape(B, T, spec.n_groups, spec.d_state)
    Cm = xbc[..., di + gn :].reshape(B, T, spec.n_groups, spec.d_state)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"][None, None])
    A = -jnp.exp(params["A_log"])
    ssm_init = init_state.ssm if init_state is not None else None
    choice = None
    if rt is not None:
        from ..kernels import dispatch

        choice = rt.kernel_choice("ssd_scan")
        dispatch.record("ssd_scan", choice)
    if choice is not None and choice.use_pallas:
        from ..kernels.ssd_scan import ops as ssd_ops

        y, final = ssd_ops.ssd_pallas(
            xs, dt, A, Bm, Cm, init=ssm_init, chunk=spec.chunk,
            interpret=choice.interpret,
        )
        y = y.astype(jnp.float32)
    else:
        y, final = ssd_chunked(xs, dt, A, Bm, Cm, spec, ssm_init)
    y = y + params["D"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(B, T, di).astype(x_in.dtype)
    y = rms_norm(params["norm_w"], y * silu(z))
    out = y @ params["out_proj"]
    if return_state:
        return out, MambaState(conv=conv_tail, ssm=final)
    return out


def apply_mamba_decode(params, x_in, state: MambaState, spec: SSMSpec):
    """Single-token step. x_in (B, 1, d) -> (out (B,1,d), new state)."""
    B, _, d_model = x_in.shape
    di = spec.d_inner(d_model)
    nh = spec.n_heads(d_model)
    gn = spec.n_groups * spec.d_state
    hpg = nh // spec.n_groups
    zxbcdt = x_in @ params["in_proj"]
    z, xbc, dt_raw = _split_proj(zxbcdt, spec, d_model)
    # conv step using cached tail
    xp = jnp.concatenate([state.conv, xbc], axis=1)  # (B, dc, cd)
    w = params["conv_w"]
    out = jnp.einsum("btc,tc->bc", xp.astype(jnp.float32), w.astype(jnp.float32))
    xbc1 = silu(out + params["conv_b"].astype(jnp.float32))[:, None].astype(x_in.dtype)
    new_conv = xp[:, 1:]
    xs = xbc1[..., :di].reshape(B, nh, spec.head_dim).astype(jnp.float32)
    Bm = xbc1[..., di : di + gn].reshape(B, spec.n_groups, spec.d_state).astype(jnp.float32)
    Cm = xbc1[..., di + gn :].reshape(B, spec.n_groups, spec.d_state).astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + params["dt_bias"][None])  # (B,H)
    A = -jnp.exp(params["A_log"])
    dec = jnp.exp(dt * A[None])  # (B,H)
    Bh = jnp.repeat(Bm, hpg, axis=1)  # (B,H,N)
    s_new = state.ssm * dec[:, :, None, None] + jnp.einsum(
        "bhn,bhp,bh->bhpn", Bh, xs, dt
    )
    Ch = jnp.repeat(Cm, hpg, axis=1)
    y = jnp.einsum("bhpn,bhn->bhp", s_new, Ch) + params["D"][None, :, None] * xs
    y = y.reshape(B, 1, di).astype(x_in.dtype)
    y = rms_norm(params["norm_w"], y * silu(z))
    return y @ params["out_proj"], MambaState(conv=new_conv, ssm=s_new)
