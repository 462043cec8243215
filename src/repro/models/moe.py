"""Sparsely-gated MoE layer (Eq. 1-2) with capacity-based dispatch and
shard_map expert parallelism.

Two execution paths share the dispatch logic:
  * local   — single device (smoke tests, offload engine, oracle)
  * sharded — shard_map over the mesh: tokens sharded on ("pod","data"),
              experts on "model"; two ``lax.all_to_all`` per layer
              (dispatch + return), grouped expert FFN in between.

Dispatch is GShard-style: per-expert capacity ``cap``; overflow tokens
are dropped (gate mass zeroed). ``zero_drop=True`` (decode) sizes the
buffer at N tokens so nothing can drop.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..configs.base import MoESpec
from .common import dense_init, silu
from .mlp import apply_mlp, init_mlp
from .runtime import Runtime

import os as _os

# §Perf optimization (EXPERIMENTS.md, granite/deepseek hillclimbs):
# baseline dispatch shards tokens over the data axes only, so the ms
# model-peers within a data row each dispatch IDENTICAL token buffers —
# the all_to_all and the expert FFN then do ms-times redundant work.
# With the flag on, tokens are sharded over ("data"..., "model") for the
# dispatch, cutting expert FLOPs and all-to-all bytes by ms at the price
# of one (N_loc, d_model) all-gather when resharding the combined output.
_OPT_MOE_DISPATCH_SHARD = "moe_dispatch_shard" in _os.environ.get("REPRO_OPT", "")


def set_opt_flags(**kw):
    g = globals()
    for k, v in kw.items():
        key = "_OPT_" + k.upper()
        assert key in g, key
        g[key] = v


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init_moe(key, d_model: int, spec: MoESpec, dtype):
    ks = jax.random.split(key, 5)
    E, f = spec.num_experts, spec.d_ff
    p = {
        "router": dense_init(ks[0], d_model, E, jnp.float32),
        "wg": jax.vmap(lambda k: dense_init(k, d_model, f, dtype))(
            jax.random.split(ks[1], E)
        ),
        "wu": jax.vmap(lambda k: dense_init(k, d_model, f, dtype))(
            jax.random.split(ks[2], E)
        ),
        "wd": jax.vmap(lambda k: dense_init(k, f, d_model, dtype))(
            jax.random.split(ks[3], E)
        ),
    }
    if spec.shared_d_ff:
        p["shared"] = init_mlp(ks[4], d_model, spec.shared_d_ff, dtype)
    return p


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------


def router_probs(params, x, spec: MoESpec):
    """x: (..., d) -> softmax router distribution (..., E) in fp32 (Eq. 1)."""
    logits = x.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    if spec.router_softcap is not None:
        logits = jnp.tanh(logits / spec.router_softcap) * spec.router_softcap
    return jax.nn.softmax(logits, axis=-1)


def top_k_route(probs, k: int):
    """probs (N, E) -> gates (N, K) raw probabilities, eids (N, K)."""
    gates, eids = lax.top_k(probs, k)
    return gates, eids.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Capacity dispatch
# ---------------------------------------------------------------------------


class Dispatch(NamedTuple):
    eids: jax.Array  # (N, K) int32, == E where dropped
    pos: jax.Array  # (N, K) int32 slot within expert buffer
    gates: jax.Array  # (N, K) f32, zeroed where dropped
    cap: int


def make_dispatch(gates, eids, spec: MoESpec, cap: int) -> Dispatch:
    N, K = eids.shape
    E = spec.num_experts
    flat = eids.reshape(N * K)
    oh = jax.nn.one_hot(flat, E, dtype=jnp.int32)
    pos = (jnp.cumsum(oh, axis=0) * oh).sum(-1) - 1  # occurrences before self
    pos = pos.reshape(N, K)
    keep = pos < cap
    return Dispatch(
        eids=jnp.where(keep, eids, E),  # E = out-of-range sentinel -> scatter drop
        pos=jnp.where(keep, pos, 0),
        gates=jnp.where(keep, gates, 0.0),
        cap=cap,
    )


def dispatch_tokens(d: Dispatch, x, n_experts: int):
    """x (N, dm) -> expert buffers (E, cap, dm)."""
    N, K = d.eids.shape
    xr = jnp.repeat(x[:, None], K, axis=1).reshape(N * K, -1)
    buf = jnp.zeros((n_experts, d.cap, x.shape[-1]), x.dtype)
    return buf.at[d.eids.reshape(-1), d.pos.reshape(-1)].set(xr, mode="drop")


def combine_tokens(d: Dispatch, buf):
    """buf (E, cap, dm) -> (N, dm) gate-weighted combine."""
    N, K = d.eids.shape
    safe_e = jnp.minimum(d.eids, buf.shape[0] - 1)
    gathered = buf[safe_e.reshape(-1), d.pos.reshape(-1)].reshape(N, K, -1)
    return jnp.einsum("nkd,nk->nd", gathered.astype(jnp.float32), d.gates).astype(buf.dtype)


# ---------------------------------------------------------------------------
# Expert FFN (grouped)
# ---------------------------------------------------------------------------


def _expert_weights(params, lora: Optional[dict], lora_scale: float, name: str):
    w = params[name]
    if lora is not None and name in lora:
        a, b = lora[name]["a"], lora[name]["b"]
        delta = jnp.einsum("edr,erf->edf", a.astype(jnp.float32), b.astype(jnp.float32))
        w = w + (lora_scale * delta).astype(w.dtype)
    return w


def expert_ffn(params, buf, rt: Runtime, lora: Optional[dict] = None, lora_scale: float = 1.0):
    """buf (E, cap, d) -> (E, cap, d) via per-expert gated MLP (Eq. 2)."""
    wg = _expert_weights(params, lora, lora_scale, "wg")
    wu = _expert_weights(params, lora, lora_scale, "wu")
    wd = _expert_weights(params, lora, lora_scale, "wd")
    from ..kernels import dispatch

    choice = rt.kernel_choice("moe_gmm")
    dispatch.record("moe_gmm", choice)
    if choice.use_pallas:
        from ..kernels.moe_gmm import ops as gmm_ops

        gmm = partial(gmm_ops.gmm_pallas, interpret=choice.interpret)
    else:
        gmm = lambda a, b: jnp.einsum("ecd,edf->ecf", a, b)
    h = silu(gmm(buf, wg)) * gmm(buf, wu)
    return gmm(h, wd)


# ---------------------------------------------------------------------------
# Local path
# ---------------------------------------------------------------------------


def _capacity(spec: MoESpec, n_tokens: int, zero_drop: bool) -> int:
    return n_tokens if zero_drop else min(n_tokens, spec.capacity(n_tokens))


def apply_moe_local(params, x2d, spec: MoESpec, rt: Runtime, lora=None,
                    lora_scale: float = 1.0, probs=None):
    """x2d (N, dm) -> (N, dm). Returns (y, probs)."""
    if probs is None:
        probs = router_probs(params, x2d, spec)
    gates, eids = top_k_route(probs, spec.top_k)
    cap = _capacity(spec, x2d.shape[0], rt.zero_drop)
    d = make_dispatch(gates, eids, spec, cap)
    buf = dispatch_tokens(d, x2d, spec.num_experts)
    out_buf = expert_ffn(params, buf, rt, lora, lora_scale)
    y = combine_tokens(d, out_buf)
    if spec.shared_d_ff:
        y = y + apply_mlp(params["shared"], x2d)
    return y, probs


# ---------------------------------------------------------------------------
# Sharded path (expert parallel over "model", tokens over data axes)
# ---------------------------------------------------------------------------


def apply_moe_sharded(params, x2d, spec: MoESpec, rt: Runtime, lora=None,
                      lora_scale: float = 1.0, probs=None):
    mesh = rt.mesh
    ms = rt.axis_size("model")
    E = spec.num_experts
    if ms == 1 or E % ms != 0:
        return apply_moe_local(params, x2d, spec, rt, lora, lora_scale, probs)

    N = x2d.shape[0]
    data_axes = rt.data_axes
    dp = rt.axis_size(data_axes) if data_axes else 1
    # optimized dispatch: tokens sharded over the model axis as well
    shard_model_too = _OPT_MOE_DISPATCH_SHARD and N % (dp * ms) == 0
    if shard_model_too:
        tok_axes = tuple(data_axes) + ("model",)
        tok_spec = P(tok_axes)
        n_loc = N // (dp * ms)
    else:
        token_sharded = bool(data_axes) and N % dp == 0
        tok_spec = P(data_axes) if token_sharded else P()
        n_loc = N // dp if token_sharded else N

    if probs is None:
        probs = router_probs(params, x2d, spec)
    gates, eids = top_k_route(probs, spec.top_k)
    cap = _capacity(spec, n_loc, rt.zero_drop)

    ew_spec = P("model", None, None)

    def fn(x_loc, gates_loc, eids_loc, wg, wu, wd, lora_loc):
        d = make_dispatch(gates_loc, eids_loc, spec, cap)
        buf = dispatch_tokens(d, x_loc, E)  # (E, cap, dm)
        # exchange: (E=ms*E_loc, cap, dm) -> rows of my experts from all peers
        buf = buf.reshape(ms, E // ms, cap, -1)
        buf = lax.all_to_all(buf, "model", split_axis=0, concat_axis=0, tiled=False)
        # (ms, E_loc, cap, dm): axis0 now indexes source shard
        buf = buf.transpose(1, 0, 2, 3).reshape(E // ms, ms * cap, -1)
        p_loc = {"wg": wg, "wu": wu, "wd": wd}
        out = expert_ffn(p_loc, buf, rt, lora_loc, lora_scale)
        out = out.reshape(E // ms, ms, cap, -1).transpose(1, 0, 2, 3)
        out = lax.all_to_all(out, "model", split_axis=0, concat_axis=0, tiled=False)
        out = out.reshape(E, cap, -1)
        return combine_tokens(d, out)

    lora_specs = jax.tree.map(lambda _: ew_spec, lora)
    y = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec, ew_spec, ew_spec, ew_spec, lora_specs),
        out_specs=tok_spec,
        check_vma=False,
    )(x2d, gates, eids, params["wg"], params["wu"], params["wd"], lora)
    if spec.shared_d_ff:
        y = y + apply_mlp(params["shared"], x2d)
    return y, probs


def apply_moe(params, x2d, spec: MoESpec, rt: Runtime, lora=None,
              lora_scale: float = 1.0, probs=None):
    if rt.sharded and rt.model_axis is not None:
        return apply_moe_sharded(params, x2d, spec, rt, lora, lora_scale, probs)
    return apply_moe_local(params, x2d, spec, rt, lora, lora_scale, probs)
