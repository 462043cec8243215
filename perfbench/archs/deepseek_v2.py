"""DeepSeek-V2 (``model_type`` deepseek_v2): multi-head latent attention
(MLA) and DeepSeekMoE, one chip's share of the routed experts.

Layers ``0 .. first_k_dense_replace - 1`` are attention + a dense SwiGLU
of ``intermediate_size``; the rest attention + MoE. Attention, without
a query LoRA (``q_lora_rank`` null): ``x @ wq`` gives each head's
``q_nope`` (``qk_nope_head_dim``) and ``q_pe`` (``qk_rope_head_dim``);
``x @ wkv_a`` gives the latent ``c_kv`` (``kv_lora_rank``), which its
own RMSNorm (eps 1e-6) normalises, and one ``k_pe`` that every head
shares; ``c_kv @ wkv_b`` gives each head's ``k_nope`` and ``v``
(``v_head_dim``). Rotary positions act on ``q_pe`` and ``k_pe`` only,
with YaRN frequencies (``rope_scaling``), and the softmax scale is
``(nope + rope)**-0.5 * m**2``, ``m = 0.1 * mscale_all_dim * ln(factor)
+ 1``. The router is a float32 softmax over all ``n_routed_experts *
expert_shares`` experts whose top ``num_experts_per_tok`` probabilities
are the gates, not renormalised (``norm_topk_prob`` false, scaling 1);
``n_shared_experts`` shared experts, fused into one SwiGLU of
``n_shared_experts * moe_intermediate_size``, take every token.

One chip's share: ``n_routed_experts`` counts the routed experts held
here (ids 0 .. n_routed_experts - 1 of the router's outputs); the
other ``expert_shares - 1`` shares live on other chips. The layer
returns its own experts' part of the result plus the shared experts'.

The plain reference below follows HF's ``modeling_deepseek.py``
(``DeepseekV2Attention``, ``DeepseekV2YarnRotaryEmbedding``,
``MoEGate``) in float32 at HIGHEST: K and V expanded per head, the full
softmax per head, the MoE computed densely over the held experts (every
held expert for every token, weighted by a gate that is zero off the
top-k), and no program code. Rotary halves are rotated in place (the
repo's layout), where HF first de-interleaves ``q_pe`` and ``k_pe``:
with seeded weights that is a fixed permutation of the rope columns of
``wq`` and ``wkv_a`` (``departures`` in the configuration).
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

from perfbench.lib import work
from perfbench.lib.model import Json, jax_seed
from perfbench.lib.reference import rms, rounding

KEYS = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
        "intermediate_size", "num_attention_heads", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "q_lora_rank",
        "n_routed_experts", "expert_shares", "num_experts_per_tok",
        "moe_intermediate_size", "n_shared_experts", "rope_theta",
        "rope_scaling", "rms_norm_eps", "norm_topk_prob",
        "routed_scaling_factor", "scoring_func", "topk_method", "hidden_act",
        "tie_word_embeddings")
# values this module runs, and no other
FIXED = {"q_lora_rank": None, "norm_topk_prob": False, "routed_scaling_factor": 1,
         "scoring_func": "softmax", "topk_method": "greedy", "hidden_act": "silu",
         "tie_word_embeddings": False}
KV_NORM_EPS = 1e-6  # HF's kv_a_layernorm keeps the RMSNorm default


def check(cj: Json) -> None:
    for key in KEYS:
        if key not in cj:
            raise KeyError(f"{cj['name']}: configuration lacks {key!r}")
    for key, want in FIXED.items():
        if cj[key] != want:
            raise ValueError(f"{cj['name']}: {key} must be {want!r}")
    if cj["rope_scaling"].get("type") != "yarn":
        raise ValueError(f"{cj['name']}: rope_scaling must be yarn")
    if not 0 <= cj["first_k_dense_replace"] <= cj["num_hidden_layers"]:
        raise ValueError(f"{cj['name']}: first_k_dense_replace out of range")


def _groups(cj: Json) -> List[Tuple[bool, int]]:
    """(is_moe, layers) of each layout group that has layers: the dense
    layers, then the MoE layers."""
    k, L = cj["first_k_dense_replace"], cj["num_hidden_layers"]
    return [(m, n) for m, n in ((False, k), (True, L - k)) if n]


def _router_width(cj: Json) -> int:
    return cj["n_routed_experts"] * cj["expert_shares"]


def _shared_width(cj: Json) -> int:
    return cj["n_shared_experts"] * cj["moe_intermediate_size"]


def param_count(cj: Json) -> Dict[str, int]:
    """Parameters by part, from the configuration's sizes alone."""
    d, V, L = cj["hidden_size"], cj["vocab_size"], cj["num_hidden_layers"]
    H, R, P = (cj["num_attention_heads"], cj["kv_lora_rank"],
               cj["qk_rope_head_dim"])
    N, Vh = cj["qk_nope_head_dim"], cj["v_head_dim"]
    held, k, f = (cj["n_routed_experts"], cj["num_experts_per_tok"],
                  cj["moe_intermediate_size"])
    n_dense = cj["first_k_dense_replace"]
    n_moe = L - n_dense
    attn = L * (d * H * (N + P) + d * (R + P) + R + R * H * (N + Vh) + H * Vh * d)
    dense = n_dense * 3 * d * cj["intermediate_size"]
    experts = n_moe * held * 3 * d * f
    shared = n_moe * 3 * d * _shared_width(cj)
    router = n_moe * d * _router_width(cj)
    norms = L * 2 * d + d
    head = V * d  # untied: the embedding is as large again
    total = attn + dense + experts + shared + router + norms + 2 * head
    # what one token multiplies through here: attention, the dense MLP or
    # the routed experts it expects among those held (top_k * held /
    # router width of them), the shared experts, the router and the head
    routed = n_moe * k * held // _router_width(cj) * 3 * d * f
    active = attn + dense + routed + shared + router + head
    return {"attention": attn, "dense_mlp": dense, "experts": experts,
            "shared_experts": shared, "router": router, "norms": norms,
            "embed_head": 2 * head, "total": total, "active": active,
            "head": head}


def experts(cj: Json):
    return cj["n_routed_experts"], cj["num_experts_per_tok"]


def kernel_work(cj: Json, kernel: str, tokens: int) -> work.Work:
    """``moe_gmm``: the MoE layers' routed experts, ``tokens`` tokens each
    sending ``top_k * held / router width`` rows to the held experts.
    ``flash_attn``: every layer's prefill attention over a ``tokens``-long
    prompt, q and k ``nope + rope`` wide and v ``v_head_dim`` wide per
    head, reading q, k, v and writing the output once. ``mla_decode``:
    ``tokens`` latent positions read per layer (``kv_lora_rank +
    qk_rope_head_dim`` floats each), every head scoring each against the
    whole latent key and summing the latent values; the queries and
    outputs (16 rows of 16 x 1,088 floats at most) are left out."""
    d, L, H = cj["hidden_size"], cj["num_hidden_layers"], cj["num_attention_heads"]
    R, P = cj["kv_lora_rank"], cj["qk_rope_head_dim"]
    if kernel == "moe_gmm":
        n_moe = L - cj["first_k_dense_replace"]
        rows_per_token = (cj["num_experts_per_tok"] * cj["n_routed_experts"]
                          / _router_width(cj))
        return work.times(n_moe, work.moe_gmms(
            tokens, d, cj["moe_intermediate_size"], cj["n_routed_experts"],
            rows_per_token))
    if kernel == "flash_attn":
        qk, v = cj["qk_nope_head_dim"] + P, cj["v_head_dim"]
        pairs = tokens * (tokens + 1) / 2
        return work.times(L, work.Work(2.0 * H * (qk + v) * pairs,
                                       work.F32 * tokens * H * (2 * qk + 2 * v)))
    if kernel == "mla_decode":
        return work.times(L, work.Work(2.0 * H * (R + P + R) * tokens,
                                       work.F32 * tokens * (R + P)))
    return work.ZERO


def program_config(cj: Json):
    """The program's ``ModelConfig`` for this file."""
    from repro.configs.base import (BlockSpec, LayoutGroup, MLASpec,
                                    ModelConfig, MoESpec, YarnSpec)

    rs = cj["rope_scaling"]
    attn = MLASpec(
        n_heads=cj["num_attention_heads"], kv_lora_rank=cj["kv_lora_rank"],
        qk_nope_head_dim=cj["qk_nope_head_dim"],
        qk_rope_head_dim=cj["qk_rope_head_dim"], v_head_dim=cj["v_head_dim"],
        rope_theta=float(cj["rope_theta"]),
        yarn=YarnSpec(factor=float(rs["factor"]),
                      original_max_position_embeddings=rs["original_max_position_embeddings"],
                      beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
                      mscale=float(rs["mscale"]),
                      mscale_all_dim=float(rs["mscale_all_dim"])))
    moe = MoESpec(num_experts=_router_width(cj), top_k=cj["num_experts_per_tok"],
                  d_ff=cj["moe_intermediate_size"], num_shared=cj["n_shared_experts"],
                  shared_d_ff=_shared_width(cj), num_held=cj["n_routed_experts"])
    blocks = {"dense": BlockSpec(kind="attn_dense", attn=attn,
                                 d_ff=cj["intermediate_size"]),
              "moe": BlockSpec(kind="attn_moe", attn=attn, moe=moe)}
    return ModelConfig(
        name=cj["name"], family="moe", d_model=cj["hidden_size"],
        vocab=cj["vocab_size"], block_defs=blocks,
        layout=tuple(LayoutGroup(("moe" if m else "dense",), n)
                     for m, n in _groups(cj)),
        norm_eps=float(cj["rms_norm_eps"]), dtype="float32", source=cj["source"])


def make_weights(cj: Json, seed: int):
    """Seeded weights in the program's parameter tree (each layout
    group's block stacked over its layers), made on the device in one
    jitted call: projections N(0, 1/fan_in), norm gains zero under the
    (1 + w) parameterisation, the embedding N(0, 1), the untied head
    N(0, 1/hidden_size), the router float32 like every other leaf."""
    import jax
    import jax.numpy as jnp

    d, V = cj["hidden_size"], cj["vocab_size"]
    H, R, P = (cj["num_attention_heads"], cj["kv_lora_rank"],
               cj["qk_rope_head_dim"])
    N, Vh = cj["qk_nope_head_dim"], cj["v_head_dim"]
    held, f, E = cj["n_routed_experts"], cj["moe_intermediate_size"], _router_width(cj)
    fs = _shared_width(cj)
    f32 = jnp.float32

    def dense(key, shape, fan_in):
        return jax.random.normal(key, shape, f32) * (fan_in ** -0.5)

    def swiglu(ks, n, width, lead=()):
        return {"wg": dense(ks[0], (n,) + lead + (d, width), d),
                "wu": dense(ks[1], (n,) + lead + (d, width), d),
                "wd": dense(ks[2], (n,) + lead + (width, d), width)}

    def group(key, is_moe, n):
        ks = jax.random.split(key, 12)
        p = {"ln1": jnp.zeros((n, d), f32), "ln2": jnp.zeros((n, d), f32),
             "mixer": {"wq": dense(ks[0], (n, d, H * (N + P)), d),
                       "wkv_a": dense(ks[1], (n, d, R + P), d),
                       "kv_norm": jnp.zeros((n, R), f32),
                       "wkv_b": dense(ks[2], (n, R, H * (N + Vh)), R),
                       "wo": dense(ks[3], (n, H * Vh, d), H * Vh)}}
        if not is_moe:
            p["ffn"] = swiglu(ks[4:7], n, cj["intermediate_size"])
            return p
        p["ffn"] = {"router": dense(ks[4], (n, d, E), d),
                    **swiglu(ks[5:8], n, f, lead=(held,)),
                    "shared": swiglu(ks[8:11], n, fs)}
        return p

    def init(key):
        ks = jax.random.split(key, 4)
        gk = jax.random.split(ks[3], 2)
        return {"embed": jax.random.normal(ks[0], (V, d), f32),
                "lm_head": dense(ks[1], (d, V), d),
                "final_norm": jnp.zeros((d,), f32),
                "groups": {f"g{i}": {"p0": group(gk[i], m, n)}
                           for i, (m, n) in enumerate(_groups(cj))}}

    return jax.jit(init)(jax.random.key(jax_seed(seed)))


def yarn_inv_freq(cj: Json) -> np.ndarray:
    """``DeepseekV2YarnRotaryEmbedding``'s frequencies: with f_extra =
    theta**(-2i/dim), inv_freq = f_extra * mask + f_extra / factor *
    (1 - mask), mask = 1 - clip((i - low) / (high - low), 0, 1), where
    low and high are the floor and ceiling of the dimensions that turn
    beta_fast and beta_slow times over the original context."""
    dim, base = cj["qk_rope_head_dim"], float(cj["rope_theta"])
    rs = cj["rope_scaling"]
    orig = rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(dim // 2, dtype=np.float64)
    mask = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
    f_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    return (f_extra * mask + f_extra / rs["factor"] * (1.0 - mask)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cj: Json) -> float:
    rs = cj["rope_scaling"]
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (cj["qk_nope_head_dim"] + cj["qk_rope_head_dim"]) ** -0.5 * m * m


def forward(cj: Json, params, tokens, quant: str = ""):
    """Logits (T, V) for one sequence ``tokens`` (T,), every matmul in
    float32 at HIGHEST precision. ``quant`` names a control: "int8" or
    "fp8" rounds every matmul weight per output channel and every
    projection's input per row to that format."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    prec = lax.Precision.HIGHEST
    act, wq = rounding(quant)
    mm = lambda a, b: jnp.matmul(act(a), b, precision=prec)
    eps = float(cj["rms_norm_eps"])
    H, R, P = (cj["num_attention_heads"], cj["kv_lora_rank"],
               cj["qk_rope_head_dim"])
    N, Vh, k_top = cj["qk_nope_head_dim"], cj["v_head_dim"], cj["num_experts_per_tok"]
    held, scale = cj["n_routed_experts"], softmax_scale(cj)
    rs = cj["rope_scaling"]
    # cos and sin scaled by mscale(mscale) / mscale(mscale_all_dim)
    cs = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(rs["factor"],
                                                               rs["mscale_all_dim"])
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    head = p["lm_head"]
    if quant:  # matrices of the layers and the head; the embedding is a gather
        p = {**p, "groups": jax.tree.map(lambda a: wq(a) if a.ndim >= 3 else a,
                                         p["groups"])}
        head = wq(head)
    T = tokens.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * jnp.asarray(yarn_inv_freq(cj))
    cos, sin = jnp.cos(ang) * cs, jnp.sin(ang) * cs  # (T, 1, P/2)

    def rope(x):  # x (T, heads, P): rotate halves
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    causal = (lax.broadcasted_iota(jnp.int32, (T, T), 0)
              >= lax.broadcasted_iota(jnp.int32, (T, T), 1))

    def swiglu(w, h):
        return mm(jax.nn.silu(mm(h, w["wg"])) * mm(h, w["wu"]), w["wd"])

    def attention(lp, x):
        a = lp["mixer"]
        h = rms(x, lp["ln1"], eps)
        q = mm(h, a["wq"]).reshape(T, H, N + P)
        ckv = mm(h, a["wkv_a"])
        c = rms(ckv[:, :R], a["kv_norm"], KV_NORM_EPS)
        kv = mm(c, a["wkv_b"]).reshape(T, H, N + Vh)
        q = jnp.concatenate([q[..., :N], rope(q[..., N:])], -1)
        k_pe = jnp.broadcast_to(rope(ckv[:, None, R:]), (T, H, P))
        k = jnp.concatenate([kv[..., :N], k_pe], -1)
        v = kv[..., N:]

        def one_head(qkv):  # the whole softmax of one head: (T, T)
            qh, kh, vh = qkv
            s = jnp.matmul(qh, kh.T, precision=prec) * scale
            w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return jnp.matmul(w, vh, precision=prec)

        o = lax.map(one_head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                               v.transpose(1, 0, 2)))  # (H, T, Vh)
        return x + mm(o.transpose(1, 0, 2).reshape(T, H * Vh), a["wo"])

    def dense_layer(x, lp):
        x = attention(lp, x)
        return x + swiglu(lp["ffn"], rms(x, lp["ln2"], eps)), None

    def moe_layer(x, lp):
        x = attention(lp, x)
        f = lp["ffn"]
        h2 = rms(x, lp["ln2"], eps)
        probs = jax.nn.softmax(mm(h2, f["router"]), -1)  # (T, router width)
        kth = lax.top_k(probs, k_top)[0][:, -1:]
        gates = jnp.where(probs >= kth, probs, 0.0)[:, :held]  # experts held here

        def expert(y, w):
            g, wg, wu, wd = w
            return y + g[:, None] * swiglu({"wg": wg, "wu": wu, "wd": wd}, h2), None

        y, _ = lax.scan(expert, jnp.zeros_like(x),
                        (gates.T, f["wg"], f["wu"], f["wd"]))
        return x + y + swiglu(f["shared"], h2), None

    x = p["embed"][tokens]
    for i, (is_moe, _) in enumerate(_groups(cj)):
        x, _ = lax.scan(moe_layer if is_moe else dense_layer, x,
                        p["groups"][f"g{i}"]["p0"])
    return mm(rms(x, p["final_norm"], eps), head)
