"""A homogeneous stack of attention + MoE layers (OLMoE; Granite's
``granitemoe.py`` is the same block).

Each of ``num_hidden_layers`` identical layers: a pre-norm grouped-query
attention block (rotary positions, optional per-head q/k RMSNorm,
causal softmax at 1/sqrt(head_dim)) and a pre-norm MoE block (float32
softmax router over all ``num_local_experts`` experts, the top-k raw
probabilities as gates, SwiGLU experts of ``intermediate_size``); a
final norm and the head, tied to the embedding or not. RMSNorm gains
use the (1 + w) form.

The plain reference computes the MoE densely (every expert for every
token, weighted by a gate that is zero off the top-k), so no dispatch,
capacity or cache is shared with the program.
"""
from __future__ import annotations

from typing import Dict

from perfbench.lib import work
from perfbench.lib.model import Json, jax_seed
from perfbench.lib.reference import rms, rope, rounding

KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "intermediate_size",
        "num_local_experts", "num_experts_per_tok", "rope_theta",
        "rms_norm_eps", "qk_norm")


def check(cj: Json) -> None:
    for key in KEYS:
        if key not in cj:
            raise KeyError(f"{cj['name']}: configuration lacks {key!r}")
    if cj["qk_norm"] not in ("none", "per_head"):
        raise ValueError(f"{cj['name']}: qk_norm must be 'none' or 'per_head'")


def param_count(cj: Json) -> Dict[str, int]:
    """Parameters by part, from the configuration's sizes alone."""
    d, L = cj["hidden_size"], cj["num_hidden_layers"]
    hq, hkv, hd = (cj["num_attention_heads"], cj["num_key_value_heads"],
                   cj["head_dim"])
    E, k, f, V = (cj["num_local_experts"], cj["num_experts_per_tok"],
                  cj["intermediate_size"], cj["vocab_size"])
    attn = L * (2 * d * hq * hd + 2 * d * hkv * hd
                + (2 * hd if cj["qk_norm"] == "per_head" else 0))
    experts = L * E * 3 * d * f
    router = L * d * E
    norms = L * 2 * d + d
    embed = V * d * (1 if cj.get("tie_word_embeddings") else 2)
    total = attn + experts + router + norms + embed
    # what one token multiplies through: attention, its top-k experts,
    # the router and the head (the embedding is a gather)
    head = V * d
    active = attn + L * k * 3 * d * f + router + head
    return {"attention": attn, "experts": experts, "router": router,
            "norms": norms, "embed_head": embed, "total": total,
            "active": active, "head": head}


def experts(cj: Json):
    return cj["num_local_experts"], cj["num_experts_per_tok"]


def kernel_work(cj: Json, kernel: str, tokens: int) -> work.Work:
    """Every layer runs ``moe_gmm`` and full causal ``flash_attn``."""
    L = cj["num_hidden_layers"]
    if kernel == "moe_gmm":
        return work.times(L, work.moe_gmms(
            tokens, cj["hidden_size"], cj["intermediate_size"],
            cj["num_local_experts"], cj["num_experts_per_tok"]))
    if kernel == "flash_attn":
        return work.times(L, work.causal_attention(
            tokens, cj["num_attention_heads"], cj["num_key_value_heads"],
            cj["head_dim"]))
    return work.ZERO


def program_config(cj: Json):
    """The program's ``ModelConfig`` for this file."""
    from repro.configs.base import (AttnSpec, BlockSpec, LayoutGroup,
                                    ModelConfig, MoESpec)

    attn = AttnSpec(n_heads=cj["num_attention_heads"],
                    n_kv_heads=cj["num_key_value_heads"],
                    head_dim=cj["head_dim"], rope_theta=float(cj["rope_theta"]),
                    qk_norm=cj["qk_norm"] == "per_head")
    moe = MoESpec(num_experts=cj["num_local_experts"],
                  top_k=cj["num_experts_per_tok"],
                  d_ff=cj["intermediate_size"])
    return ModelConfig(
        name=cj["name"], family="moe", d_model=cj["hidden_size"],
        vocab=cj["vocab_size"],
        block_defs={"moe": BlockSpec(kind="attn_moe", attn=attn, moe=moe)},
        layout=(LayoutGroup(("moe",), cj["num_hidden_layers"]),),
        norm_eps=float(cj["rms_norm_eps"]),
        tie_embeddings=bool(cj.get("tie_word_embeddings", False)),
        dtype="float32", source=cj["source"])


def make_weights(cj: Json, seed: int):
    """Seeded weights in the program's parameter tree, made on the
    device in one jitted call. Projections are N(0, 1/fan_in), norm
    gains zero under the (1 + w) parameterisation; the router is float32
    like every other leaf. An untied embedding is N(0, 1). A tied one is
    also the head, so it is drawn as the head is, N(0, 1/hidden_size):
    at N(0, 1) its logit for the token just read would be about
    hidden_size and every greedy step would repeat that token."""
    import jax
    import jax.numpy as jnp

    d, L = cj["hidden_size"], cj["num_hidden_layers"]
    hq, hkv, hd = (cj["num_attention_heads"], cj["num_key_value_heads"],
                   cj["head_dim"])
    E, f, V = cj["num_local_experts"], cj["intermediate_size"], cj["vocab_size"]
    f32 = jnp.float32

    def dense(key, shape, fan_in):
        return jax.random.normal(key, shape, f32) * (fan_in ** -0.5)

    def init(key):
        ks = jax.random.split(key, 10)
        layer = {
            "ln1": jnp.zeros((L, d), f32),
            "ln2": jnp.zeros((L, d), f32),
            "mixer": {
                "wq": dense(ks[0], (L, d, hq * hd), d),
                "wk": dense(ks[1], (L, d, hkv * hd), d),
                "wv": dense(ks[2], (L, d, hkv * hd), d),
                "wo": dense(ks[3], (L, hq * hd, d), hq * hd),
            },
            "ffn": {
                "router": dense(ks[4], (L, d, E), d),
                "wg": dense(ks[5], (L, E, d, f), d),
                "wu": dense(ks[6], (L, E, d, f), d),
                "wd": dense(ks[7], (L, E, f, d), f),
            },
        }
        if cj["qk_norm"] == "per_head":
            layer["mixer"]["q_norm"] = jnp.zeros((L, hd), f32)
            layer["mixer"]["k_norm"] = jnp.zeros((L, hd), f32)
        params = {
            "embed": (dense(ks[8], (V, d), d) if cj.get("tie_word_embeddings")
                      else jax.random.normal(ks[8], (V, d), f32)),
            "final_norm": jnp.zeros((d,), f32),
            "groups": {"g0": {"p0": layer}},
        }
        if not cj.get("tie_word_embeddings"):
            params["lm_head"] = dense(ks[9], (d, V), d)
        return params

    return jax.jit(init)(jax.random.key(jax_seed(seed)))


def forward(cj: Json, params, tokens, quant: str = ""):
    """Logits (T, V) for one sequence ``tokens`` (T,), every matmul in
    float32 at HIGHEST precision. ``quant`` names a control: "int8" or
    "fp8" (e4m3) rounds every matmul weight per output channel and every
    projection's input per row to that format, and multiplies the
    rounded values in float32."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    prec = lax.Precision.HIGHEST
    act, wq = rounding(quant)
    mm = lambda a, b: jnp.matmul(act(a), b, precision=prec)
    eps = float(cj["rms_norm_eps"])
    hq, hkv, hd = (cj["num_attention_heads"], cj["num_key_value_heads"],
                   cj["head_dim"])
    k_top, theta = cj["num_experts_per_tok"], float(cj["rope_theta"])
    qk_norm = cj["qk_norm"] == "per_head"
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    head = p["embed"].T if cj.get("tie_word_embeddings") else p["lm_head"]
    if quant:  # matrices of the layers and the head; the embedding is a gather
        p = {**p, "groups": jax.tree.map(lambda a: wq(a) if a.ndim >= 3 else a,
                                         p["groups"])}
        head = wq(head)
    T = tokens.shape[0]
    x = p["embed"][tokens]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, lp):
        a = lp["mixer"]
        h = rms(x, lp["ln1"], eps)
        q = mm(h, a["wq"]).reshape(T, hq, hd)
        k = mm(h, a["wk"]).reshape(T, hkv, hd)
        v = mm(h, a["wv"]).reshape(T, hkv, hd)
        if qk_norm:
            q, k = rms(q, a["q_norm"], eps), rms(k, a["k_norm"], eps)
        q, k = rope(q, theta), rope(k, theta)
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k, precision=prec) * (hd ** -0.5)
        s = jnp.where(causal[None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hts,shd->thd", w, v, precision=prec).reshape(T, hq * hd)
        x = x + mm(o, a["wo"])

        f = lp["ffn"]
        h2 = rms(x, lp["ln2"], eps)
        probs = jax.nn.softmax(mm(h2, f["router"]).astype(jnp.float32), -1)
        kth = lax.top_k(probs, k_top)[0][:, -1:]
        gates = jnp.where(probs >= kth, probs, 0.0)  # (T, E)
        g = jnp.einsum("td,edf->tef", act(h2), f["wg"], precision=prec)
        u = jnp.einsum("td,edf->tef", act(h2), f["wu"], precision=prec)
        y = jnp.einsum("tef,efd->ted", act(jax.nn.silu(g) * u), f["wd"],
                       precision=prec)
        x = x + jnp.einsum("ted,te->td", y, gates, precision=prec)
        return x, None

    x, _ = lax.scan(layer, x, p["groups"]["g0"]["p0"])
    x = rms(x, p["final_norm"], eps)
    return mm(x, head)
