"""A test-only architecture, added as files alone: the first
``first_k_dense_replace`` layers are attention + dense SwiGLU of
``intermediate_size``; the rest attention + MoE, whose routed experts
(softmax over all, top-k raw probabilities as gates) of
``moe_intermediate_size`` are joined by ``n_shared_experts`` shared
experts fused into one SwiGLU that every token passes through. Each
layer's ``layer_types`` entry makes its attention full causal or a
sliding window of ``sliding_window`` keys (query t sees keys
t - window + 1 .. t). No q/k norm, an untied head; RMSNorm gains in
the (1 + w) form.

The program runs it as two layout groups of one repeat each: the dense
layers, then the MoE layers, each layer its own block of the pattern.
"""
from __future__ import annotations

from perfbench.lib import work
from perfbench.lib.model import jax_seed
from perfbench.lib.reference import rms, rope, rounding

KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "first_k_dense_replace",
        "intermediate_size", "layer_types", "sliding_window",
        "num_local_experts", "num_experts_per_tok", "moe_intermediate_size",
        "n_shared_experts", "rope_theta", "rms_norm_eps")
TYPES = ("full_attention", "sliding_attention")


def check(cj):
    for key in KEYS:
        if key not in cj:
            raise KeyError(f"{cj['name']}: configuration lacks {key!r}")
    if len(cj["layer_types"]) != cj["num_hidden_layers"]:
        raise ValueError(f"{cj['name']}: one layer_types entry per layer")
    if not set(cj["layer_types"]) <= set(TYPES):
        raise ValueError(f"{cj['name']}: layer_types must be among {TYPES}")


def _layers(cj):
    """(group, position, is_moe, window) of each layer, in order."""
    k = cj["first_k_dense_replace"]
    out = []
    for i, kind in enumerate(cj["layer_types"]):
        window = cj["sliding_window"] if kind == "sliding_attention" else None
        out.append((0, i, False, window) if i < k else
                   (int(k > 0), i - k, True, window))
    return out


def _shared_width(cj):
    return cj["n_shared_experts"] * cj["moe_intermediate_size"]


def param_count(cj):
    d, V = cj["hidden_size"], cj["vocab_size"]
    hq, hkv, hd = (cj["num_attention_heads"], cj["num_key_value_heads"],
                   cj["head_dim"])
    E, k, f = (cj["num_local_experts"], cj["num_experts_per_tok"],
               cj["moe_intermediate_size"])
    layers = _layers(cj)
    n_moe = sum(m for _, _, m, _ in layers)
    n_dense = len(layers) - n_moe
    attn = len(layers) * (2 * d * hq * hd + 2 * d * hkv * hd)
    dense = n_dense * 3 * d * cj["intermediate_size"]
    experts = n_moe * E * 3 * d * f
    shared = n_moe * 3 * d * _shared_width(cj)
    router = n_moe * d * E
    norms = len(layers) * 2 * d + d
    head = V * d  # untied: the embedding is as large again
    return {"total": attn + dense + experts + shared + router + norms + 2 * head,
            "active": attn + dense + n_moe * k * 3 * d * f + shared + router + head,
            "head": head}


def experts(cj):
    return cj["num_local_experts"], cj["num_experts_per_tok"]


def kernel_work(cj, kernel, tokens):
    """Every layer runs ``flash_attn`` at its own window; the MoE layers'
    routed experts run ``moe_gmm`` (the shared experts are a dense
    matmul)."""
    total = work.ZERO
    for _, _, is_moe, window in _layers(cj):
        if kernel == "flash_attn":
            total = total + work.causal_attention(
                tokens, cj["num_attention_heads"], cj["num_key_value_heads"],
                cj["head_dim"], window)
        elif kernel == "moe_gmm" and is_moe:
            total = total + work.moe_gmms(
                tokens, cj["hidden_size"], cj["moe_intermediate_size"],
                cj["num_local_experts"], cj["num_experts_per_tok"])
    return total


def program_config(cj):
    from repro.configs.base import (AttnSpec, BlockSpec, LayoutGroup,
                                    ModelConfig, MoESpec)

    moe = MoESpec(num_experts=cj["num_local_experts"],
                  top_k=cj["num_experts_per_tok"],
                  d_ff=cj["moe_intermediate_size"],
                  num_shared=cj["n_shared_experts"], shared_d_ff=_shared_width(cj))
    defs, patterns = {}, ([], [])
    for g, _, is_moe, window in _layers(cj):
        name = f"{'moe' if is_moe else 'dense'}_{window or 'full'}"
        attn = AttnSpec(n_heads=cj["num_attention_heads"],
                        n_kv_heads=cj["num_key_value_heads"],
                        head_dim=cj["head_dim"], rope_theta=float(cj["rope_theta"]),
                        window=window)
        defs[name] = (BlockSpec(kind="attn_moe", attn=attn, moe=moe) if is_moe else
                      BlockSpec(kind="attn_dense", attn=attn,
                                d_ff=cj["intermediate_size"]))
        patterns[g].append(name)
    return ModelConfig(
        name=cj["name"], family="moe", d_model=cj["hidden_size"],
        vocab=cj["vocab_size"], block_defs=defs,
        layout=tuple(LayoutGroup(tuple(p), 1) for p in patterns if p),
        norm_eps=float(cj["rms_norm_eps"]),
        dtype="float32", source=cj["source"])


def make_weights(cj, seed):
    """Seeded weights in the program's tree (each block stacked over its
    one repeat), in one jitted call: projections N(0, 1/fan_in), norm
    gains zero, the embedding N(0, 1)."""
    import jax
    import jax.numpy as jnp

    d, V = cj["hidden_size"], cj["vocab_size"]
    hq, hkv, hd = (cj["num_attention_heads"], cj["num_key_value_heads"],
                   cj["head_dim"])
    E, f, fs = (cj["num_local_experts"], cj["moe_intermediate_size"],
                _shared_width(cj))
    f32 = jnp.float32

    def dense(key, shape, fan_in):
        return jax.random.normal(key, (1,) + shape, f32) * (fan_in ** -0.5)

    def swiglu(ks, width):
        return {"wg": dense(ks[0], (d, width), d), "wu": dense(ks[1], (d, width), d),
                "wd": dense(ks[2], (width, d), width)}

    def block(key, is_moe):
        ks = jax.random.split(key, 11)
        p = {"ln1": jnp.zeros((1, d), f32), "ln2": jnp.zeros((1, d), f32),
             "mixer": {"wq": dense(ks[0], (d, hq * hd), d),
                       "wk": dense(ks[1], (d, hkv * hd), d),
                       "wv": dense(ks[2], (d, hkv * hd), d),
                       "wo": dense(ks[3], (hq * hd, d), hq * hd)}}
        if not is_moe:
            p["ffn"] = swiglu(ks[4:7], cj["intermediate_size"])
            return p
        p["ffn"] = {"router": dense(ks[4], (d, E), d),
                    "wg": dense(ks[5], (E, d, f), d), "wu": dense(ks[6], (E, d, f), d),
                    "wd": dense(ks[7], (E, f, d), f), "shared": swiglu(ks[8:11], fs)}
        return p

    def init(key):
        ks = jax.random.split(key, 3 + cj["num_hidden_layers"])
        groups = {}
        for i, (g, pos, is_moe, _) in enumerate(_layers(cj)):
            groups.setdefault(f"g{g}", {})[f"p{pos}"] = block(ks[3 + i], is_moe)
        return {"embed": jax.random.normal(ks[0], (V, d), f32),
                "lm_head": dense(ks[1], (d, V), d)[0],
                "final_norm": jnp.zeros((d,), f32), "groups": groups}

    return jax.jit(init)(jax.random.key(jax_seed(seed)))


def forward(cj, params, tokens, quant=""):
    """Logits (T, V) in float32 at HIGHEST, layer by layer; ``quant``
    rounds matmul weights and projection inputs as a control does."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    prec = lax.Precision.HIGHEST
    act, wq = rounding(quant)
    mm = lambda a, b: jnp.matmul(act(a), b, precision=prec)
    eps, theta = float(cj["rms_norm_eps"]), float(cj["rope_theta"])
    hq, hkv, hd = (cj["num_attention_heads"], cj["num_key_value_heads"],
                   cj["head_dim"])
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    head = p["lm_head"]
    if quant:
        p = {**p, "groups": jax.tree.map(lambda a: wq(a) if a.ndim >= 3 else a,
                                         p["groups"])}
        head = wq(head)
    T = tokens.shape[0]
    t, s = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    x = p["embed"][tokens]

    def swiglu(w, h):
        return mm(jax.nn.silu(mm(h, w["wg"])) * mm(h, w["wu"]), w["wd"])

    for g, pos, is_moe, window in _layers(cj):
        lp = jax.tree.map(lambda a: a[0], p["groups"][f"g{g}"][f"p{pos}"])
        a = lp["mixer"]
        h = rms(x, lp["ln1"], eps)
        q = rope(mm(h, a["wq"]).reshape(T, hq, hd), theta)
        k = rope(mm(h, a["wk"]).reshape(T, hkv, hd), theta)
        v = mm(h, a["wv"]).reshape(T, hkv, hd)
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
        seen = (s <= t) if window is None else (s <= t) & (s > t - window)
        sc = jnp.einsum("thd,shd->hts", q, k, precision=prec) * (hd ** -0.5)
        w = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,shd->thd", w, v, precision=prec).reshape(T, hq * hd)
        x = x + mm(o, a["wo"])

        f = lp["ffn"]
        h2 = rms(x, lp["ln2"], eps)
        if not is_moe:
            x = x + swiglu(f, h2)
            continue
        probs = jax.nn.softmax(mm(h2, f["router"]), -1)
        kth = lax.top_k(probs, cj["num_experts_per_tok"])[0][:, -1:]
        gates = jnp.where(probs >= kth, probs, 0.0)
        gu = jax.nn.silu(jnp.einsum("td,edf->tef", act(h2), f["wg"], precision=prec))
        gu = gu * jnp.einsum("td,edf->tef", act(h2), f["wu"], precision=prec)
        y = jnp.einsum("tef,efd->ted", act(gu), f["wd"], precision=prec)
        x = x + jnp.einsum("ted,te->td", y, gates, precision=prec) + swiglu(f["shared"], h2)
    return mm(rms(x, p["final_norm"], eps), head)
