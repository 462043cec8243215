"""``make_weights`` and ``forward`` of the one homogeneous attention +
MoE block as they stood in ``perfbench/lib/model.py`` and
``perfbench/lib/reference.py`` before each architecture got a module of
its own (``perfbench/archs/olmoe.py``), kept unchanged so that a test
can hold that module to them bit for bit. Only the shared helpers'
names changed (``_rms``, ``_rope``, ``_round`` became ``rms``, ``rope``,
``round_to``).

This copy is a pin, not code to maintain: never edit it. A change that
means to alter the numbers of ``archs/olmoe.py``'s weights or reference
deletes this file together with
``test_perfbench_archs.py::test_weights_and_reference_are_bit_identical_to_the_one_block_code``
and says so. Otherwise the next change that reworks the benchmark's own
files removes both, once a second architecture module has landed: by
then the move they prove is settled."""
from __future__ import annotations

from perfbench.lib.model import jax_seed
from perfbench.lib.reference import rms, rope, round_to


def make_weights(cj: dict, seed: int):
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


def forward(cj: dict, params, tokens, quant: str = ""):
    """Logits (T, V) for one sequence ``tokens`` (T,), every matmul in
    float32 at HIGHEST precision. ``quant`` names a control: "int8" or
    "fp8" (e4m3) rounds every matmul weight per output channel and every
    projection's input per row to that format, and multiplies the
    rounded values in float32."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    prec = lax.Precision.HIGHEST
    act = (lambda a: round_to(a, -1, quant)) if quant else (lambda a: a)
    wq = (lambda w: round_to(w, -2, quant)) if quant else (lambda w: w)
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
