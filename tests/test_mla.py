"""Multi-head latent attention (``models/mla.py``) against DeepSeek-V2's
equations at a small width: the prefill layer against a per-head
computation written out here, decode through the latent cache against
the full forward pass, YaRN at the published sizes, and what the cache
holds."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import MLASpec, YarnSpec
from repro.models import Runtime, apply_model, init_cache, init_params, prefill
from repro.models.mla import (init_mla, latent_len, mla_full, rope_inv_freq,
                              softmax_scale)
from repro.models.model import decode_step
from repro.serving.server import ContinuousBatchingServer

SPEC = MLASpec(n_heads=4, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, yarn=YarnSpec(factor=40.0,
                                            original_max_position_embeddings=4096,
                                            mscale=0.707, mscale_all_dim=0.707))


def _naive_layer(p, spec, x):
    """One MLA layer as the equations read, per head, for x (T, d)."""
    T = x.shape[0]
    H, N, P, R, V = (spec.n_heads, spec.qk_nope_head_dim, spec.qk_rope_head_dim,
                     spec.kv_lora_rank, spec.v_head_dim)
    hi = jax.lax.Precision.HIGHEST
    inv = rope_inv_freq(spec)
    ang = np.arange(T)[:, None] * inv[None]  # (T, P/2)
    cos, sin = np.cos(ang), np.sin(ang)

    def rot(v):  # (T, P): rotate halves
        a, b = v[:, :P // 2], v[:, P // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    q = jnp.matmul(x, p["wq"], precision=hi).reshape(T, H, N + P)
    ckv = jnp.matmul(x, p["wkv_a"], precision=hi)
    c = ckv[:, :R] / jnp.sqrt(jnp.mean(ckv[:, :R] ** 2, -1, keepdims=True) + 1e-6)
    c = c * (1 + p["kv_norm"])
    k_pe = rot(ckv[:, R:])
    kv = jnp.matmul(c, p["wkv_b"], precision=hi).reshape(T, H, N + V)
    outs = []
    for h in range(H):
        qh = jnp.concatenate([q[:, h, :N], rot(q[:, h, N:])], -1)
        kh = jnp.concatenate([kv[:, h, :N], k_pe], -1)
        s = jnp.matmul(qh, kh.T, precision=hi) * softmax_scale(spec)
        s = jnp.where(np.tril(np.ones((T, T), bool)), s, -jnp.inf)
        outs.append(jnp.matmul(jax.nn.softmax(s, -1), kv[:, h, N:], precision=hi))
    return jnp.matmul(jnp.concatenate(outs, -1), p["wo"], precision=hi)


@pytest.mark.parametrize("backend", ["ref", "auto"])
def test_prefill_layer_matches_the_equations(backend):
    d, T = 48, 21
    p = init_mla(jax.random.key(0), d, SPEC, jnp.float32)
    p["kv_norm"] = 0.1 * jax.random.normal(jax.random.key(2), p["kv_norm"].shape)
    x = jax.random.normal(jax.random.key(1), (1, T, d))
    y, (c_kv, k_pe) = mla_full(p, SPEC, x, jnp.arange(T)[None],
                               Runtime(kernel_backend=backend))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(_naive_layer(p, SPEC, x[0])),
                               atol=2e-5, rtol=1e-4)
    assert c_kv.shape == (1, T, 32) and k_pe.shape == (1, T, 8)


@pytest.mark.parametrize("backend", ["ref", "auto"])
def test_decode_through_the_latent_cache_matches_the_full_forward(backend):
    """Requests of different lengths prefilled into a slot pool (one slot
    left empty), then decoded together, each row at its own position:
    every active row's logits equal the full forward pass of its whole
    sequence at that position."""
    cfg = get_config("deepseek-v2-lite-smoke")
    rt = Runtime(zero_drop=True, kernel_backend=backend)
    params = init_params(jax.random.key(0), cfg, jnp.float32)
    S, steps = 24, 4
    toks = np.asarray(jax.random.randint(jax.random.key(3), (3, 20), 0, cfg.vocab))
    lens = {0: 5, 2: 11}  # slot 1 stays empty
    pool = init_cache(cfg, 3, S, jnp.float32)
    pool["pos"] = jnp.zeros((3,), jnp.int32)
    cur = np.zeros((3, 1), np.int32)
    for row, n in lens.items():
        _, pre = prefill(params, cfg, jnp.asarray(toks[row:row + 1, :n]), rt, n_slots=S)
        pool = ContinuousBatchingServer._insert_row(pool, pre, row)
        cur[row, 0] = toks[row, n]
    for t in range(steps):
        logits, pool, _ = decode_step(params, cfg, jnp.asarray(cur), pool, rt)
        for row, n in lens.items():
            seq = jnp.asarray(toks[row:row + 1, :n + t + 1])
            full, _ = apply_model(params, cfg, seq, Runtime(zero_drop=True))
            np.testing.assert_allclose(np.asarray(logits[row, 0]),
                                       np.asarray(full[0, -1]), atol=1e-4, rtol=1e-4)
            cur[row, 0] = toks[row, n + t + 1]
    assert not bool(jnp.any(jnp.isnan(logits)))


def test_yarn_and_softmax_scale_at_the_published_sizes():
    spec = get_config("deepseek-v2-lite").block_defs["moe"].attn
    i = np.arange(32)
    f_extra = 10000.0 ** (-2 * i / 64)
    mask = 1 - np.clip((i - 10) / (23 - 10), 0, 1)  # floor(10.47), ceil(22.51)
    np.testing.assert_allclose(rope_inv_freq(spec),
                               f_extra * mask + f_extra / 40 * (1 - mask), rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert math.isclose(m, 1.26080, rel_tol=1e-5)
    assert math.isclose(softmax_scale(spec), 192 ** -0.5 * m * m, rel_tol=1e-12)
    assert round(softmax_scale(spec), 6) == 0.114721


def test_the_cache_holds_the_latent_and_no_per_head_keys_or_values():
    cfg = get_config("deepseek-v2-lite")
    cache = jax.eval_shape(lambda: init_cache(cfg, 16, 4353, jnp.float32))
    for g, n in (("g0", 1), ("g1", 26)):
        c = cache[g]["p0"]
        S = latent_len(4353)
        assert S == 4608 and S % 512 == 0
        assert c.c_kv.shape == (n, 16, S, 512)
        assert c.k_pe.shape == (n, 16, S, 64)
        assert c.slot_pos.shape == (n, 16, S)
        # floats per token per layer: the latent and the shared rope key
        assert c.c_kv.shape[-1] + c.k_pe.shape[-1] == 576
        assert len(jax.tree.leaves(c)) == 3


def test_paths_without_latent_attention_refuse_it():
    """The offload engine runs grouped-query caches only: it names the
    attention it does not take."""
    from repro.core.offload_engine import OffloadedMoEEngine

    cfg = get_config("deepseek-v2-lite-smoke")
    params = init_params(jax.random.key(0), cfg, jnp.float32)
    with pytest.raises(NotImplementedError, match="MLASpec"):
        OffloadedMoEEngine(cfg, params, capacity=2)
