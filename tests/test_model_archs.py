"""Per-architecture smoke tests (deliverable f): instantiate the REDUCED
variant of each assigned family, run one forward and one train step on
CPU, assert output shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, get_config
from repro.launch.steps import build_train_step
from repro.models import Runtime, apply_model, decode_step, init_params, prefill
from repro.training.optim import OptConfig, init_opt_state

ALL = list(ASSIGNED) + ["olmoe", "mixtral-8x7b", "phi35-moe", "deepseek-v2-lite"]


def make_batch(cfg, B=2, T=24, seed=1):
    toks = jax.random.randint(jax.random.key(seed), (B, T), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    if cfg.prefix_len:
        batch["prefix_embed"] = jax.random.normal(
            jax.random.key(seed + 1), (B, cfg.prefix_len, cfg.d_model)
        )
    return batch


@pytest.mark.parametrize("arch", ALL)
def test_smoke_forward(arch):
    cfg = get_config(arch + "-smoke")
    assert cfg.n_layers <= 3 and cfg.d_model <= 512
    if cfg.moe_spec:
        assert cfg.moe_spec.num_experts <= 4
    params = init_params(jax.random.key(0), cfg, jnp.float32)
    batch = make_batch(cfg)
    logits, aux = apply_model(
        params, cfg, batch["tokens"], Runtime(),
        prefix_embed=batch.get("prefix_embed"),
    )
    B, T = batch["tokens"].shape
    assert logits.shape == (B, T + cfg.prefix_len, cfg.vocab)
    assert not bool(jnp.any(jnp.isnan(logits)))


@pytest.mark.parametrize("arch", ALL)
def test_smoke_train_step(arch):
    cfg = get_config(arch + "-smoke")
    rt = Runtime()
    params = init_params(jax.random.key(0), cfg, jnp.float32)
    opt = init_opt_state(params)
    step = jax.jit(build_train_step(cfg, rt, OptConfig(peak_lr=1e-3, total_steps=10)))
    batch = make_batch(cfg)
    params2, opt2, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # parameters actually moved
    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda a, b: float(jnp.abs(a - b).sum()), params, params2),
    )
    assert delta > 0


@pytest.mark.parametrize("arch", ["qwen3-4b", "zamba2-7b", "mamba2-130m",
                                  "deepseek-moe-16b", "gemma2-27b",
                                  "deepseek-v2-lite"])
def test_smoke_decode_step(arch):
    cfg = get_config(arch + "-smoke")
    rt = Runtime(zero_drop=True)
    params = init_params(jax.random.key(0), cfg, jnp.float32)
    batch = make_batch(cfg)
    lg, cache = prefill(params, cfg, batch["tokens"], rt,
                        prefix_embed=batch.get("prefix_embed"), n_slots=40)
    nt = jnp.argmax(lg, -1).astype(jnp.int32)
    lg2, cache, _ = decode_step(params, cfg, nt, cache, rt)
    assert lg2.shape == (2, 1, cfg.vocab)
    assert not bool(jnp.any(jnp.isnan(lg2)))
    assert int(cache["pos"]) == batch["tokens"].shape[1] + cfg.prefix_len + 1
