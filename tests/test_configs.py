"""Config registry: exact assigned hyper-parameters + input specs."""
import jax.numpy as jnp
import pytest

from repro.configs import ASSIGNED, SHAPES, get_config, list_archs
from repro.launch.specs import decode_window_override, input_specs

EXPECT = {
    "musicgen-medium": dict(L=48, d=1536, H=24, kv=24, vocab=2048, family="audio"),
    "gemma2-27b": dict(L=46, d=4608, H=32, kv=16, vocab=256000, family="dense"),
    "granite-moe-1b-a400m": dict(L=24, d=1024, H=16, kv=8, vocab=49155, family="moe",
                                 E=32, K=8),
    "stablelm-12b": dict(L=40, d=5120, H=32, kv=8, vocab=100352, family="dense"),
    "zamba2-7b": dict(L=81, d=3584, vocab=32000, family="hybrid"),
    "command-r-plus-104b": dict(L=64, d=12288, H=96, kv=8, vocab=256000, family="dense"),
    "deepseek-moe-16b": dict(L=28, d=2048, H=16, kv=16, vocab=102400, family="moe",
                             E=64, K=6),
    "internvl2-76b": dict(L=80, d=8192, H=64, kv=8, vocab=128256, family="vlm"),
    "qwen3-4b": dict(L=36, d=2560, H=32, kv=8, vocab=151936, family="dense"),
    "mamba2-130m": dict(L=24, d=768, vocab=50280, family="ssm"),
    "deepseek-v2-lite": dict(L=27, d=2048, vocab=102400, family="moe", E=64, K=6,
                             mla=(16, 512, 128, 64, 128)),
}


@pytest.mark.parametrize("arch", sorted(EXPECT))
def test_assigned_configs_exact(arch):
    cfg = get_config(arch)
    e = EXPECT[arch]
    assert cfg.n_layers == e["L"]
    assert cfg.d_model == e["d"]
    assert cfg.vocab == e["vocab"]
    assert cfg.family == e["family"]
    if "H" in e:
        attn = next(b.attn for b in cfg.block_defs.values() if b.attn is not None)
        assert attn.n_heads == e["H"] and attn.n_kv_heads == e["kv"]
    if "E" in e:
        assert cfg.moe_spec.num_experts == e["E"]
        assert cfg.moe_spec.top_k == e["K"]
    if "mla" in e:  # latent attention: heads, latent, q/k nope + rope, v
        a = next(b.attn for b in cfg.block_defs.values())
        assert (a.n_heads, a.kv_lora_rank, a.qk_nope_head_dim, a.qk_rope_head_dim,
                a.v_head_dim) == e["mla"]
    assert cfg.source  # every config cites its source


def test_all_assigned_present():
    assert set(ASSIGNED) <= set(list_archs())
    assert len(ASSIGNED) == 10


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_input_specs_shapes(shape):
    cfg = get_config("granite-moe-1b-a400m")
    sh = SHAPES[shape]
    specs = input_specs(cfg, sh)
    if sh.mode == "train":
        assert specs["tokens"].shape == (sh.global_batch, sh.seq_len)
        assert specs["labels"].shape == (sh.global_batch, sh.seq_len)
    elif sh.mode == "prefill":
        assert specs["tokens"].shape == (sh.global_batch, sh.seq_len)
    else:
        assert specs["tokens"].shape == (sh.global_batch, 1)
        assert "cache" in specs


def test_long_context_policy():
    assert decode_window_override(get_config("mamba2-130m"), SHAPES["long_500k"]) is None
    assert decode_window_override(get_config("command-r-plus-104b"),
                                  SHAPES["long_500k"]) == 8192
    assert decode_window_override(get_config("command-r-plus-104b"),
                                  SHAPES["decode_32k"]) is None


def test_long_500k_cache_is_bounded():
    """The 500k decode cache must use the ring-buffer window, not 500k slots."""
    import jax

    cfg = get_config("qwen3-4b")
    specs = input_specs(cfg, SHAPES["long_500k"])
    kv_leaves = [
        l for l in jax.tree.leaves(specs["cache"]) if getattr(l, "ndim", 0) == 5
    ]
    assert kv_leaves and all(l.shape[2] == cfg.long_context_window for l in kv_leaves)


def test_melinoe_capacity_default_quarter():
    cfg = get_config("granite-moe-1b-a400m")
    assert cfg.melinoe_cache_capacity() == 8  # E/4 = 32/4
    assert get_config("olmoe").melinoe_cache_capacity() == 16  # paper C=16
