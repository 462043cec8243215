"""Architecture modules (``perfbench/archs/``): the homogeneous
attention + MoE block of both benchmark configurations pinned to the
one-block code the harness ran before each architecture had a module,
and a second architecture (``tests/data/archs/tinymix.py``) added as
files alone."""
from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from perfbench.lib import work
from perfbench.lib.model import load_config
from perfbench.tests import frozen_one_block

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
CONFIGS = {"granite": "granite-moe-1b-a400m.json", "olmoe4l": "olmoe-1b-7b.4l.json"}
LENGTHS = (128, 256, 512, 1024) + tuple(range(1, 17))  # prompts, decode rows


def _config(name):
    return load_config(BENCH / "configs" / CONFIGS[name], BENCH / "archs")


def _sizes(cj):
    return (cj["hidden_size"], cj["num_hidden_layers"], cj["num_attention_heads"],
            cj["num_key_value_heads"], cj["head_dim"], cj["num_local_experts"],
            cj["num_experts_per_tok"], cj["intermediate_size"], cj["vocab_size"])


@pytest.mark.parametrize("name,total,active", [("granite", 1334628352, 428608512),
                                               ("olmoe4l", 1884310528, 371983360)])
def test_param_count_is_the_one_block_formula(name, total, active):
    cj, arch = _config(name)
    d, L, hq, hkv, hd, E, k, f, V = _sizes(cj)
    attn = L * (2 * d * hq * hd + 2 * d * hkv * hd
                + (2 * hd if cj["qk_norm"] == "per_head" else 0))
    embed = V * d * (1 if cj.get("tie_word_embeddings") else 2)
    assert arch.param_count(cj) == {
        "attention": attn, "experts": L * E * 3 * d * f, "router": L * d * E,
        "norms": L * 2 * d + d, "embed_head": embed, "total": total,
        "active": active, "head": V * d}
    assert total == attn + L * E * 3 * d * f + L * d * E + L * 2 * d + d + embed
    assert active == attn + L * k * 3 * d * f + L * d * E + V * d
    assert 4 * total == cj["param_bytes"]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_kernel_work_is_the_one_block_formula(name):
    """Every layer runs both kernels: three grouped matmuls over the
    tokens' top-k rows, and full causal attention."""
    cj, arch = _config(name)
    d, L, hq, hkv, hd, E, k, f, _ = _sizes(cj)
    for n in LENGTHS:
        rows = n * k
        assert arch.kernel_work(cj, "moe_gmm", n) == work.Work(
            L * 3 * 2 * rows * d * f, L * 4 * 3 * (E * d * f + rows * (d + f)))
        assert arch.kernel_work(cj, "flash_attn", n) == work.Work(
            L * 4 * hq * hd * n * (n + 1) / 2, L * 4 * n * hd * (2 * hq + 2 * hkv))
    assert arch.kernel_work(cj, "ragged_dot", 128) == work.ZERO


def _small(cj):
    """The configuration's own flags (tied head, q/k norm, GQA or not)
    at widths the CPU holds."""
    gqa = cj["num_key_value_heads"] < cj["num_attention_heads"]
    return {**cj, "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2 if gqa else 4, "head_dim": 16,
            "intermediate_size": 32, "num_local_experts": 8,
            "num_experts_per_tok": 2, "vocab_size": 256}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_weights_and_reference_are_bit_identical_to_the_one_block_code(name):
    import jax

    cj, arch = _config(name)
    cj = _small(cj)
    seed = 2**33 + 3
    new, old = arch.make_weights(cj, seed), frozen_one_block.make_weights(cj, seed)
    assert jax.tree.structure(new) == jax.tree.structure(old)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))
    tokens = np.random.default_rng(5).integers(0, 256, 40).astype(np.int32)
    for quant in ("", "int8"):
        got = jax.jit(lambda p, t: arch.forward(cj, p, t, quant))(new, tokens)
        want = jax.jit(lambda p, t: frozen_one_block.forward(cj, p, t, quant))(old, tokens)
        assert np.array_equal(np.asarray(got), np.asarray(want)), quant


def test_a_configuration_names_a_module_that_exists():
    with pytest.raises(FileNotFoundError):
        load_config(DATA / "tinymix.json", BENCH / "archs")  # tinymix is a test's


def test_tinymix_kernel_work_follows_its_layers():
    """One dense and three MoE layers, the middle two windowed (8):
    windowed queries see min(t + 1, 8) keys; the routed experts of the
    MoE layers alone run ``moe_gmm``."""
    cj, arch = load_config(DATA / "tinymix.json", DATA / "archs")
    hq, hkv, hd = 4, 2, 16
    for T in (4, 8, 16, 100):
        pairs = 2 * T * (T + 1) / 2 + 2 * sum(min(t + 1, 8) for t in range(T))
        assert arch.kernel_work(cj, "flash_attn", T) == work.Work(
            4 * hq * hd * pairs, 4 * 4 * T * hd * (2 * hq + 2 * hkv))
        assert arch.kernel_work(cj, "moe_gmm", T) == work.Work(
            3 * 3 * 2 * T * 2 * 64 * 32, 3 * 4 * 3 * (8 * 64 * 32 + T * 2 * (64 + 32)))
    pc = arch.param_count(cj)
    assert pc["head"] == 256 * 64 and pc["active"] < pc["total"]
