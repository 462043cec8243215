"""The main path's Pallas kernels compile for a TPU v5e at granite-moe-
1b-a400m widths, and DeepSeek-V2-Lite's latent attention at its own.
The chip's compiler is installed without a chip: it compiles for a
described v5e:2x2 topology and refuses what the chip would refuse
(unaligned blocks, VMEM overflow) — which interpret mode never checks.
Nothing runs, so these tests say nothing about results.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file."""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attn.ops import flash_pallas
from repro.kernels.mla_decode.ops import mla_decode_pallas
from repro.kernels.moe_gmm.ops import gmm_pallas

pytestmark = pytest.mark.kernels

# granite-moe-1b-a400m: 16 q heads over 8 kv heads, head_dim 64;
# 32 experts, d_model 1024, expert d_ff 512; offloaded cache C = E/4
HKV, G, HD = 8, 2, 64
N_EXPERTS, CAPACITY = 32, 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep these compiles out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    if prev_log is None:
        os.environ.pop("TPU_LOG_DIR", None)


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _has_kernel(text: str, name: str) -> bool:
    return any(f"%{name}" in line and 'custom_call_target="tpu_custom_call"'
               in line for line in text.splitlines())


@pytest.mark.parametrize("T", [1, 23, 256])
def test_flash_attn_compiles_for_v5e(one_chip, T):
    q = jax.ShapeDtypeStruct((1, T, HKV, G, HD), jnp.float32, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, T, HKV, HD), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v: flash_pallas(q, k, v, interpret=False), q, kv, kv)
    assert _has_kernel(text, "flash_attn")


@pytest.mark.parametrize("M", [4, 23, 200])
@pytest.mark.parametrize("K,N", [(1024, 512), (512, 1024)])
@pytest.mark.parametrize("ragged", [False, True], ids=["plain", "ragged"])
def test_moe_gmm_compiles_for_v5e(one_chip, M, K, N, ragged):
    E = CAPACITY if ragged else N_EXPERTS
    a = jax.ShapeDtypeStruct((E, M, K), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((E, K, N), jnp.float32, sharding=one_chip)
    if ragged:
        sizes = jax.ShapeDtypeStruct((E,), jnp.int32, sharding=one_chip)
        text = _compiled_text(
            lambda a, b, s: gmm_pallas(a, b, s, interpret=False), a, b, sizes)
        assert _has_kernel(text, "moe_gmm_ragged")
    else:
        text = _compiled_text(lambda a, b: gmm_pallas(a, b, interpret=False),
                              a, b)
        assert _has_kernel(text, "moe_gmm")


# DeepSeek-V2-Lite: 16 heads, q/k 128 + 64 rope, v 128, a 512-wide latent;
# 16 slots of a 4,353-position cache, held as 4,608 (whole 512-blocks)
MLA_H, MLA_QK, MLA_V, MLA_R, MLA_P = 16, 192, 128, 512, 64


@pytest.mark.parametrize("T", [1024, 4096])
def test_flash_attn_compiles_for_v5e_at_latent_widths(one_chip, T):
    q = jax.ShapeDtypeStruct((1, T, MLA_H, 1, MLA_QK), jnp.float32, sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, T, MLA_H, MLA_QK), jnp.float32, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, T, MLA_H, MLA_V), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v: flash_pallas(q, k, v, interpret=False, scale=0.114721),
        q, k, v)
    assert _has_kernel(text, "flash_attn")


def test_mla_decode_compiles_for_v5e(one_chip):
    B, S = 16, 4608
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    text = _compiled_text(
        lambda *a: mla_decode_pallas(*a, scale=0.114721, interpret=False),
        f32(B, MLA_H, MLA_R), f32(B, MLA_H, MLA_P), f32(B, S, MLA_R),
        f32(B, S, MLA_P), i32(B, S), i32(B))
    assert _has_kernel(text, "mla_decode")
    # the rotary keys reach the kernel positions-minor without a copy
    assert not any(l.lstrip().startswith("%copy") for l in text.splitlines()
                   if "f32[16,4608,64]" in l)
