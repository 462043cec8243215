"""Kernel-dispatch subsystem: backend selection unit tests, and parity
tests asserting every op family gives the same model outputs under the
"ref" and "pallas"-interpret backends (attention prefill, MoE forward,
mamba2 scan, quantized matmul)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import AttnSpec, MoESpec, SSMSpec
from repro.kernels import dispatch
from repro.models import Runtime
from repro.models import attention as attn_mod
from repro.models import mamba2 as mamba_mod
from repro.models import moe as moe_mod
from repro.models.model import apply_model, init_params

pytestmark = pytest.mark.kernels

RT_REF = Runtime(kernel_backend="ref")
RT_PALLAS = Runtime(kernel_backend="auto")  # CPU -> pallas interpret


@pytest.fixture(autouse=True)
def _no_env_backend(monkeypatch):
    """Selection/parity assertions must not depend on an externally
    exported REPRO_KERNEL_BACKEND."""
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)


# ---------------------------------------------------------------------------
# Selection unit tests
# ---------------------------------------------------------------------------


def test_resolve_ref():
    c = dispatch.resolve("moe_gmm", "ref")
    assert c.backend == "ref" and not c.use_pallas


def test_resolve_auto_on_cpu_is_pallas_interpret():
    c = dispatch.resolve("moe_gmm", "auto", platform="cpu")
    assert c.use_pallas and c.interpret
    c = dispatch.resolve("moe_gmm", "auto", platform="tpu")
    assert c.use_pallas and not c.interpret


def test_resolve_explicit_interpret_wins():
    c = dispatch.resolve("flash_attn", "pallas", interpret=True, platform="tpu")
    assert c.use_pallas and c.interpret


def test_per_op_overrides():
    spec = "auto,flash_attn=ref"
    assert dispatch.resolve("flash_attn", spec).backend == "ref"
    assert dispatch.resolve("moe_gmm", spec).use_pallas


def test_parse_spec_rejects_unknown():
    with pytest.raises(ValueError):
        dispatch.parse_spec("warp_drive=pallas")
    with pytest.raises(ValueError):
        dispatch.parse_spec("moe_gmm=cuda")
    with pytest.raises(ValueError):
        dispatch.resolve("not_an_op", "ref")


def test_env_override(monkeypatch):
    monkeypatch.setenv(dispatch.ENV_VAR, "ref")
    assert dispatch.resolve("moe_gmm", "pallas").backend == "ref"
    monkeypatch.setenv(dispatch.ENV_VAR, "moe_gmm=pallas")
    assert dispatch.resolve("moe_gmm", "ref").use_pallas
    assert dispatch.resolve("ssd_scan", "ref").backend == "ref"
    monkeypatch.delenv(dispatch.ENV_VAR)
    assert dispatch.resolve("moe_gmm", "ref").backend == "ref"


def test_env_override_merges_per_op(monkeypatch):
    """A per-op-only env override adjusts that op and leaves the
    caller's spec in force for every other family."""
    monkeypatch.setenv(dispatch.ENV_VAR, "flash_attn=ref")
    assert dispatch.resolve("flash_attn", "auto").backend == "ref"
    assert dispatch.resolve("moe_gmm", "auto").use_pallas
    assert dispatch.resolve("int4_matmul", "ref").backend == "ref"


def test_sharded_runtime_pins_ref_even_under_env(monkeypatch):
    """The shard_map path must keep the reference kernels no matter what
    REPRO_KERNEL_BACKEND says (single-device kernel bodies)."""
    monkeypatch.setenv(dispatch.ENV_VAR, "auto")
    monkeypatch.setattr(Runtime, "sharded", property(lambda self: True))
    rt = Runtime(kernel_backend="auto")
    assert rt.kernel_choice("moe_gmm").backend == "ref"
    monkeypatch.setattr(Runtime, "sharded", property(lambda self: False))
    assert rt.kernel_choice("moe_gmm").use_pallas  # env honoured unsharded


def test_runtime_legacy_use_kernels_maps_to_auto():
    rt = Runtime(use_kernels=True)
    assert rt.kernel_backend == "auto"
    assert rt.kernel_choice("moe_gmm").use_pallas
    rt = Runtime(use_kernels=False)
    assert rt.kernel_backend == "ref"
    assert not Runtime().kernel_choice("moe_gmm").use_pallas


def test_runtime_per_op_backend():
    rt = Runtime(kernel_backend="auto,ssd_scan=ref")
    assert rt.kernel_choice("moe_gmm").use_pallas
    assert not rt.kernel_choice("ssd_scan").use_pallas


# ---------------------------------------------------------------------------
# Op-level parity (ref backend vs pallas interpret)
# ---------------------------------------------------------------------------


def test_moe_forward_parity():
    spec = MoESpec(num_experts=4, top_k=2, d_ff=64, capacity_factor=2.0)
    params = moe_mod.init_moe(jax.random.key(0), 32, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (24, 32))
    y_ref, _ = moe_mod.apply_moe(params, x, spec, RT_REF)
    y_pal, _ = moe_mod.apply_moe(params, x, spec, RT_PALLAS)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)


def test_attention_prefill_parity():
    spec = AttnSpec(n_heads=4, n_kv_heads=2, head_dim=16)
    params = attn_mod.init_attn(jax.random.key(2), 32, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(3), (2, 48, 32))
    pos = jnp.broadcast_to(jnp.arange(48), (2, 48))
    y_ref = attn_mod.attend_full(params, spec, x, pos, None, rt=RT_REF)
    y_pal = attn_mod.attend_full(params, spec, x, pos, None, rt=RT_PALLAS)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-4)


def test_flash_bailout_books_ref(monkeypatch):
    """A shape the compiled kernel cannot take falls back to the
    blockwise reference, and the dispatch counter says so: it books the
    backend that ran, never the one first resolved."""
    from repro.kernels.flash_attn import ops as flash_ops
    from repro.obs.registry import REGISTRY, MetricsRegistry

    monkeypatch.setattr(flash_ops, "supported", lambda *a: False)
    spec = AttnSpec(n_heads=4, n_kv_heads=2, head_dim=16)
    params = attn_mod.init_attn(jax.random.key(2), 32, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(3), (1, 8, 32))
    pos = jnp.broadcast_to(jnp.arange(8), (1, 8))
    before = REGISTRY.snapshot()
    y = attn_mod.attend_full(params, spec, x, pos, None, rt=RT_PALLAS)
    y_ref = attn_mod.attend_full(params, spec, x, pos, None, rt=RT_REF)
    d = MetricsRegistry.diff(REGISTRY.snapshot(), before)
    booked = {k: v for k, v in d.items()
              if k.startswith("kernel_dispatch_total") and 'op="flash_attn"' in k
              and v}
    assert list(booked.values()) == [2.0], booked
    assert all('backend="ref"' in k for k in booked), booked
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


@pytest.mark.parametrize("n_groups", [1, 2])
def test_mamba2_scan_parity(n_groups):
    spec = SSMSpec(d_state=8, d_conv=4, expand=2, head_dim=8,
                   n_groups=n_groups, chunk=16)
    params = mamba_mod.init_mamba(jax.random.key(4), 32, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(5), (2, 32, 32)) * 0.3
    y_ref, st_ref = mamba_mod.apply_mamba_full(params, x, spec,
                                               return_state=True, rt=RT_REF)
    y_pal, st_pal = mamba_mod.apply_mamba_full(params, x, spec,
                                               return_state=True, rt=RT_PALLAS)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(st_pal.ssm), np.asarray(st_ref.ssm),
                               atol=1e-5, rtol=1e-4)


def test_mamba2_initial_state_parity():
    """The kernel path must honour a carried SSM state (chained prefill) —
    previously an explicit gap that silently fell back to the reference."""
    spec = SSMSpec(d_state=8, d_conv=4, expand=2, head_dim=8, n_groups=2,
                   chunk=16)
    params = mamba_mod.init_mamba(jax.random.key(6), 32, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(7), (2, 32, 32)) * 0.3
    _, st = mamba_mod.apply_mamba_full(params, x[:, :16], spec,
                                       return_state=True, rt=RT_REF)
    y_ref, _ = mamba_mod.apply_mamba_full(params, x[:, 16:], spec,
                                          init_state=st, return_state=True,
                                          rt=RT_REF)
    y_pal, _ = mamba_mod.apply_mamba_full(params, x[:, 16:], spec,
                                          init_state=st, return_state=True,
                                          rt=RT_PALLAS)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-4)
    # and chained == full-sequence (the recurrence actually carried over)
    y_full, _ = mamba_mod.apply_mamba_full(params, x, spec,
                                           return_state=True, rt=RT_PALLAS)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_full[:, 16:]),
                               atol=1e-4, rtol=1e-3)


def test_qmatmul_parity():
    from repro.core.quant import matmul_layout, qmatmul, quantize_linear

    w = jax.random.normal(jax.random.key(8), (128, 96)) * 0.05
    ql = quantize_linear(w, group=32, iters=4)
    x = jax.random.normal(jax.random.key(9), (8, 128))
    y_ref = qmatmul(x, ql, backend="ref")
    y_pal = qmatmul(x, matmul_layout(ql), backend="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)


def test_qmatmul_after_numpy_roundtrip():
    """The offload engine tree-maps whole QTensors through np.asarray for
    host storage, which turns the static shape/group ints into 0-d
    arrays — the fused path must coerce them back (regression)."""
    from repro.core.quant import QTensor, matmul_layout, qmatmul, quantize_linear

    w = jax.random.normal(jax.random.key(10), (64, 32)) * 0.05
    ql = quantize_linear(w, group=32, iters=2)
    ql_np = QTensor(*[np.asarray(f) for f in ql])  # host-store round trip
    x = jax.random.normal(jax.random.key(11), (4, 64))
    y_ref = qmatmul(x, ql, backend="ref")
    y_pal = qmatmul(x, matmul_layout(QTensor(*[jnp.asarray(f) for f in ql_np])),
                    backend="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# Model-level parity: full forward under backend "auto" on CPU routes the
# MoE + attention + mamba2 paths through Pallas interpret kernels and the
# logits must match the reference backend.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["granite-moe-1b-a400m-smoke", "mamba2-130m-smoke"])
def test_model_forward_parity(arch):
    cfg = get_config(arch)
    params = init_params(jax.random.key(0), cfg, jnp.float32)
    toks = jax.random.randint(jax.random.key(1), (2, 24), 0, cfg.vocab)
    logits_ref, _ = apply_model(params, cfg, toks, RT_REF)
    logits_pal, _ = apply_model(params, cfg, toks, RT_PALLAS)
    np.testing.assert_allclose(np.asarray(logits_pal), np.asarray(logits_ref),
                               atol=2e-4, rtol=1e-3)
