"""Offload engine: exactness vs the fused decode path, transfer
accounting, quantized residency, baseline policies (Sec 3.2 / Sec 4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.baselines import BASELINES, make_engine
from repro.core.offload_engine import HardwareProfile, OffloadedMoEEngine
from repro.models import Runtime, decode_step, init_params, prefill


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("granite-moe-1b-a400m-smoke")
    params = init_params(jax.random.key(0), cfg, jnp.float32)
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab)
    return cfg, params, toks


def reference_tokens(cfg, params, toks, n):
    rt = Runtime(zero_drop=True)
    lg, cache = prefill(params, cfg, toks, rt, n_slots=toks.shape[1] + n)
    out = [jnp.argmax(lg, -1).astype(jnp.int32)]
    for _ in range(n - 1):
        lg, cache, _ = decode_step(params, cfg, out[-1], cache, rt)
        out.append(jnp.argmax(lg, -1).astype(jnp.int32))
    return jnp.concatenate(out, 1)


def test_engine_exact_with_full_cache(setup):
    cfg, params, toks = setup
    E = cfg.moe_spec.num_experts
    eng = OffloadedMoEEngine(cfg, params, capacity=E)
    res = eng.generate(toks, max_new_tokens=5)
    ref = reference_tokens(cfg, params, toks, 5)
    assert bool(jnp.all(res["tokens"] == ref))


def test_engine_output_correct_even_under_tiny_cache(setup):
    """The cache changes WHEN weights move, never WHAT is computed."""
    cfg, params, toks = setup
    eng = OffloadedMoEEngine(cfg, params, capacity=1)
    res = eng.generate(toks, max_new_tokens=5)
    ref = reference_tokens(cfg, params, toks, 5)
    assert bool(jnp.all(res["tokens"] == ref))
    assert res["metrics"].transfers > 0


def test_transfers_decrease_with_capacity(setup):
    cfg, params, toks = setup
    E = cfg.moe_spec.num_experts
    tx = []
    for C in (1, 2, E):
        eng = OffloadedMoEEngine(cfg, params, capacity=C)
        res = eng.generate(toks, max_new_tokens=4)
        tx.append(res["metrics"].transfers)
    assert tx[0] >= tx[1] >= tx[2]


def test_eq3_throughput_decreases_with_transfers(setup):
    cfg, params, toks = setup
    E = cfg.moe_spec.num_experts
    r_small = OffloadedMoEEngine(cfg, params, capacity=1).generate(toks, 4)
    r_big = OffloadedMoEEngine(cfg, params, capacity=E).generate(toks, 4)
    assert r_big["throughput_tok_s"] > r_small["throughput_tok_s"]


def test_quantized_engine_runs_and_counts_smaller_transfers(setup):
    cfg, params, toks = setup
    e_fp = OffloadedMoEEngine(cfg, params, capacity=2)
    e_q = OffloadedMoEEngine(cfg, params, capacity=2, quantized=True)
    assert e_q.expert_bytes < e_fp.expert_bytes * 0.6
    res = e_q.generate(toks, max_new_tokens=3)
    assert not bool(jnp.any(res["tokens"] < 0))


@pytest.mark.parametrize("name", sorted(BASELINES))
def test_baseline_policies_run(setup, name):
    cfg, params, toks = setup
    eng = make_engine(cfg, params, BASELINES[name], capacity=2)
    res = eng.generate(toks, max_new_tokens=3)
    m = res["metrics"]
    assert m.decode_tokens == 3
    if name == "stream_all":
        # every activation transfers: K experts x L layers x tokens x batch
        K, L = cfg.moe_spec.top_k, cfg.n_moe_layers
        n_tok = toks.shape[0] * (toks.shape[1] + 2)  # prefill + 2 decode steps
        assert m.transfers == K * L * n_tok
    if name == "cpu_execute":
        assert m.transfers == 0 and m.host_executed > 0


def test_prefetch_counts_separately(setup):
    cfg, params, toks = setup
    E = cfg.moe_spec.num_experts
    eng = OffloadedMoEEngine(cfg, params, capacity=2)
    scores = np.zeros((cfg.n_moe_layers, E))
    scores[:, :2] = 1.0
    eng.prefetch(scores)
    assert eng.metrics.prefetch_transfers == cfg.n_moe_layers * 2
    assert eng.metrics.transfers == 0


class _FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("platform,kind,name", [
    ("tpu", "TPU v5 lite", "tpu-v5e"),
    ("cpu", "cpu", "tpu-v5e"),  # off-chip: the documented target profile
])
def test_hardware_profile_from_device_kind(platform, kind, name):
    from repro.core.offload_engine import TPU_PEAKS, hardware_profile

    hw = hardware_profile(_FakeDevice(platform, kind))
    assert hw.name == name
    assert (hw.peak_flops, hw.hbm_bw) == (197e12, 819e9)
    assert set(TPU_PEAKS) == {"TPU v5 lite"}


def test_hardware_profile_unknown_tpu_kind_raises():
    from repro.core.offload_engine import hardware_profile

    with pytest.raises(ValueError, match="TPU v9"):
        hardware_profile(_FakeDevice("tpu", "TPU v9"))
