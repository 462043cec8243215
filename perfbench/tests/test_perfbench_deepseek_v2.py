"""``perfbench/archs/deepseek_v2.py``: DeepSeek-V2-Lite's configuration
counted from its published sizes, the work of its three kernels, and a
tiny configuration of the same architecture (latent attention, a
leading dense layer, shared experts and one share of the routed
experts) served by the continuous server on the CPU and judged by the
plain reference."""
from __future__ import annotations

import math
import time
from pathlib import Path

import numpy as np
import pytest

from perfbench.lib import serve, traffic, work
from perfbench.lib.model import load_config

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"


def _dsv2lite():
    return load_config(BENCH / "configs" / "deepseek-v2-lite.5l-e32.json",
                       BENCH / "archs")


def test_param_count_of_the_published_sizes():
    cj, arch = _dsv2lite()
    attention = 5 * (2048 * 16 * 192 + 2048 * 576 + 512 + 512 * 16 * 256
                     + 16 * 128 * 2048)
    dense = 3 * 2048 * 10944
    experts = 4 * 32 * 3 * 2048 * 1408
    shared = 4 * 3 * 2048 * 2 * 1408
    router = 4 * 2048 * 64
    norms = 5 * 2 * 2048 + 2048
    head = 102400 * 2048
    pc = arch.param_count(cj)
    assert pc == {
        "attention": attention, "dense_mlp": dense, "experts": experts,
        "shared_experts": shared, "router": router, "norms": norms,
        "embed_head": 2 * head,
        "total": attention + dense + experts + shared + router + norms + 2 * head,
        # a token sends 6 x 32 / 64 = 3 of its routes to the experts held here
        "active": attention + dense + 4 * 3 * 3 * 2048 * 1408 + shared + router + head,
        "head": head}
    assert attention == 5 * 13_763_072
    assert 4 * pc["total"] == 6_930_139_136 == cj["param_bytes"]
    assert arch.experts(cj) == (32, 6)


@pytest.mark.parametrize("n", [1, 16, 1000, 4096, 43_000])
def test_kernel_work_of_the_published_sizes(n):
    cj, arch = _dsv2lite()
    # mla_decode: n latent positions per layer, 576 floats each; every one
    # of 16 heads scores each (576 wide) and sums it (512 wide)
    assert arch.kernel_work(cj, "mla_decode", n) == work.Work(
        5 * 2 * 16 * (576 + 512) * n, 5 * 4 * 576 * n)
    # flash_attn: causal prefill of n tokens, q/k 192 and v 128 per head
    pairs = n * (n + 1) / 2
    assert arch.kernel_work(cj, "flash_attn", n) == work.Work(
        5 * 2 * 16 * (192 + 128) * pairs, 5 * 4 * n * 16 * (192 + 192 + 128 + 128))
    # moe_gmm: 4 MoE layers, 3 routed rows per token over 32 held experts
    rows = 3 * n
    assert arch.kernel_work(cj, "moe_gmm", n) == work.Work(
        4 * 3 * 2 * rows * 2048 * 1408,
        4 * 4 * 3 * (32 * 2048 * 1408 + rows * (2048 + 1408)))
    assert arch.kernel_work(cj, "ssd_scan", n) == work.ZERO


def test_prefill_attention_at_4096_is_the_issue_arithmetic():
    cj, arch = _dsv2lite()
    assert math.isclose(arch.kernel_work(cj, "flash_attn", 4096).flops, 4.296e11,
                        rel_tol=1e-3)


def test_yarn_frequencies_and_scale_of_the_published_sizes():
    cj, arch = _dsv2lite()
    i = np.arange(32)
    f_extra = 10000.0 ** (-2 * i / 64)
    mask = 1 - np.clip((i - 10) / (23 - 10), 0, 1)
    np.testing.assert_allclose(arch.yarn_inv_freq(cj),
                               f_extra * mask + f_extra / 40 * (1 - mask), rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert math.isclose(arch.softmax_scale(cj), 192 ** -0.5 * m * m)
    assert round(arch.softmax_scale(cj), 6) == 0.114721


def _run(fault=None):
    cj, arch = load_config(DATA / "tiny-dsv2.json", BENCH / "archs")
    t = traffic.load_traffic(DATA / "tiny-chat.json")
    ms = [{"name": "output_tok_s", "unit": "tokens/s"}]
    return serve.run_cell({"name": cj["name"]}, cj, arch, t, ms, BENCH / "metrics",
                          BENCH / ".cache" / "tests", 2**33 + 5, 2.0, False,
                          time.perf_counter(), fault=fault)


def _alter_tokens(srv):
    """A token altered where it is produced: greedy picks the next id."""
    import repro.serving.server as server_mod

    real = server_mod.greedy
    server_mod.greedy = lambda logits: (real(logits) + 1) % logits.shape[-1]


def _stale_latent_rows(srv):
    """A prefilled request's latent rows never reach its slot: the splice
    keeps the pool's ``c_kv`` as it was, so decode reads the slot's
    previous rows."""
    import jax

    from repro.models.mla import LatentCache

    real = srv._insert_jit

    def insert(cache, pre, slot):
        out = real(cache, pre, slot)
        return jax.tree.map(
            lambda new, old: new._replace(c_kv=old.c_kv) if isinstance(new, LatentCache)
            else new, out, cache, is_leaf=lambda x: isinstance(x, LatentCache))

    srv._insert_jit = insert


@pytest.mark.parametrize("fault", [None, _alter_tokens, _stale_latent_rows],
                         ids=["sound", "token_altered", "stale_latent_rows"])
def test_the_tiny_configuration_is_judged_by_its_reference(fault):
    import repro.serving.server as server_mod

    real = server_mod.greedy
    try:
        out = _run(fault)
    finally:
        server_mod.greedy = real
    gap = out["checks"]["mean_logit_gap"]
    assert out["attempted"] == 12 and out["failed"] == 0
    if fault is None:
        assert out["correct"] and gap["value"] <= gap["limit"], out["checks"]
    else:
        assert not out["correct"] and gap["value"] > gap["limit"]


def test_the_mla_decode_reader_on_made_up_spans_and_trace():
    """The new reader charges each traced decode step the work of the
    positions its span records, against the ``mla_decode`` time inside
    it; an older program's span without ``cached_positions`` reads
    nothing."""
    from types import SimpleNamespace

    from perfbench.lib import peaks, trace_reduce
    from perfbench.lib.serve import load_reader

    cj, arch = _dsv2lite()
    pk = peaks.peaks_for("TPU v5 lite")
    read = load_reader(BENCH / "metrics" / "mla_decode_roofline.fits.py")
    t_min = arch.kernel_work(cj, "mla_decode", 40_000).min_seconds(
        pk.flops_bf16, pk.hbm_bytes_s)

    def ctx(args):
        step = SimpleNamespace(name="serve.decode_step", t0=0.010, dur=0.050,
                               args=args)
        ops = [("mla_decode.7", 12e6, 12e6 + 2 * t_min * 1e9),  # twice the bound
               ("moe_gmm.3", 20e6, 30e6)]
        trace = trace_reduce.Trace(device_ops=ops, host_spans=[], window=(0, 1e9),
                                   per_device=[ops])
        trace.perf_at_window = 0.0
        return SimpleNamespace(spans=[step], trace=trace, peaks=pk, cj=cj, arch=arch)

    assert read(ctx({"active": 16, "cached_positions": 40_000})) == pytest.approx(50.0)
    assert read(ctx({"active": 16})) is None
