"""The benchmark's arithmetic on made-up inputs: traffic, percentiles
and rates, trace reduction, kernel work, peaks."""
from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench.lib import peaks, readers, stats, trace_reduce, traffic, work
from perfbench.lib.model import load_config

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"


# ---- traffic -------------------------------------------------------------

def test_traffic_is_deterministic_per_seed_and_the_schedule_is_seed_free():
    t = traffic.load_traffic(DATA / "tiny-chat.json")
    a = traffic.build_requests(t, 256, seed=2**33 + 5, seconds=10)
    b = traffic.build_requests(t, 256, seed=2**33 + 5, seconds=10)
    c = traffic.build_requests(t, 256, seed=11, seconds=10)
    assert [(r.due, r.max_new, r.prompt.tolist()) for r in a] == \
           [(r.due, r.max_new, r.prompt.tolist()) for r in b]
    assert len(a) == len(c) == 60  # round(6 req/s * 10 s)
    sched = lambda rs: [(r.due, len(r.prompt), r.max_new) for r in rs]
    assert sched(a) == sched(c)  # seeds change tokens, not the schedule
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]
    assert sorted(len(r.prompt) for r in a) == [8] * 30 + [16] * 30
    other = traffic.build_requests({**t, "schedule_seed": 99}, 256, 11, 10)
    assert sched(other) != sched(a)
    gaps = lambda rs: sorted(np.diff([0.0] + [r.due for r in rs]).round(9))
    assert np.allclose(gaps(a), gaps(other))  # same gaps, another order
    due = [r.due for r in a]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 10


def test_exact_counts_and_quantiles():
    assert traffic.exact_counts(10, [0.4, 0.3, 0.2, 0.1]) == [4, 3, 2, 1]
    assert sum(traffic.exact_counts(7, [0.4, 0.3, 0.2, 0.1])) == 7
    q = traffic.quantile_sizes(4, {"min": 32, "max": 256})
    assert q.tolist() == [60, 116, 172, 228]


def test_closed_loop_pool_follows_the_cycle():
    t = traffic.load_traffic(DATA / "tiny-solo.json")
    rs = traffic.build_requests(t, 256, seed=3, seconds=5)
    assert [(len(r.prompt), r.max_new) for r in rs] == [(8, 4), (16, 3)] * 3
    assert all(r.due is None for r in rs)


def test_benchmark_files_resolve():
    """Every cell's configuration, traffic and metric reader exists, and
    each configuration states its parameter bytes as its sizes give them."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        cj, arch = load_config(BENCH.parent / c["file"], BENCH / "archs")
        assert 4 * arch.param_count(cj)["total"] == cj["param_bytes"], c["name"]
        for key in c["reduced"]:
            assert key in cj["reduced"], (c["name"], key)
    for w in bench["workloads"]:
        traffic.load_traffic(BENCH / "traffic" / f"{w['traffic']}.json")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists(), m["name"]


# ---- percentiles and rates -----------------------------------------------

def test_percentile_and_rates_on_made_up_stamps():
    assert stats.percentile([], 90) is None
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(range(101), 90) == pytest.approx(90)
    times = {0: [1.5, 1.6, 1.8], 1: [2.0, 2.5]}
    due = {0: 1.0, 1: 1.9}
    assert stats.ttfts_s(times, due) == pytest.approx([0.5, 0.1])
    assert sorted(stats.token_gaps_s(times)) == pytest.approx([0.1, 0.2, 0.5])
    assert stats.tokens_in_window(times, 1.55, 2.5) == 3


def _read(name):
    from perfbench.lib.serve import load_reader

    return load_reader(BENCH / "metrics" / f"{name}.py")


def test_end_to_end_readers_on_made_up_stamps():
    st = SimpleNamespace(token_times={0: [10.5, 10.6, 12.1], 1: [11.0, 11.2]},
                         retired={0: 12.1, 1: 11.2})
    ctx = SimpleNamespace(kind="continuous", stamps=st, attempted=[0, 1],
                          due_abs={0: 10.1, 1: 10.9}, t0=10.0, seconds=2.0,
                          setup_s=3.5)
    assert _read("setup_s")(ctx) == 3.5
    assert _read("ttft_p90_ms")(ctx) == pytest.approx(1e3 * (0.1 + 0.9 * 0.3))
    gaps = [0.1, 1.5, 0.2]
    assert _read("itl_p90_ms")(ctx) == pytest.approx(1e3 * np.percentile(gaps, 90))
    assert _read("output_tok_s")(ctx) == pytest.approx(4 / 2.0)  # 12.1 is out
    ctx.kind = "wave"
    assert _read("ttft_p90_ms")(ctx) is None
    assert _read("output_tok_s")(ctx) == pytest.approx(5 / 2.1)


# ---- trace reduction -----------------------------------------------------

def _trace():
    ops = [("while.2", 100, 300), ("fusion.1", 100, 200), ("moe_gmm", 150, 300),
           ("flash_attn", 500, 600),
           ("moe_gmm.2", 650, 700), ("fusion.9", 950, 1000)]
    spans = [("bench.window", 0, 1000), ("serve.prefill", 90, 620),
             ("serve.decode_step", 640, 720), ("moe.fetch", 800, 900)]
    return trace_reduce.Trace(device_ops=ops, host_spans=spans, window=(0, 1000),
                              per_device=[ops])


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40), (35, 38)]
    assert trace_reduce.union_ns(iv, 0, 100) == 30
    assert trace_reduce.union_ns(iv, 8, 36) == 18
    assert trace_reduce.gaps_ns(iv, 0, 50) == [(20, 30), (40, 50)]
    assert trace_reduce.gaps_ns([], 0, 5) == [(0, 5)]


def test_trace_busy_idle_and_breakdown():
    tr = _trace()
    assert tr.window_s == pytest.approx(1e-6)
    assert tr.busy_s == pytest.approx((200 + 100 + 50 + 50) * 1e-9)
    bd = tr.breakdown()
    assert bd["device_ops"][0] == ["moe_gmm", pytest.approx(150e-9)]
    assert len(bd["device_ops"]) == 5
    # gaps, named by the span at their middle: 0-100 none, 300-500
    # prefill, 600-650 none (625 is between spans), 700-950 fetch
    assert bd["idle_gaps"] == [["moe.fetch", pytest.approx(250e-9)],
                               ["serve.prefill", pytest.approx(200e-9)],
                               ["no host span", pytest.approx(150e-9)]]


def test_from_xspace_reads_device_ops_and_host_spans():
    ev = lambda n, s, d: SimpleNamespace(name=n, start_ns=s, duration_ns=d)
    line = lambda n, evs: SimpleNamespace(name=n, events=evs)
    planes = [
        SimpleNamespace(name="/host:CPU", lines=[line("python", [
            ev("bench.window", 0, 1000), ev("serve.prefill", 10, 50),
            ev("something.else", 0, 5)])]),
        SimpleNamespace(name="/device:TPU:0", lines=[
            line("XLA Ops", [ev("%moe_gmm.3 = f32[32,16,512]{2,1,0} custom-call("
                                "f32[32,16,1024]{2,1,0} %fusion.1)", 20, 30)]),
            line("XLA Modules", [ev("jit_step", 20, 500)])]),
    ]
    tr = trace_reduce.from_xspace(planes)
    assert tr.device_ops == [("moe_gmm.3", 20, 50)]
    assert [s[0] for s in tr.host_spans] == ["bench.window", "serve.prefill"]
    assert tr.window == (0, 1000) and tr.busy_s == pytest.approx(30e-9)
    with pytest.raises(ValueError):
        trace_reduce.from_xspace(planes[1:])


def test_roofline_reader_never_passes_100_for_honest_times():
    """A kernel timed at exactly its bound reads 100%; slower reads less."""
    w = work.moe_gmms(16, d=1024, f=512, experts=32, top_k=8)
    pk = peaks.peaks_for("TPU v5 lite")
    t_min = w.min_seconds(pk.flops_bf16, pk.hbm_bytes_s)
    for slow in (1.0, 2.5):
        dur = t_min * slow * 1e9
        ctx = SimpleNamespace(peaks=pk, trace=SimpleNamespace(
            device_ops=[("moe_gmm", 0.0, dur)]))
        got = readers.roofline_share(ctx, "moe_gmm", [(0.0, 1e12, w)])
        assert got == pytest.approx(100.0 / slow)
    ctx = SimpleNamespace(peaks=pk, trace=SimpleNamespace(device_ops=[]))
    assert readers.roofline_share(ctx, "moe_gmm", [(0.0, 1.0, w)]) is None


# ---- kernel work and peaks -----------------------------------------------

def test_kernel_work_on_known_shapes():
    g = work.gmm(rows=8, k=4, n=2, experts=3)
    assert g.flops == 2 * 8 * 4 * 2
    assert g.bytes == 4 * (3 * 4 * 2 + 8 * (4 + 2))
    # decode of one token through granite's MoE layer is bound by weights
    pk = peaks.peaks_for("TPU v5 lite")
    w = work.moe_gmms(1, d=1024, f=512, experts=32, top_k=8)
    assert w.bound(pk.flops_bf16, pk.hbm_bytes_s) == "memory"
    assert work.model_flops(3, 10) == 60


def test_attention_work_on_known_shapes():
    """Causal attention over T tokens: each query sees (T + 1) / 2 keys
    on average; q, k, v are read and the output written once. In a
    sliding window query t sees min(t + 1, window) keys."""
    w = work.causal_attention(3, hq=4, hkv=2, hd=8)
    assert w.flops == 4.0 * 4 * 8 * 6  # pairs (t, s <= t): 1 + 2 + 3
    assert w.bytes == 4 * 3 * 8 * (2 * 4 + 2 * 2)
    assert work.causal_attention(3, 4, 2, 8, window=3) == w
    win = work.causal_attention(5, hq=4, hkv=2, hd=8, window=2)
    assert win.flops == 4.0 * 4 * 8 * (1 + 2 + 2 + 2 + 2)
    assert win.bytes == 4 * 5 * 8 * (2 * 4 + 2 * 2)


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks.peaks_for("TPU v5 lite").hbm_bytes_s == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
