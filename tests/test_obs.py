"""Observability subsystem: span nesting/timing, zero-overhead disabled
tracer, Chrome-trace schema validity, metrics registry snapshot/diff,
serving TTFT/ITL + rolling windows, and the Eq.-3 reconciliation
invariants on a real (dict-impl) engine run."""
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.offload_engine import EngineMetrics, OffloadedMoEEngine
from repro.models.model import init_params
from repro.obs import (
    MetricsRegistry,
    NULL_TRACER,
    Tracer,
    chrome_trace,
    clock_span,
    disable_tracing,
    enable_tracing,
    get_tracer,
    reconcile,
    validate_chrome_trace,
)
from repro.obs.reconcile import OTHER
from repro.serving.metrics import ServerMetrics


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("granite-moe-1b-a400m-smoke")
    params = init_params(jax.random.key(0), cfg, jnp.float32)
    return cfg, params


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with the global tracer disabled."""
    disable_tracing()
    yield
    disable_tracing()


# ---------------------------------------------------------------------------
# trace.py
# ---------------------------------------------------------------------------


def test_span_nesting_and_timing():
    tr = Tracer()
    with tr.span("outer", layer=0):
        time.sleep(0.002)
        with tr.span("inner"):
            time.sleep(0.001)
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner", "outer"]  # close order
    inner, outer = spans
    assert inner.depth == 1 and outer.depth == 0
    # monotone + containment: inner lives within outer, durations positive
    assert outer.dur >= inner.dur > 0
    assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1 + 1e-9
    assert outer.args == {"layer": 0}


def test_instants_and_drain():
    tr = Tracer()
    tr.instant("cache.access", layer=1, misses=2)
    with tr.span("s"):
        pass
    s, i = tr.drain()
    assert len(s) == 1 and len(i) == 1
    assert i[0].args["misses"] == 2
    assert tr.spans() == [] and tr.instants() == []


def test_buffer_bound():
    tr = Tracer(max_records=10)
    for _ in range(25):
        with tr.span("x"):
            pass
    assert len(tr.spans()) <= 10
    assert tr.dropped > 0


def test_disabled_tracer_is_noop():
    assert get_tracer() is NULL_TRACER
    assert NULL_TRACER.enabled is False
    ctx = NULL_TRACER.span("anything", layer=3)
    with ctx:
        pass
    # the no-op context is shared — nothing is allocated or stored
    assert NULL_TRACER.span("other") is ctx
    assert NULL_TRACER.spans() == [] and NULL_TRACER.instants() == []


def test_enable_disable_roundtrip():
    tr = enable_tracing()
    assert get_tracer() is tr and tr.enabled
    with get_tracer().span("a"):
        pass
    assert len(tr.spans()) == 1
    disable_tracing()
    assert get_tracer() is NULL_TRACER


def test_clock_span_always_times():
    # disabled: .dur still measures, nothing recorded
    with clock_span("serve.decode_step") as cs:
        time.sleep(0.001)
    assert cs.dur > 0
    # enabled: same interval is also a span on the tracer
    tr = enable_tracing()
    with clock_span("serve.decode_step", active=2) as cs:
        time.sleep(0.001)
    assert cs.dur > 0
    spans = tr.spans()
    assert len(spans) == 1 and spans[0].name == "serve.decode_step"
    assert abs(spans[0].dur - cs.dur) < 5e-3


def test_chrome_trace_schema_valid():
    tr = Tracer()
    with tr.span("engine.decode_step", step=0):
        with tr.span("moe.compute", layer=1, experts=np.int64(4)):
            pass
    tr.instant("serve.retire", rid=np.int32(7))
    obj = tr.to_chrome_trace(process_name="test")
    assert validate_chrome_trace(obj) == []
    # round-trips through JSON (numpy args coerced)
    obj2 = json.loads(json.dumps(obj))
    assert validate_chrome_trace(obj2) == []
    evs = [e for e in obj2["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in evs} == {"engine.decode_step", "moe.compute"}
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in evs)


def test_chrome_trace_exporters(tmp_path):
    tr = Tracer()
    with tr.span("a"):
        pass
    p = tmp_path / "trace.json"
    tr.export_chrome_trace(str(p), process_name="t")
    assert validate_chrome_trace(json.load(open(p))) == []
    pj = tmp_path / "trace.jsonl"
    tr.export_jsonl(str(pj))
    lines = [json.loads(l) for l in open(pj)]
    assert lines and lines[0]["kind"] == "span" and lines[0]["name"] == "a"


def test_validate_rejects_bad_traces():
    assert validate_chrome_trace({"traceEvents": []}) != []  # no real events
    assert validate_chrome_trace({"nope": 1}) != []
    bad = {"traceEvents": [{"name": "x", "ph": "X", "ts": -1, "pid": 0,
                            "tid": 0, "dur": 1}]}
    assert any("ts" in e for e in validate_chrome_trace(bad))


def test_tracer_thread_safety():
    tr = Tracer()

    def work(n):
        for i in range(50):
            with tr.span("t", n=n, i=i):
                pass

    threads = [threading.Thread(target=work, args=(n,)) for n in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tr.spans()
    assert len(spans) == 200
    assert all(s.depth == 0 for s in spans)  # stacks are per-thread


def test_t0_ns_is_present_and_ordered_like_t0(tmp_path):
    tr = Tracer()
    with tr.span("outer"):
        for i in range(3):
            time.sleep(0.002)
            with tr.span("inner", i=i):
                pass
    tr.instant("mark")
    spans = sorted(tr.spans(), key=lambda s: s.t0)
    assert all(s.t0_ns > 0 for s in spans)
    assert [s.t0_ns for s in spans] == sorted(s.t0_ns for s in spans)
    # both clocks see the same stretch between the first and last start
    wall = (spans[-1].t0_ns - spans[0].t0_ns) * 1e-9
    assert wall == pytest.approx(spans[-1].t0 - spans[0].t0, abs=2e-3)
    (mark,) = tr.instants()
    assert mark.t0_ns >= spans[-1].t0_ns
    tr.export_jsonl(tmp_path / "t.jsonl")
    rows = [json.loads(l) for l in (tmp_path / "t.jsonl").read_text().splitlines()]
    assert sorted(r["t0_ns"] for r in rows) == sorted(
        [s.t0_ns for s in spans] + [mark.t0_ns])


def _fresh_jit():
    """A function JAX has never traced, so calling it compiles."""
    k = time.perf_counter_ns() % 1000 + 2
    return jax.jit(lambda x: x * k + 1), jnp.ones((k,), jnp.float32)


def _compiles(span):
    from repro.obs import REGISTRY

    return REGISTRY.counter("jax_compiles_total", span=span).value


def test_jit_compile_is_charged_to_the_innermost_span():
    tr = enable_tracing()
    before = _compiles("inner")
    with tr.span("outer"):
        with tr.span("inner"):
            f, x = _fresh_jit()
            f(x).block_until_ready()
    inner, outer = tr.spans()
    for key in ("jax_trace_s", "jax_lower_s", "jax_compile_s"):
        assert inner.args[key] > 0, key
    assert inner.args["jax_compiles"] >= 1
    assert not any(k.startswith("jax_") for k in outer.args)
    assert _compiles("inner") - before == inner.args["jax_compiles"]


def test_cache_read_is_split_from_the_backend_compile():
    """A persistent-cache read fires inside the backend compile event:
    it is charged as jax_cache_s alone, and the compile keeps the rest."""
    tr = enable_tracing()
    none0 = _compiles("none")
    with tr.span("s"):
        tr.charge_jax("jax_cache_s", 0.2)
        tr.charge_jax("jax_compile_s", 0.5)
    (s,) = tr.spans()
    assert s.args["jax_cache_s"] == pytest.approx(0.2)
    assert s.args["jax_compile_s"] == pytest.approx(0.3)
    assert s.args["jax_compiles"] == 1
    tr.charge_jax("jax_compile_s", 0.1)  # no span open
    assert _compiles("none") - none0 == 1


def test_nothing_is_charged_or_stored_with_tracing_off():
    from repro.obs import REGISTRY

    enable_tracing()  # registers the listener
    disable_tracing()
    snap = {k: v for k, v in REGISTRY.snapshot().items()
            if k.startswith("jax_compiles_total")}
    with get_tracer().span("off"):
        f, x = _fresh_jit()
        f(x).block_until_ready()
    after = {k: v for k, v in REGISTRY.snapshot().items()
             if k.startswith("jax_compiles_total")}
    assert after == snap
    assert get_tracer().spans() == [] and get_tracer().instants() == []


# ---------------------------------------------------------------------------
# registry.py
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("tx_total", "transfers", layer=0)
    c.inc()
    c.inc(2)
    assert reg.counter("tx_total", layer=0) is c  # get-or-create
    reg.gauge("depth", policy="fcfs").set(3.5)
    h = reg.histogram("lat_s", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    snap = reg.snapshot()
    assert snap['tx_total{layer="0"}'] == 3.0
    assert snap['depth{policy="fcfs"}'] == 3.5
    assert snap['lat_s_bucket{le="0.1"}'] == 1.0
    assert snap['lat_s_bucket{le="1.0"}'] == 2.0  # cumulative
    assert snap['lat_s_bucket{le="+Inf"}'] == 3.0
    assert snap["lat_s_count"] == 3.0
    assert snap["lat_s_sum"] == pytest.approx(5.55)


def test_registry_snapshot_diff():
    reg = MetricsRegistry()
    c = reg.counter("n")
    c.inc(5)
    before = reg.snapshot()
    c.inc(3)
    reg.gauge("g").set(2.0)
    d = MetricsRegistry.diff(reg.snapshot(), before)
    assert d["n"] == 3.0 and d["g"] == 2.0


def test_registry_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("tx_total", "number of transfers", op="moe_gmm").inc(4)
    reg.histogram("lat_s", buckets=(1.0,)).observe(0.5)
    text = reg.to_prometheus_text()
    assert "# HELP tx_total number of transfers" in text
    assert "# TYPE tx_total counter" in text
    assert 'tx_total{op="moe_gmm"} 4' in text
    assert "# TYPE lat_s histogram" in text
    assert 'lat_s_bucket{le="+Inf"} 1' in text
    json.loads(reg.to_json())  # parses


def test_registry_type_conflict():
    reg = MetricsRegistry()
    reg.counter("m")
    with pytest.raises(TypeError):
        reg.gauge("m")


def test_kernel_dispatch_counts():
    from repro.kernels.moe_gmm import gmm
    from repro.obs.registry import REGISTRY

    a = jnp.ones((2, 8, 128), jnp.float32)
    b = jnp.ones((2, 128, 128), jnp.float32)
    before = REGISTRY.snapshot()
    gmm(a, b, backend="auto")
    gmm(a, b, backend="ref")
    d = MetricsRegistry.diff(REGISTRY.snapshot(), before)
    inc = {k: v for k, v in d.items()
           if k.startswith("kernel_dispatch_total") and v}
    assert sum(inc.values()) == 2
    assert any('backend="ref"' in k for k in inc)
    assert any('backend="pallas"' in k for k in inc)


# ---------------------------------------------------------------------------
# ServerMetrics: TTFT / ITL + rolling windows
# ---------------------------------------------------------------------------


def test_server_metrics_ttft_itl_and_windows():
    mt = ServerMetrics(policy="fcfs", window=8)
    for i in range(20):
        mt.observe_finish(1.0 + i, ttft=0.1 * (i + 1), itl=0.01)
        mt.observe_queue_depth(i)
    s = mt.summary()
    assert s["requests"] == 20  # cumulative, not window-truncated
    assert len(mt.latencies) == 8 == len(mt.ttfts)
    # exact mean over all 20 observations despite the window of 8
    assert s["mean_queue_depth"] == pytest.approx(np.mean(np.arange(20)))
    assert s["ttft_p50"] == pytest.approx(
        np.percentile(np.asarray(mt.ttfts), 50))
    assert s["ttft_p95"] >= s["ttft_p50"] > 0
    assert s["itl_p50"] == pytest.approx(0.01)
    for k in ("ttft_p50", "ttft_p95", "itl_p50", "itl_p95"):
        assert k in s


def test_server_metrics_publish():
    reg = MetricsRegistry()
    mt = ServerMetrics(policy="sjf")
    mt.observe_finish(0.5, ttft=0.1, itl=0.02)
    mt.publish(reg)
    snap = reg.snapshot()
    assert snap['serve_requests{policy="sjf"}'] == 1.0
    assert snap['serve_ttft_p50{policy="sjf"}'] == pytest.approx(0.1)


def test_engine_metrics_per_layer_and_spans():
    m = EngineMetrics()
    m.begin_step(2)
    m.add_flops(1e9)
    m.add_demand_transfers(0, 2, 2048)
    m.add_prefetch_transfers(1, 3, 3072)
    assert m.layer_tx == {0: 2} and m.layer_tx_bytes == {0: 2048}
    assert m.layer_prefetch_tx == {1: 3}
    from repro.core.offload_engine import HardwareProfile

    hw = HardwareProfile()
    assert m.serial_span(hw) > 0
    assert m.overlapped_span(hw, 0, 1) <= m.serial_span(hw, 0, 1) + 1e-12
    # per-layer dicts survive the per-step array drop
    m.drop_step_records(hw)
    assert m.layer_tx == {0: 2}
    reg = MetricsRegistry()
    m.publish(reg, impl="slab")
    assert reg.snapshot()['engine_transfers{impl="slab"}'] == 2.0


# ---------------------------------------------------------------------------
# reconciliation on a real engine run (dict impl, smoke config)
# ---------------------------------------------------------------------------


def test_reconcile_dict_engine(setup):
    cfg, params = setup
    eng = OffloadedMoEEngine(
        cfg, params, capacity=max(cfg.moe_spec.num_experts // 2, 1),
        impl="dict")
    toks = jax.random.randint(jax.random.key(1), (1, 8), 0, cfg.vocab)
    baseline = np.asarray(eng.generate(toks, max_new_tokens=4)["tokens"])

    eng.metrics = EngineMetrics()
    tracer = enable_tracing()
    try:
        res = eng.generate(toks, max_new_tokens=4)
    finally:
        disable_tracing()
    # tracing must not perturb the decode
    assert (np.asarray(res["tokens"]) == baseline).all()

    spans = tracer.spans()
    names = {s.name for s in spans}
    assert {"engine.prefill", "engine.decode_step", "moe.pre",
            "moe.compute"} <= names
    # per-layer attribution exists
    assert any(s.args.get("layer") == 0 for s in spans
               if s.name == "moe.compute")

    report = reconcile(spans, eng.metrics, eng.hw, tolerance=0.5)
    # the invariants the tracing subsystem exists to check:
    assert report.measured_overlap_s >= 0.0
    assert report.modeled_overlapped_s <= report.modeled_serial_s + 1e-12
    # Eq. 3 at measured rates explains the measured step wall
    assert report.ok, report.format_table()
    assert report.serial_agreement_ratio == pytest.approx(1.0, abs=0.5)
    assert report.measured_serial_s > 0
    assert report.unmodeled_s >= 0.0
    moe_rows = [r for r in report.layers if r.layer != OTHER]
    assert len(moe_rows) == len(eng.moe_layer_ids)
    assert all(r.measured_compute_s > 0 for r in moe_rows)
    json.dumps(report.to_json())  # serializable
    assert "Eq.3" in report.format_table()

    # cache instants were aggregated per access with layer attribution
    inst = [i for i in tracer.instants() if i.name == "cache.access"]
    assert inst and all("layer" in i.args for i in inst)


def test_tracing_disabled_leaves_no_buffer(setup):
    cfg, params = setup
    eng = OffloadedMoEEngine(
        cfg, params, capacity=max(cfg.moe_spec.num_experts // 2, 1),
        impl="dict")
    toks = jax.random.randint(jax.random.key(2), (1, 4), 0, cfg.vocab)
    eng.generate(toks, max_new_tokens=2)
    assert get_tracer().spans() == []
    assert get_tracer().instants() == []
