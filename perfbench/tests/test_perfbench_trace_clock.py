"""Program spans on the device trace's clock, and the readers that use
it: placing spans by ``t0_ns``, the three readers on made-up spans and a
made-up trace, the CPU profiler's own clock, and the served path's child
spans under the benchmark's wall-clock queue."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace

import pytest

from perfbench.lib import trace_clock, trace_reduce

BENCH = Path(__file__).resolve().parents[1]
START = 1_792_000_000_000_000_000  # the profiler session's start, ns
TOLERANCE_NS = 50_000  # a span's start against its own annotation's


def _read(name):
    from perfbench.lib.serve import load_reader

    return load_reader(BENCH / "metrics" / f"{name}.py")


def _span(name, t0_ns, dur_ns, depth=0, **args):
    """A recorded span whose perf_counter clock reads ns / 1e9."""
    return SimpleNamespace(name=name, t0=t0_ns * 1e-9, dur=dur_ns * 1e-9,
                           t0_ns=t0_ns, tid=1, depth=depth, args=args)


def test_spans_are_placed_by_t0_ns_alone():
    """A span starts in the trace at t0_ns less the session's start; only
    spans wholly inside the traced window, and carrying t0_ns, are
    placed."""
    ms = 1_000_000
    spans = [_span("serve.prefill", START + 10 * ms, 5 * ms),
             _span("serve.prefill", START - 1 * ms, 5 * ms),    # began before
             _span("serve.prefill", START + 98 * ms, 5 * ms),   # ends after
             _span("serve.decode_step", START + 20 * ms, 5 * ms),
             SimpleNamespace(name="serve.prefill", t0=0.0, dur=0.001, tid=1,
                             depth=0, args={})]                 # no t0_ns
    ctx = _ctx(spans, [], (0.0, 100 * ms))
    got = trace_clock.placed(ctx, "serve.prefill")
    assert [(sp, a, b) for sp, a, b in got] == [(spans[0], 10 * ms, 15 * ms)]
    ctx.trace.profile_start_ns = None
    assert trace_clock.placed(ctx, "serve.prefill") == []
    ctx.trace = None
    assert trace_clock.placed(ctx, "serve.prefill") == []


def test_a_trace_file_missing_places_nothing(tmp_path, monkeypatch, capsys):
    ms = 1_000_000
    monkeypatch.setattr(trace_clock, "TRACE_ROOT", tmp_path)
    ctx = _ctx([_span("serve.prefill", START + 10 * ms, 5 * ms)], [],
               (0.0, 100 * ms))
    del ctx.trace.profile_start_ns
    assert trace_clock.placed(ctx, "serve.prefill") == []
    assert ctx.trace.profile_start_ns is None
    assert "no profile_start_time" in capsys.readouterr().err


def _prefill(rid, t_ns, fwd_ns, jax_s=None, kids=True):
    """A serve.prefill span with its three children, back to back."""
    fwd_args = jax_s or {}
    out, t = [], t_ns
    if kids:
        for name, d, args in (("serve.prefill.forward", fwd_ns, fwd_args),
                              ("serve.prefill.insert", 1_000_000, {}),
                              ("serve.prefill.first_token", 4_000_000, {})):
            out.append(_span(name, t, d, depth=1, **args))
            t += d
    out.append(_span("serve.prefill", t_ns, fwd_ns + 5_000_000, rid=rid,
                     prompt_len=128))
    return out


def _ctx(spans, ops, window, start=START):
    trace = trace_reduce.Trace(device_ops=ops, host_spans=[("bench.window",) + window],
                               window=window, per_device=[ops])
    trace.profile_start_ns = start
    return SimpleNamespace(spans=spans, trace=trace, t0=START * 1e-9 - 1.0,
                           due_abs={0: 0.0, 1: 0.0, 2: 0.0}, cell={"name": "x"})


def test_new_readers_on_made_up_spans_and_trace():
    ms = 1_000_000
    spans = (_prefill(0, START + 100 * ms, 200 * ms,
                      {"jax_trace_s": 0.03, "jax_lower_s": 0.02,
                       "jax_compiles": 1, "jax_compile_s": 0.004,
                       "jax_cache_s": 0.001})
             + _prefill(1, START + 500 * ms, 100 * ms, {"jax_trace_s": 0.01})
             # straddles the traced window's end: out of the idle reader
             + _prefill(2, START + 950 * ms, 100 * ms, {"jax_trace_s": 0.02})
             # not a request of the window: read by neither
             + _prefill(7, START + 300 * ms, 100 * ms, {"jax_trace_s": 9.0})
             + [_span("serve.decode_step.dispatch", START + d * ms, d * ms // 10)
                for d in (10, 20, 40)])
    ops = [("fusion.1", 250 * ms, 305 * ms),   # 55 ms busy in prefill 0
           ("fusion.2", 590 * ms, 620 * ms),   # 15 ms of it in prefill 1
           ("fusion.3", 960 * ms, 990 * ms)]
    ctx = _ctx(spans, ops, (0.0, 1000 * ms))
    # prefill 0 lasts 205 ms, 150 idle; prefill 1 105 ms, 90 idle
    assert _read("prefill_idle_ms_p50.fits")(ctx) == pytest.approx((150 + 90) / 2)
    # compile time of prefills 0, 1, 2: 55, 10, 20 ms
    assert _read("prefill_compile_ms_p50.fits")(ctx) == pytest.approx(20)
    assert _read("decode_dispatch_ms_p50.fits")(ctx) == pytest.approx(2)


def test_new_readers_find_nothing_in_an_older_program():
    """Spans without t0_ns or children, and no dispatch span: each new
    reader returns None and raises nothing."""
    ms = 1_000_000
    spans = [SimpleNamespace(name=s.name, t0=s.t0, dur=s.dur, tid=1,
                             depth=s.depth, args=s.args)
             for s in _prefill(0, START + 100 * ms, 200 * ms, kids=False)]
    ctx = _ctx(spans, [("fusion.1", 250 * ms, 305 * ms)], (0.0, 1000 * ms))
    for name in ("prefill_idle_ms_p50.fits", "prefill_compile_ms_p50.fits",
                 "decode_dispatch_ms_p50.fits"):
        assert _read(name)(ctx) is None, name
    ctx.trace = None
    assert _read("prefill_idle_ms_p50.fits")(ctx) is None


def test_t0_ns_is_the_cpu_profilers_clock(tmp_path):
    """Spans recorded on the CPU under the profiler start within the
    tolerance of their own host-plane annotations."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.obs import disable_tracing, enable_tracing

    tr = enable_tracing(jax_annotations=True)
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            for i in range(20):
                with tr.span("serve.decode_step", i=i):
                    with tr.span("serve.decode_step.sync"):
                        sum(range(2000))
        finally:
            jax.profiler.stop_trace()
    finally:
        disable_tracing()
    f = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))[-1]
    planes = list(ProfileData.from_file(f).planes)
    host = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for pl in planes if pl.name.startswith("/host:")
            for ln in pl.lines for ev in ln.events if ev.name.startswith("serve.")]
    start = trace_clock.profile_start_ns(planes)
    assert start is not None
    # each span against its own annotation: same name, k-th occurrence
    deltas = []
    for name in ("serve.decode_step", "serve.decode_step.sync"):
        rec = sorted(s.t0_ns for s in tr.spans() if s.name == name)
        ann = sorted(a for n, a, _ in host if n == name)
        assert len(rec) == len(ann) == 20
        deltas += [abs((t - start) - a) for t, a in zip(rec, ann)]
    assert max(deltas) <= TOLERANCE_NS


def test_served_child_spans_nest_inside_their_parents():
    from perfbench.lib import clock
    from perfbench.tests.test_perfbench_clock import _olmoe_mini_server, _requests
    from repro.obs import disable_tracing, enable_tracing
    from repro.serving import RequestQueue

    cfg, srv = _olmoe_mini_server()
    srv.run(RequestQueue(_requests(cfg, [0, 0])))  # compile both lengths
    dues = [0.0, 0.02, 0.3]
    q = clock.WallQueue(_requests(cfg, dues), dict(enumerate(dues)), window_s=1.0)
    tr = enable_tracing()
    try:
        q.start()
        srv.run(q, journal=clock.Stamps(q))
    finally:
        disable_tracing()
    spans = tr.spans()
    kids = {"serve.prefill": ["serve.prefill.forward", "serve.prefill.insert",
                              "serve.prefill.first_token"],
            "serve.decode_step": ["serve.decode_step.dispatch",
                                  "serve.decode_step.sync"]}
    for parent, names in kids.items():
        ps = [s for s in spans if s.name == parent]
        assert ps, parent
        for p in ps:
            inside = sorted((s for s in spans if s.name.startswith(parent + ".")
                             and p.t0 <= s.t0 and s.t1 <= p.t1), key=lambda s: s.t0)
            assert [s.name for s in inside] == names
            assert all(s.depth == p.depth + 1 and s.tid == p.tid for s in inside)
            assert all(p.t0_ns <= s.t0_ns for s in inside)
    assert len([s for s in spans if s.name == "serve.prefill"]) == 3
