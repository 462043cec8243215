"""One run of one cell: set-up, the measured window on the served path,
the reading of metrics, and the comparison with the reference.

The served path is the program's, built as ``repro.launch.bench_serve``
builds it: ``ContinuousBatchingServer`` for a ``continuous`` mix and
``OffloadedWaveServer`` over the offloaded engine for a ``wave`` mix,
with the kernel backend ``auto`` (compiled Pallas on the chip). The
benchmark drives the server's own ``run()`` through its wall-clock
queue and journal (``clock.py``); every end-to-end time is read on the
host's ``perf_counter``.
"""
from __future__ import annotations

import gc
import importlib.util
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from . import clock, reference, traffic as traffic_mod
from .model import jax_seed

DRAIN_LIMIT_S = 150.0  # after the window: stop admitting, finish in flight
PROFILE_AT = 0.3  # traced run: the profiler starts this far into the window
PROFILE_S = 4.0  # ... and captures this long (at most 40% of the window)
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_misses",
                  "/jax/compilation_cache/cache_hits")


@dataclass
class Context:
    """What a metric reader may read."""

    cell: dict
    cj: dict
    arch: Any  # the configuration's architecture module (perfbench/archs)
    traffic: dict
    kind: str  # "continuous" | "wave"
    seconds: float
    setup_s: float
    t0: float  # window start, perf_counter seconds
    due_abs: Dict[int, float]
    stamps: clock.Stamps
    attempted: List[int]
    spans: list = field(default_factory=list)  # program spans (traced run)
    trace: Any = None  # trace_reduce.Trace (traced run)
    peaks: Any = None
    counters: Dict[str, float] = field(default_factory=dict)


class CompileCounter:
    """Counts compile requests and persistent-cache misses while on."""

    def __init__(self):
        self.on = False
        self.counts = {e: 0 for e in COMPILE_EVENTS}
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._ev)

    def _dur(self, event, duration=None, **_):
        self._ev(event)

    def _ev(self, event, **_):
        if self.on and event in self.counts:
            self.counts[event] += 1


def load_reader(path: Path) -> Callable:
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _serve_requests(reqs):
    from repro.serving.request import ServeRequest

    return [ServeRequest(rid=r.rid, prompt=r.prompt, max_new_tokens=r.max_new,
                         temperature=0.0, arrival_time=0.0) for r in reqs]


def _warm_requests(t: dict, vocab: int, seed: int):
    """Requests that warm every shape the window will use. Continuous
    serving: one per prompt length, two tokens each (the slot pool fixes
    every other shape). Wave serving sizes its KV cache per request, so
    each distinct (prompt, output) size of the cycle is served in full."""
    if t["arrival"] == "closed":
        sizes = sorted({tuple(c) for c in t["cycle"]})
    else:
        sizes = [(L, 2) for L in sorted(set(t["prompt_len"].get("values", [])))]
    rng = np.random.default_rng([seed, 0xA4])
    return [traffic_mod.BenchRequest(rid=10**6 + i,
                                     prompt=rng.integers(0, vocab, L).astype(np.int32),
                                     max_new=m, due=0.0)
            for i, (L, m) in enumerate(sizes)]


def build_server(arch, cj, t, params, seed, reqs):
    """The served path, as ``bench_serve`` builds it (kernels ``auto``):
    the mix's ``server`` names the server, its scheduler and its sizes."""
    from repro.models.runtime import Runtime
    from repro.serving import (ContinuousBatchingServer, OffloadedWaveServer,
                               get_scheduler, prefill_expert_scores)

    cfg = arch.program_config(cj)
    s = t["server"]
    rt = Runtime(zero_drop=True, kernel_backend="auto")
    name = s.get("scheduler", "fcfs")
    if s["kind"] == "continuous":
        return ContinuousBatchingServer(
            cfg, params, n_slots=s["slots"], max_len=traffic_mod.kv_len(t),
            scheduler=get_scheduler(name), rt=rt, seed=jax_seed(seed))
    if s["kind"] == "wave":
        prefill_expert_scores(cfg, params, reqs, rt=rt)  # oracle profiles
        held, top_k = arch.experts(cj)
        capacity = max(top_k, int(held * s["capacity_frac"]))
        kw = {"top_c": capacity} if name == "expert-affinity" else {}
        return OffloadedWaveServer(
            cfg, params, capacity=capacity, scheduler=get_scheduler(name, **kw),
            wave_size=s.get("wave_size", 1), use_prefetch=s.get("prefetch", True),
            seed=jax_seed(seed), kernel_backend="auto")
    raise ValueError(f"unknown server kind {s['kind']!r}")


class _Profiler(threading.Thread):
    """Captures a device trace of part of the window, from a thread, so
    the serving loop runs untouched. The capture is bracketed by a
    ``bench.window`` annotation that fixes the traced window."""

    def __init__(self, out_dir: Path, start_in: float, length: float):
        super().__init__(daemon=True)
        self.out_dir, self.start_in, self.length = out_dir, start_in, length
        self.done = threading.Event()
        self.error: Optional[BaseException] = None
        self.perf_at_window: Optional[float] = None

    def run(self):
        import jax

        try:
            time.sleep(self.start_in)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    self.perf_at_window = time.perf_counter()
                    time.sleep(self.length)
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:  # reported by the caller
            self.error = e
        finally:
            self.done.set()


def run_cell(cell: dict, cj: dict, arch, t: dict, metric_entries: List[dict],
             metrics_dir: Path, work_dir: Path, seed: int, seconds: float,
             trace: bool, t_start: float, fault: Optional[Callable] = None,
             controls: Sequence[str] = ()) -> dict:
    """Run ``cell`` (configuration ``cj`` of architecture module ``arch``,
    traffic ``t``) once and return what the result line needs.
    ``work_dir`` takes the device trace. ``fault``, for tests, is called
    with the warmed server and may break the timed path; ``controls``
    (for calibration) are judged beside the program."""
    import jax

    kind = t["server"]["kind"]
    dev = jax.devices()[0]

    # ---- set-up: weights, requests, server, warm-up -----------------
    params = arch.make_weights(cj, seed)
    jax.block_until_ready(params)
    bytes_on_device = sum(x.nbytes for x in jax.tree.leaves(params))
    reqs = traffic_mod.build_requests(t, cj["vocab_size"], seed, seconds)
    sreqs = _serve_requests(reqs)
    warm = _serve_requests(_warm_requests(t, cj["vocab_size"], seed))
    srv = build_server(arch, cj, t, params, seed, sreqs + warm)
    del params  # the server holds what it serves from; freed with it
    from repro.serving import RequestQueue

    srv.run(RequestQueue(warm))
    if fault is not None:
        fault(srv)
    engine0 = _engine_counters(srv) if kind == "wave" else {}
    print(f"bench: config {cj['name']}: declared parameter bytes "
          f"{cj['param_bytes']}, from the sizes {4 * arch.param_count(cj)['total']}, "
          f"made on the device {bytes_on_device}", file=sys.stderr)

    # ---- the measured window ----------------------------------------
    if trace:
        from repro.obs import enable_tracing
        tracer = enable_tracing(jax_annotations=True)
        trace_dir = work_dir / "trace" / cell["name"]
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        prof = _Profiler(trace_dir, PROFILE_AT * seconds,
                         min(PROFILE_S, 0.4 * seconds))
    counter = CompileCounter()
    due = {r.rid: r.due for r in reqs}
    q = clock.WallQueue(sreqs, due, seconds, closed=t["arrival"] == "closed")
    st = clock.Stamps(q)
    setup_s = time.perf_counter() - t_start
    counter.on = True
    print("bench: window opens", file=sys.stderr)
    t0 = q.start()
    if trace:
        prof.start()
    results, _ = srv.run(q, journal=st,
                         should_drain=lambda: q.now() > seconds + DRAIN_LIMIT_S)
    counter.on = False
    print("bench: window closed", file=sys.stderr)
    if trace:
        prof.done.wait()
        if prof.error is not None:
            raise prof.error
    mem_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"bench: peak_bytes_in_use {mem_peak}; parameter bytes made on the "
          f"device {bytes_on_device} (declared {cj['param_bytes']})", file=sys.stderr)

    attempted = [rid for rid in q.released] + [r.rid for r in q.pending()]
    attempted = sorted(rid for rid in set(attempted) if q.due[rid] is not None)
    by_rid = {r.rid: r for r in results}
    ok = {rid for rid in attempted if rid in by_rid
          and by_rid[rid].finish_reason in ("length", "stop")}
    failed = len(attempted) - len(ok)
    engine1 = _engine_counters(srv) if kind == "wave" else {}
    counters = {k: engine1[k] - engine0[k] for k in engine1}
    counters.update({"compiles_in_window": counter.counts[COMPILE_EVENTS[0]],
                     "cache_misses_in_window": counter.counts[COMPILE_EVENTS[1]],
                     "cache_hits_in_window": counter.counts[COMPILE_EVENTS[2]],
                     "generator_lateness_s": q.lateness_s,
                     "memory_peak_bytes": mem_peak,
                     "kernel_sites": _kernel_sites(),
                     "latency_ms": _latency_ms(st, q, attempted)})

    ctx = Context(cell=cell, cj=cj, arch=arch, traffic=t, kind=kind,
                  seconds=seconds, setup_s=setup_s, t0=t0,
                  due_abs={rid: q.due_at(rid) for rid in attempted},
                  stamps=st, attempted=attempted, counters=counters)
    if trace:
        from repro.obs import disable_tracing

        from . import peaks as peaks_mod, trace_reduce
        ctx.spans = tracer.spans()
        disable_tracing()
        ctx.peaks = peaks_mod.peaks_for(dev.device_kind)
        ctx.trace = trace_reduce.load(trace_dir)
        ctx.trace.perf_at_window = prof.perf_at_window

    metrics = {}
    for m in metric_entries:
        v = load_reader(metrics_dir / f"{m['name']}.py")(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # ---- correctness: the reference, once the program's state is freed
    prompts = {r.rid: r.prompt for r in reqs}
    finished = {rid: (prompts[rid], np.asarray(by_rid[rid].tokens, np.int32))
                for rid in ok}
    del srv, results, by_rid
    gc.collect()
    check = compare(arch, cj, t, seed, finished, controls)
    out = {
        "correct": check["ok"] and failed == 0 and len(attempted) > 0,
        "attempted": len(attempted),
        "failed": failed,
        "metrics": metrics,
        "memory_peak_bytes": mem_peak,
        "counters": counters,
        "checks": check["checks"],
        "compared": {k: check[k] for k in ("tokens", "rids", "gaps", "controls")},
    }
    if trace:
        out["busy_s"] = ctx.trace.busy_s
        out["window_s"] = ctx.trace.window_s
        out["breakdown"] = ctx.trace.breakdown()
    return out


def _latency_ms(st: clock.Stamps, q: clock.WallQueue, attempted: List[int]
                ) -> Dict[str, Optional[float]]:
    """Quantiles of the window's times to first token and gaps between
    tokens, beside the metrics (for reading how a tail is made)."""
    from . import readers, stats

    times = {rid: st.token_times.get(rid, []) for rid in attempted}
    ttft = stats.ttfts_s(times, {rid: q.due_at(rid) for rid in attempted})
    gaps = stats.token_gaps_s(times)
    out = {f"ttft_p{p}": readers.ms_percentile(ttft, p) for p in (50, 90, 95)}
    out.update({f"itl_p{p}": readers.ms_percentile(gaps, p)
                for p in (50, 90, 95, 99)})
    return out


def _engine_counters(srv) -> Dict[str, float]:
    m, cs = srv.engine.metrics, srv.engine.cache.stats()
    return {"transfer_bytes": m.transfer_bytes, "prefetch_bytes": m.prefetch_bytes,
            "transfers": m.transfers, "prefetch_transfers": m.prefetch_transfers,
            "cache_hits": cs.hits, "cache_misses": cs.misses}


def _kernel_sites() -> Dict[str, int]:
    """Traced call sites per kernel that ran as compiled Pallas."""
    from repro.obs.registry import REGISTRY

    return {op: REGISTRY.counter("kernel_dispatch_total", op=op, backend="pallas",
                                 interpret=False).value
            for op in ("flash_attn", "moe_gmm")}


def judge(gaps: np.ndarray, c: dict) -> dict:
    """The number compared, beside its limit, for one set of per-token
    gaps (the program's, or a control's in its place): the mean gap over
    the sample. ``ok`` only where there was something to compare."""
    mean = float(gaps.mean()) if gaps.size else None
    limit = float(c["mean_logit_gap"])
    return {"ok": mean is not None and mean <= limit,
            "checks": {"mean_logit_gap": {"value": mean, "limit": limit}}}


def compare(arch, cj: dict, t: dict, seed: int, finished: Dict[int, tuple],
            controls: Sequence[str] = ()) -> dict:
    """Compare a seeded sample of the finished requests with the
    architecture's plain reference and judge the program by it. Each
    control named in ``controls`` (a lower precision of the reference,
    put in the program's place at the same positions) is judged by the
    same rule."""
    c = t["correct"]
    rids = reference.pick_sample(finished, seed, c["sample_tokens"],
                                 c["max_requests"])
    params = arch.make_weights(cj, seed)
    ref = reference.Reference(cj, arch.forward)
    parts: Dict[str, list] = {"program": [], **{q: [] for q in controls}}
    for rid in rids:
        prompt, served = finished[rid]
        for k, v in reference.served_gaps(ref, params, prompt, served,
                                          controls).items():
            parts[k].append(v)
    del params
    gaps = {k: np.concatenate(v) if v else np.zeros(0) for k, v in parts.items()}
    verdict = judge(gaps["program"], c)
    return {"ok": bool(rids) and verdict["ok"], "checks": verdict["checks"],
            "tokens": int(gaps["program"].size), "rids": rids,
            "gaps": {k: {"max": float(g.max()), "mean": float(g.mean()),
                         "flipped": float((g > 0).mean())}
                     for k, g in gaps.items() if g.size},
            "controls": {q: judge(gaps[q], c) for q in controls}}
