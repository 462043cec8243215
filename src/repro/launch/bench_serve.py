"""Continuous-batching serving launcher.

    # fits-in-memory path: continuous batching over the jitted decode step
    PYTHONPATH=src python -m repro.launch.bench_serve --arch olmoe-mini \
        --n-requests 16 --slots 4 --scheduler fcfs

    # offloaded path: scheduler-driven prefetch between waves (Sec 3.2)
    PYTHONPATH=src python -m repro.launch.bench_serve --arch olmoe-mini \
        --offloaded --capacity 8 --scheduler expert-affinity

Synthesizes a Poisson/bursty workload over the ClusterLM prompt
distribution, serves it through the chosen scheduler, and prints the
ServerMetrics summary (throughput, latency percentiles, queue depth,
slot occupancy, and — offloaded — transfers + cache hit rate).
``main(argv)`` returns ``(results, metrics)`` for in-process callers.
"""
from __future__ import annotations

import argparse
import json
import os
import signal

import jax
import jax.numpy as jnp

from ..configs import get_config
from ..data.synthetic import ClusterLM, SyntheticConfig
from ..faults import InjectedCrash, get_fault_plan, install_fault_plan
from ..models.model import init_params
from ..models.runtime import Runtime
from ..obs import REGISTRY, enable_tracing, get_tracer, reconcile
from ..serving import (
    ContinuousBatchingServer,
    OffloadedWaveServer,
    RequestQueue,
    TrafficConfig,
    get_scheduler,
    prefill_expert_scores,
    synthesize_workload,
)
from ..training.checkpoint import load_checkpoint
from .compile_cache import use_compile_cache


def main(argv=None):
    """Serve one synthesized workload; returns ``(results, metrics)``
    (``(None, None)`` after an injected crash)."""
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmoe-mini")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--scheduler", default="fcfs",
                    choices=["fcfs", "sjf", "expert-affinity"])
    ap.add_argument("--offloaded", action="store_true",
                    help="serve through the offloaded expert cache (Sec 3.2)")
    ap.add_argument("--overlap", action="store_true",
                    help="advance the offloaded clock by the overlapped "
                         "Eq.-3 model (layer l compute hides layer l+1 "
                         "fetches); both clocks are reported either way")
    ap.add_argument("--engine-impl", default="slab", choices=["slab", "dict"],
                    help="offloaded engine implementation (slab = grouped "
                         "jitted hot path; dict = legacy per-expert loop)")
    ap.add_argument("--capacity", type=int, default=0, help="0 => E/4 (offloaded)")
    ap.add_argument("--slots", type=int, default=4,
                    help="concurrent KV slots / wave size")
    ap.add_argument("--n-requests", type=int, default=16)
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "bursty", "all_at_once"])
    ap.add_argument("--rate", type=float, default=4.0, help="requests / second")
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--min-prompt-len", type=int, default=None,
                    help="shortest prompt (default: half of --prompt-len)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kernel-backend", default="ref",
                    help="kernel dispatch spec for the whole served path: "
                         "ref | pallas | auto, optionally per op "
                         "('auto,flash_attn=ref'); Pallas compiles on a "
                         "TPU and runs interpreted elsewhere")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="install a deterministic fault plan, e.g. "
                         "'fail=0.1,spike=0.05:2e-3,storm=0.02:0.5,seed=7' "
                         "(same grammar as REPRO_FAULTS)")
    ap.add_argument("--slo", type=float, default=None,
                    help="per-request SLO in virtual seconds after arrival "
                         "(default: best effort, never shed)")
    ap.add_argument("--quality", type=float, default=1.0,
                    help="little-expert quality dial: fraction of cache "
                         "misses served by the big expert (needs --little)")
    ap.add_argument("--little", action="store_true",
                    help="build the always-resident low-rank little-expert "
                         "bank (degraded mode on fetch failure / deadline "
                         "pressure; offloaded path only)")
    ap.add_argument("--little-rank", type=int, default=8)
    ap.add_argument("--max-backlog", type=int, default=None,
                    help="bound the pending queue; the latest arrivals "
                         "beyond it are shed (admission control)")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="enable structured tracing; write trace.json "
                         "(Perfetto), trace.jsonl, metrics.json/.prom and "
                         "— offloaded — the Eq.-3 reconciliation report "
                         "into DIR")
    ap.add_argument("--journal", default=None, metavar="DIR",
                    help="write-ahead request journal + checkpoints into "
                         "DIR (default: $REPRO_JOURNAL); enables crash "
                         "recovery via --resume")
    ap.add_argument("--checkpoint-every", type=int, default=8,
                    help="checkpoint + rotate the journal every N decode "
                         "steps (continuous) / waves (offloaded); needs "
                         "--journal")
    ap.add_argument("--audit-every", type=int, default=0,
                    help="run the invariant-audit watchdog every N steps/"
                         "waves (0 = only after a restore)")
    ap.add_argument("--resume", action="store_true",
                    help="recover from the journal dir and continue the "
                         "interrupted run (token-identical under greedy)")
    ap.add_argument("--cold-restore", action="store_true",
                    help="with --resume on the offloaded path: skip the "
                         "warm slab revival (restore policy scores only "
                         "and pay the demand misses again)")
    ap.add_argument("--out-results", default=None, metavar="PATH",
                    help="write per-request tokens + summary JSON (use to "
                         "diff a crashed+resumed run against an "
                         "uninterrupted one)")
    args = ap.parse_args(argv)

    if args.trace:
        enable_tracing()
    if args.faults:
        install_fault_plan(args.faults)

    cfg = get_config(args.arch)
    if args.ckpt:
        like = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg, jnp.float32))
        params, _, meta = load_checkpoint(args.ckpt, like)
        print(f"loaded {args.ckpt} ({meta})")
    else:
        params = init_params(jax.random.key(0), cfg, jnp.float32)
        print("using randomly initialized weights (demo mode)")

    # -- crash recovery: journal + optional restore ---------------------
    from ..recovery import RequestJournal, journal_dir_from_env, recover

    jdir = args.journal or journal_dir_from_env()
    state = None
    if args.resume:
        assert jdir, "--resume needs --journal DIR (or $REPRO_JOURNAL)"
        state = recover(jdir)
        assert state is not None, f"nothing to recover in {jdir}"
        want = "wave" if args.offloaded else "continuous"
        assert state.kind == want, (
            f"journal was written by a {state.kind!r} server; rerun with "
            f"the matching path (expected {want!r})")
        print(f"resuming from {jdir}: step={state.step} now={state.now:.3f}s "
              f"pending={len(state.pending)} finished={len(state.results)}")

    if state is not None:
        requests = state.pending  # expert scores ride in the records
        queue = state.build_queue(args.max_backlog)
    else:
        lm = ClusterLM(SyntheticConfig(vocab=cfg.vocab,
                                       seq_len=args.prompt_len * 2,
                                       seed=args.seed + 3))
        tcfg = TrafficConfig(
            n_requests=args.n_requests, arrival=args.arrival, rate=args.rate,
            prompt_len=(args.min_prompt_len or max(args.prompt_len // 2, 1),
                        args.prompt_len),
            max_new_tokens=(max(args.max_new // 2, 1), args.max_new),
            temperature=args.temperature, seed=args.seed,
            slo=args.slo, quality=args.quality,
        )
        requests = synthesize_workload(lm, tcfg)
        # the burst fault compresses arrival gaps in place (overload)
        get_fault_plan().compress_arrivals(requests)
        queue = RequestQueue(requests, max_pending=args.max_backlog)

    rt = Runtime(zero_drop=True, kernel_backend=args.kernel_backend)
    if args.offloaded:
        assert cfg.has_router, "offloaded serving applies to MoE architectures"
        if args.temperature > 0:
            print("note: the offloaded engine decodes greedily; "
                  "--temperature is ignored on this path")
        capacity = args.capacity or cfg.melinoe_cache_capacity()
        if state is None:
            prefill_expert_scores(cfg, params, requests, rt=rt)  # oracle profiles
        kw = {"top_c": capacity} if args.scheduler == "expert-affinity" else {}
        srv = OffloadedWaveServer(
            cfg, params, capacity=capacity,
            scheduler=get_scheduler(args.scheduler, **kw), wave_size=args.slots,
            overlap=args.overlap, engine_impl=args.engine_impl,
            little_experts=args.little, little_rank=args.little_rank,
            seed=state.seed if state else args.seed,
            kernel_backend=args.kernel_backend,
        )
        if state is not None and state.engine is not None:
            srv.engine.metrics.load_state(state.engine["metrics"])
            rev = srv.engine.revive(state.engine["cache"],
                                    warm=not args.cold_restore)
            print(f"{'warm' if not args.cold_restore else 'cold'} revival: "
                  f"{rev['loaded']} experts, {rev['bytes']} bytes")
    else:
        srv = ContinuousBatchingServer(
            cfg, params, n_slots=args.slots,
            max_len=args.prompt_len + args.max_new + 1,
            scheduler=get_scheduler(args.scheduler), rt=rt,
            seed=state.seed if state else args.seed,
        )

    jr = RequestJournal(jdir, seen=state.seen_rids if state else None) \
        if jdir else None
    # graceful drain on SIGTERM: stop admission, finish in-flight, and
    # (journaled) anchor a final checkpoint instead of dying mid-step —
    # what a fleet supervisor or k8s preemption sends before SIGKILL
    drain_flag = {"drain": False}
    prev_term = signal.signal(
        signal.SIGTERM, lambda *_: drain_flag.__setitem__("drain", True))
    try:
        results, mt = srv.run(
            queue, state.metrics if state else None,
            journal=jr,
            checkpoint_every=args.checkpoint_every if jr else None,
            audit_every=args.audit_every or None,
            resume=state,
            should_drain=lambda: drain_flag["drain"],
        )
    except InjectedCrash as e:
        # deliberate fault-injection exit: the journal holds everything
        # needed for --resume, so this is a success for the harness
        print(f"CRASHED (injected): {e}")
        print(f"journal is recoverable at {jdir}" if jdir else
              "no journal configured; run is lost")
        return None, None
    finally:
        if jr is not None:
            jr.close()
        signal.signal(signal.SIGTERM, prev_term)
    if getattr(srv, "drained", False):
        print(f"DRAINED on SIGTERM: {len(results)} finished, "
              f"{len(queue)} pending left "
              + (f"checkpointed in {jdir}" if jdir else "(no journal — lost)"))
    for r in results[: min(4, len(results))]:
        print(f"  rid={r.rid} {len(r.tokens)} toks ({r.finish_reason}) "
              f"latency={r.latency:.4f}s tokens={r.tokens[:8].tolist()}...")
    print(json.dumps(mt.summary(), indent=2))

    if args.out_results:
        payload = {
            "results": [{"rid": r.rid, "tokens": [int(t) for t in r.tokens],
                         "finish_reason": r.finish_reason} for r in results],
            "summary": mt.summary(),
        }
        with open(args.out_results, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"results: {args.out_results}")

    if args.trace:
        _export_trace(args.trace, srv, mt, offloaded=args.offloaded)
    return results, mt


def _export_trace(outdir: str, srv, mt, *, offloaded: bool) -> None:
    """Dump the run's spans/metrics and (offloaded) the per-layer
    reconciliation of the Eq.-3 modeled clock against measured spans."""
    os.makedirs(outdir, exist_ok=True)
    tracer = get_tracer()
    trace_path = os.path.join(outdir, "trace.json")
    tracer.export_chrome_trace(trace_path, process_name="bench_serve")
    tracer.export_jsonl(os.path.join(outdir, "trace.jsonl"))

    mt.publish()
    get_fault_plan().publish()
    if offloaded:
        srv.engine.metrics.publish()
        srv.engine.cache.publish()
    with open(os.path.join(outdir, "metrics.json"), "w") as f:
        f.write(REGISTRY.to_json(indent=2))
    with open(os.path.join(outdir, "metrics.prom"), "w") as f:
        f.write(REGISTRY.to_prometheus_text())
    print(f"trace: {trace_path} ({len(tracer.spans())} spans)")

    if offloaded:
        report = reconcile(tracer.spans(), srv.engine.metrics, srv.engine.hw)
        with open(os.path.join(outdir, "reconcile.json"), "w") as f:
            json.dump(report.to_json(), f, indent=2)
        print(report.format_table())


if __name__ == "__main__":
    main()
