"""Continuous-batching servers over both inference engines.

``ContinuousBatchingServer`` (fits-in-memory path) runs the jitted
single-step decode over a fixed pool of KV slots. Sequences live at
independent positions (the per-row ``pos`` vector threaded through
``decode_attend``); finished sequences retire on a stop token or their
token budget and the freed slot is re-prefilled with the next scheduled
request — no one is padded to the longest prompt or decoded past their
own budget.

``OffloadedWaveServer`` (memory-constrained path, Sec 3.2) drives the
``OffloadedMoEEngine``: the scheduler picks the next wave of requests,
the union of their predicted expert sets is prefetched (Eq. 7), and the
wave is decoded over the shared resident cache. Its clock advances by
the Eq. 3 cost model (demand misses AND prefetch DMA), so
latency/throughput reflect transfer traffic.

Clock semantics (continuous server): the virtual clock counts measured
host time for prefill + decode; the decode step is pre-compiled in the
constructor so no XLA compile lands on a request's latency. Prefill is
one jitted program per prompt shape, kept in JAX's cache: the first
prefill of a new prompt LENGTH traces and compiles it inside the clock,
every later one dispatches the cached executable
(``serve_prefill_programs_total{outcome="built"|"reused"}`` counts
which). Warm every prompt length before timing, and bucket lengths
upstream if tail latencies at many distinct lengths matter.
"""
from __future__ import annotations

import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import MLASpec, ModelConfig
from ..core.offload_engine import HardwareProfile, OffloadedMoEEngine
from ..faults import FetchPolicy, get_fault_plan
from ..inference.engine import Request, ServingEngine, truncate_at_stop
from ..inference.sampling import greedy, sample_per_row
from ..models.model import decode_step, prefill
from ..models.runtime import Runtime
from ..obs.registry import REGISTRY
from ..obs.trace import clock_span, get_tracer
from .batch import BatchState
from .metrics import ServerMetrics
from .queue import RequestQueue
from .request import ServeRequest, ServeResult
from .scheduler import FCFSScheduler, Scheduler


def _reject_unservable(queue: RequestQueue, now: float, mt: ServerMetrics,
                       results: List[ServeResult], tr, jr=None) -> None:
    """Admission control: turn bound-overflow and expired-while-queued
    requests into "shed" results — they never reach a slot or wave.
    ``drop_expired`` routes its victims through the queue's shed pool,
    so one drain covers both kinds; identity tells them apart. ``jr``
    (a recovery ``RequestJournal``) makes each shed durable."""
    expired = {id(r) for r in queue.drop_expired(now)}
    queue.enforce_bound(now)
    for r in queue.drain_shed():
        if id(r) in expired:
            mt.requests_expired += 1
        else:
            mt.requests_shed += 1
        if tr.enabled:
            tr.instant("serve.shed", rid=r.rid, expired=id(r) in expired,
                       wait_s=now - r.arrival_time)
        if jr is not None:
            jr.shed(r, expired=id(r) in expired, now=now)
        results.append(ServeResult(
            rid=r.rid, tokens=np.zeros(0, np.int32), finish_reason="shed",
            arrival_time=r.arrival_time, start_time=now, finish_time=now,
        ))


class ContinuousBatchingServer:
    """In-flight batching over the jitted fused decode step."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        n_slots: int = 4,
        max_len: int = 128,
        scheduler: Optional[Scheduler] = None,
        rt: Optional[Runtime] = None,
        lora=None,
        lora_scale: float = 1.0,
        window_override: Optional[int] = None,
        seed: int = 0,
    ):
        self.cfg = cfg
        self.params = params
        self.rt = rt or Runtime(zero_drop=True)
        self.scheduler = scheduler or FCFSScheduler()
        self.n_slots = n_slots
        self.max_len = max_len
        self.seed = seed  # recorded in recovery checkpoints
        self._key0 = jax.random.key(seed)
        # latent-attention layers read each row's cache up to its position
        self._latent = any(isinstance(b.attn, MLASpec)
                           for b in cfg.block_defs.values())

        def _decode(params, tokens, cache):
            return decode_step(
                params, cfg, tokens, cache, self.rt,
                window_override=window_override, lora=lora, lora_scale=lora_scale,
            )

        self._decode_jit = jax.jit(_decode)

        def _prefill(params, tokens):
            # one executable per tokens shape in JAX's cache; returns the
            # greedy first token (to match ServingEngine.generate_batch)
            logits, cache = prefill(
                params, cfg, tokens, self.rt, n_slots=max_len,
                window_override=window_override, lora=lora, lora_scale=lora_scale,
            )
            return greedy(logits), cache

        self._prefill_jit = jax.jit(_prefill)
        # prompt lengths whose prefill program this server has built
        self._prefill_lengths = {1}

        def _sample(logits, rids, steps, temps):
            # request-keyed per-row sampling: randomness follows the
            # (rid, step) pair, not the slot, so batch composition never
            # perturbs a sequence; keys derive inside the jit to keep the
            # per-step host work to three small array transfers
            keys = jax.vmap(
                lambda r, s: jax.random.fold_in(jax.random.fold_in(self._key0, r), s)
            )(rids, steps)
            return sample_per_row(logits, None, temps, keys=keys)

        self._sample_jit = jax.jit(_sample)
        self._insert_jit = jax.jit(self._insert_row)
        self.cache = self._fresh_cache()
        # warm every compilation now so the serving clock (latency
        # percentiles, queue-depth trace) never charges XLA compile time
        # to the first requests
        dummy = jnp.zeros((n_slots, 1), jnp.int32)
        self._decode_jit(self.params, dummy, self.cache)
        self._sample_jit(
            jnp.zeros((n_slots, 1, cfg.vocab), jnp.float32),
            jnp.zeros((n_slots,), jnp.int32),
            jnp.zeros((n_slots,), jnp.int32),
            jnp.ones((n_slots,), jnp.float32),
        )
        _, pre = self._prefill_jit(self.params, dummy[:1])
        self._insert_jit(self.cache, pre, 0)

    # ------------------------------------------------------------------
    def _fresh_cache(self):
        """Slot-pool cache: a dummy 1-token prefill fixes the tree
        structure (ring sizes etc.) to exactly what per-request prefills
        produce; rows are garbage until a request is inserted."""
        dummy = jnp.zeros((self.n_slots, 1), jnp.int32)
        _, cache = self._prefill_jit(self.params, dummy)
        cache["pos"] = jnp.zeros((self.n_slots,), jnp.int32)  # per-row positions
        return cache

    @staticmethod
    def _insert_row(cache, pre_cache, slot):
        """Splice a freshly prefilled request (batch of 1) into slot
        ``slot`` of the pooled cache. Group leaves are stacked
        (R, B, ...), so one tree_map covers KV, ring positions and SSM
        state alike."""
        out = {"pos": cache["pos"].at[slot].set(pre_cache["pos"])}
        for g, sub in cache.items():
            if g == "pos":
                continue
            out[g] = jax.tree.map(
                lambda big, small: big.at[:, slot].set(small[:, 0]), sub, pre_cache[g]
            )
        return out

    # ------------------------------------------------------------------
    def _admit(self, state: BatchState, slot: int, req: ServeRequest,
               cur: np.ndarray, now: float, mt: ServerMetrics) -> Optional[str]:
        """Prefill one request into a free slot; start_time is the
        admission moment (queueing ends, service begins). Returns the
        finish reason if the request completed immediately (budget of
        1 / instant stop) — the caller retires it with a clock that
        includes this prefill's cost.

        A request resumed from a crash re-prefills ``prompt + resumed``
        (its journaled watermark); greedy decode depends only on the
        token prefix, so the continuation is token-identical to the
        uninterrupted run."""
        tr = get_tracer()
        inp = (req.prompt if req.resumed is None else
               np.concatenate([req.prompt, req.resumed]).astype(np.int32))
        built = len(inp) not in self._prefill_lengths
        self._prefill_lengths.add(len(inp))
        REGISTRY.counter(
            "serve_prefill_programs_total",
            "continuous-server prefills, by whether their prompt length's "
            "program was built or reused",
            outcome="built" if built else "reused").inc()
        # dispatch of this prompt length's cached prefill program; the
        # first prefill of a length traces, lowers and compiles it here
        with tr.span("serve.prefill.forward"):
            first, pre_cache = self._prefill_jit(
                self.params, jnp.asarray(inp[None], jnp.int32))
        with tr.span("serve.prefill.insert"):
            self.cache = self._insert_jit(self.cache, pre_cache, slot)
        state.occupy(slot, req, now)
        mt.prefill_tokens += len(inp)
        # the host waits here for the device's prefill, then reads back
        # the one greedy first token
        with tr.span("serve.prefill.first_token"):
            tok = int(np.asarray(first)[0, 0])
        cur[slot, 0] = tok
        mt.generated_tokens += 1
        return state.append_token(slot, tok)

    def run(self, queue: RequestQueue,
            metrics: Optional[ServerMetrics] = None,
            *,
            journal=None,
            checkpoint_every: Optional[int] = None,
            audit_every: Optional[int] = None,
            resume=None,
            on_step=None,
            should_drain=None,
            ) -> Tuple[List[ServeResult], ServerMetrics]:
        """Serve the queue. Crash-safety knobs (all optional):

        * ``journal`` — a ``recovery.RequestJournal``; every arrival /
          admit / emitted-token watermark / retire / shed lands as a
          flushed JSONL event
        * ``checkpoint_every`` — snapshot + journal rotation every N
          decode steps (requires ``journal``)
        * ``audit_every`` — run the invariant watchdog every N steps
        * ``resume`` — a ``recovery.RecoveredState``; the clock, step
          counter and finished results continue from it (pass
          ``resume.metrics`` as ``metrics`` and a queue built via
          ``resume.build_queue()`` for full continuity)
        * ``on_step`` — liveness hook called after every decode step
          with a dict (step/now/backlog/in_flight/finished/generated);
          the fleet worker heartbeats (and injects worker faults) here
        * ``should_drain`` — polled each loop iteration; once it
          returns True admission stops, in-flight requests finish, a
          final checkpoint anchors the journal, and ``self.drained``
          is set — still-pending requests stay journaled for a resume
        """
        mt = metrics or ServerMetrics(policy=self.scheduler.name)
        tr = get_tracer()
        plan = get_fault_plan()
        jr = journal
        state = BatchState(self.n_slots, self.max_len)
        cur = np.zeros((self.n_slots, 1), np.int32)
        results: List[ServeResult] = []
        # virtual first-token time per live rid, for TTFT/ITL at retire
        first_tok: dict = {}
        now = 0.0
        step_idx = 0
        wd = None
        if resume is not None:
            now = resume.now
            step_idx = resume.step
            results = list(resume.results)
        if audit_every or resume is not None:
            from ..recovery.audit import Watchdog
            wd = Watchdog(queue=queue, metrics=mt, batch=state,
                          offered_base=resume.offered_base if resume else 0)
            if resume is not None:
                wd.check(in_flight=0)  # trust nothing restored, audited
        if jr is not None:
            for r in queue.pending():
                jr.arrival(r)
        t_wall0 = time.perf_counter()

        def _retire(s: int, reason: str) -> None:
            req = state.slots[s].request
            res = state.retire(s, now, reason)
            attained = False
            if reason == "deadline":
                mt.deadline_retired += 1
            elif req.deadline is None or now <= req.deadline:
                mt.slo_attained += 1
                attained = True
            ft = first_tok.pop(res.rid, None)
            ttft = None if ft is None else ft - res.arrival_time
            itl = (None if ft is None else
                   (now - ft) / max(len(res.tokens) - 1, 1))
            mt.observe_finish(res.latency, ttft=ttft, itl=itl)
            if tr.enabled:
                tr.instant("serve.retire", rid=res.rid, reason=reason,
                           tokens=len(res.tokens))
            if jr is not None:
                jr.retire(res, plen=req.prompt_len, attained=attained,
                          ttft=ttft, itl=itl)
            results.append(res)

        self.drained = False
        while len(queue) or state.active_slots():
            draining = should_drain is not None and should_drain()
            # -- admission control: shed what can't be served -----------
            _reject_unservable(queue, now, mt, results, tr, jr)
            # -- admission: scheduler fills freed slots -----------------
            free = state.free_slots() if not draining else []
            if free:
                ready = queue.ready(now)
                if ready:
                    order = self.scheduler.order(ready, hot=state.active_requests())
                    for slot, req in zip(free, order):
                        queue.admit(req)
                        # prefill is service time: the clock_span both
                        # advances the serving clock and (when tracing)
                        # records the same interval as a span
                        with clock_span("serve.prefill", rid=req.rid,
                                        prompt_len=req.prompt_len) as cs:
                            reason = self._admit(state, slot, req, cur, now, mt)
                        now += cs.dur
                        # the first token materializes with the prefill
                        first_tok[req.rid] = now
                        if jr is not None:
                            jr.admit(req.rid, now)
                            jr.watermark({req.rid: [int(cur[slot, 0])]}, now)
                        if reason is not None:
                            _retire(slot, reason)
                        elif req.deadline is not None and now >= req.deadline:
                            # earlier admissions' prefills ate the budget
                            _retire(slot, "deadline")
            active = state.active_slots()
            if not active:
                if draining:
                    break  # nothing in flight: pending stays journaled
                # idle: jump the virtual clock to the next arrival
                nxt = queue.next_arrival()
                if nxt is not None:
                    now = max(now, nxt)
                continue

            # injected crash: raises InjectedCrash between steps — the
            # journal is flushed through the last completed step, so
            # recovery resumes exactly here
            if plan.enabled:
                plan.maybe_crash("serve.decode")

            # -- one fused decode step over the whole slot pool ---------
            # cached_positions: what one latent-attention layer reads over
            # the active rows, each its position (prompt + generated - 1)
            # plus one; 0 where no layer reads a latent cache
            cached = (sum(state.slots[s].request.prompt_len
                          + len(state.slots[s].generated) for s in active)
                      if self._latent else 0)
            with clock_span("serve.decode_step", active=len(active),
                            slots=self.n_slots, cached_positions=cached) as cs:
                with tr.span("serve.decode_step.dispatch"):
                    logits, self.cache, _ = self._decode_jit(
                        self.params, jnp.asarray(cur), self.cache
                    )
                    temps = np.zeros(self.n_slots, np.float32)
                    # filler (rid, step) for free/greedy rows: any
                    # non-negative value works, the draw is discarded by
                    # the temperature mask
                    rids = (np.arange(self.n_slots, dtype=np.int32)
                            + (2**31 - 1 - self.n_slots))
                    steps = np.zeros(self.n_slots, np.int32)
                    for s in active:
                        slot = state.slots[s]
                        temps[s] = slot.request.temperature
                        rids[s] = slot.request.rid
                        steps[s] = len(slot.generated)
                    if np.any(temps > 0):
                        toks = self._sample_jit(logits, jnp.asarray(rids),
                                                jnp.asarray(steps),
                                                jnp.asarray(temps))
                    else:
                        toks = greedy(logits)
                with tr.span("serve.decode_step.sync"):
                    toks_np = np.asarray(toks)
            # charge the step (plus any injected scheduler hiccup) before
            # retiring
            now += cs.dur + plan.step_delay()

            step_toks: dict = {}
            retire_now: List[Tuple[int, str]] = []
            for s in active:
                state.slots[s].decode_steps += 1
                tok = int(toks_np[s, 0])
                cur[s, 0] = tok
                mt.generated_tokens += 1
                step_toks[state.slots[s].request.rid] = [tok]
                reason = state.append_token(s, tok)
                if reason is None:
                    dl = state.slots[s].request.deadline
                    if dl is not None and now >= dl:
                        reason = "deadline"
                if reason is not None:
                    retire_now.append((s, reason))
            mt.observe_step(len(active), self.n_slots, queue.backlog(now))
            # watermark BEFORE the retires so replay sees tokens first
            if jr is not None:
                jr.watermark(step_toks, now)
            for s, reason in retire_now:
                _retire(s, reason)

            step_idx += 1
            if on_step is not None:
                on_step({"step": step_idx, "now": now,
                         "backlog": queue.backlog(now),
                         "in_flight": len(state.active_slots()),
                         "finished": mt.requests_finished,
                         "generated": mt.generated_tokens})
            if wd is not None and audit_every and step_idx % audit_every == 0:
                wd.check(in_flight=len(state.active_slots()))
            if (jr is not None and checkpoint_every
                    and step_idx % checkpoint_every == 0):
                from ..recovery.checkpoint import save_server_checkpoint
                ck = jr.checkpoint_path(step_idx)
                # a slot's absolute watermark is its generated list; the
                # record folds the resumed prefix in itself, so hand it
                # only the tokens emitted THIS incarnation
                inflight = [
                    (state.slots[s].request,
                     state.slots[s].generated[
                         state.slots[s].request.n_resumed:])
                    for s in state.active_slots()
                ]
                save_server_checkpoint(
                    ck, kind="continuous", step=step_idx, now=now,
                    seed=self.seed, policy=self.scheduler.name,
                    pending=queue.pending(), inflight=inflight,
                    results=results, metrics=mt)
                jr.rotate(ck, step_idx, now)

        _reject_unservable(queue, now, mt, results, tr, jr)
        self.drained = should_drain is not None and should_drain()
        if jr is not None and self.drained:
            # final drain checkpoint: everything finished or pending is
            # anchored, so a later --resume (or a fleet re-offer) picks
            # up exactly here with no journal tail to replay
            from ..recovery.checkpoint import save_server_checkpoint
            ck = jr.checkpoint_path(step_idx)
            save_server_checkpoint(
                ck, kind="continuous", step=step_idx, now=now,
                seed=self.seed, policy=self.scheduler.name,
                pending=queue.pending(), inflight=[],
                results=results, metrics=mt)
            jr.rotate(ck, step_idx, now)
        mt.wall_time += time.perf_counter() - t_wall0
        return sorted(results, key=lambda r: r.rid), mt


# ---------------------------------------------------------------------------
# Static-batching baseline (for the continuous-vs-static comparison)
# ---------------------------------------------------------------------------


def serve_static(cfg: ModelConfig, params, requests: Sequence[ServeRequest], *,
                 batch_size: int, rt: Optional[Runtime] = None,
                 ) -> Tuple[List[ServeResult], int]:
    """Serve in arrival-order chunks with the padded static engine; every
    request in a chunk decodes to the chunk max budget. Returns results
    (stop-token truncated) and the total number of decode iterations."""
    eng = ServingEngine(cfg, params, rt=rt, max_batch=batch_size)
    ordered = sorted(requests, key=lambda r: (r.arrival_time, r.rid))
    results: List[ServeResult] = []
    decode_iters = 0
    for i in range(0, len(ordered), batch_size):
        chunk = ordered[i : i + batch_size]
        comps = eng.generate_batch([
            Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                    temperature=r.temperature) for r in chunk
        ])
        decode_iters += max(r.max_new_tokens for r in chunk) - 1
        for r, c in zip(chunk, comps):
            toks, reason = truncate_at_stop(c.tokens, r.stop_tokens)
            results.append(ServeResult(rid=r.rid, tokens=toks, finish_reason=reason,
                                       arrival_time=r.arrival_time))
    return sorted(results, key=lambda r: r.rid), decode_iters


# ---------------------------------------------------------------------------
# Offloaded path: scheduler-driven prefetch between batch waves
# ---------------------------------------------------------------------------


class OffloadedWaveServer:
    """Wave scheduling over the offloaded expert cache (Sec 3.2).

    Requests are served greedily in scheduler order, ``wave_size`` at a
    time; before each wave the mean of the wave's predicted expert
    scores is prefetched so the resident set matches the co-scheduled
    requests. The expert cache (and its residency) persists across
    waves — that persistence is exactly what the affinity policy
    exploits. The serving clock advances by the Eq. 3 cost model:
    serial by default, or the engine's overlapped clock (layer ``l``'s
    router output issues layer ``l+1``'s fetches) with ``overlap=True``.
    Both cumulative modeled times are reported either way."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        capacity: int,
        policy: str = "lfu",
        gamma: float = 0.9,
        scheduler: Optional[Scheduler] = None,
        wave_size: int = 4,
        quantized: bool = False,
        hw: Optional[HardwareProfile] = None,
        use_prefetch: bool = True,
        lora=None,
        lora_scale: float = 1.0,
        overlap: bool = False,
        engine_impl: str = "slab",
        little_experts: bool = False,
        little_rank: int = 8,
        little_quantized: bool = False,
        fetch_policy: Optional[FetchPolicy] = None,
        pressure_frac: float = 0.75,
        max_backlog: Optional[int] = None,
        seed: int = 0,
        kernel_backend: str = "ref",
    ):
        self.cfg = cfg
        self.seed = seed  # recorded in recovery checkpoints
        self.scheduler = scheduler or FCFSScheduler()
        self.wave_size = wave_size
        self.use_prefetch = use_prefetch
        self.overlap = overlap
        self.max_backlog = max_backlog
        self.engine = OffloadedMoEEngine(
            cfg, params, capacity=capacity, policy=policy, gamma=gamma,
            quantized=quantized, hw=hw, lora=lora, lora_scale=lora_scale,
            impl=engine_impl, little_experts=little_experts,
            little_rank=little_rank, little_quantized=little_quantized,
            fetch_policy=fetch_policy, pressure_frac=pressure_frac,
            kernel_backend=kernel_backend,
        )
        self.hw = self.engine.hw

    def run(self, queue: RequestQueue,
            metrics: Optional[ServerMetrics] = None,
            *,
            journal=None,
            checkpoint_every: Optional[int] = None,
            audit_every: Optional[int] = None,
            resume=None,
            on_step=None,
            should_drain=None,
            ) -> Tuple[List[ServeResult], ServerMetrics]:
        """Serve the queue. Same crash-safety knobs as
        :meth:`ContinuousBatchingServer.run`, on wave granularity:
        checkpoints land every ``checkpoint_every`` waves (with the
        engine's cache state for warm revival — in-flight is always
        empty because requests are atomic within a wave), the watchdog
        runs every ``audit_every`` waves, ``on_step`` fires once per
        completed wave, and ``should_drain`` stops scheduling further
        waves (a wave is atomic, so drain waits for the current one,
        writes a final anchored checkpoint, and sets ``self.drained``).
        Revive the engine (``engine.revive(resume.engine["cache"])`` +
        restoring ``engine.metrics``) before calling run with
        ``resume``."""
        mt = metrics or ServerMetrics(policy=self.scheduler.name)
        tr = get_tracer()
        plan = get_fault_plan()
        eng = self.engine
        jr = journal
        results: List[ServeResult] = []
        now = 0.0
        wave_idx = 0
        wd = None
        if resume is not None:
            now = resume.now
            wave_idx = resume.step
            results = list(resume.results)
        if audit_every or resume is not None:
            from ..recovery.audit import Watchdog
            wd = Watchdog(queue=queue, metrics=mt, engine=eng,
                          offered_base=resume.offered_base if resume else 0)
            if resume is not None:
                wd.check(in_flight=0)  # trust nothing restored, audited
        if jr is not None:
            for r in queue.pending():
                jr.arrival(r)
        t_wall0 = time.perf_counter()
        prev_wave: List[ServeRequest] = []
        if self.max_backlog is not None:
            queue.set_bound(self.max_backlog)

        self.drained = False
        while len(queue):
            if should_drain is not None and should_drain():
                break
            # -- admission control: shed what can't be served -----------
            _reject_unservable(queue, now, mt, results, tr, jr)
            if not len(queue):
                break
            ready = queue.ready(now)
            if not ready:
                now = max(now, queue.next_arrival())
                continue
            order = self.scheduler.order(ready, hot=prev_wave)
            wave = order[: self.wave_size]
            mt.observe_queue_depth(queue.backlog(now))
            # injected scheduling hiccup (traffic-burst / host jitter)
            now += plan.step_delay()

            if self.use_prefetch:
                scored = [r.expert_scores for r in wave if r.expert_scores is not None]
                if scored:
                    # prefetch DMA is real link traffic: charge it to the
                    # wave on the same Eq. 3 terms as demand misses (it
                    # precedes the wave, so it is not hidden under either
                    # clock — both accumulators advance equally)
                    p_tx0 = eng.metrics.prefetch_transfers
                    p_b0 = eng.metrics.prefetch_bytes
                    fd0 = eng.metrics.fault_delay_s
                    eng.prefetch(np.mean(scored, axis=0))
                    dt = (
                        (eng.metrics.prefetch_bytes - p_b0) / self.hw.host_link_bw
                        + (eng.metrics.prefetch_transfers - p_tx0)
                        * self.hw.transfer_latency
                        # spike/retry stall injected during the prefetch:
                        # no step record is open, so the cumulative
                        # fault-delay delta is exactly the prefetch's
                        # share (request deltas below can't see it —
                        # their baselines are read after this point)
                        + (eng.metrics.fault_delay_s - fd0)
                    )
                    now += dt
                    mt.modeled_time_serial += dt
                    mt.modeled_time_overlapped += dt

            for req in wave:
                queue.admit(req)
                if jr is not None:
                    jr.admit(req.rid, now)
                start = now
                before_s = eng.metrics.modeled_time(self.hw)
                step0 = len(eng.metrics.step_flops)
                host0 = eng.metrics.host_time
                deg0 = eng.metrics.degraded_uses
                # SLO budget left on the engine's own (serial) clock
                deadline_s = (None if req.slo is None
                              else max(req.deadline - now, 0.0))
                # a request resumed from a crash re-prefills up to its
                # journaled watermark and only generates the remainder
                inp = (req.prompt if req.resumed is None else
                       np.concatenate([req.prompt, req.resumed])
                       .astype(np.int32))
                res = eng.generate(inp[None, :],
                                   max_new_tokens=(req.max_new_tokens
                                                   - req.n_resumed),
                                   quality=req.quality, deadline_s=deadline_s)
                d_serial = eng.metrics.modeled_time(self.hw) - before_s
                # delta over only this request's recorded steps — not a
                # re-walk of the whole accumulated history per request
                d_overlap = (eng.metrics.overlapped_span(self.hw, step0)
                             + eng.metrics.host_time - host0)
                # the prefill step alone (step0) dates the first token on
                # whichever Eq.-3 clock drives this server's time
                d_first = (eng.metrics.overlapped_span(self.hw, step0, step0 + 1)
                           if self.overlap else
                           eng.metrics.serial_span(self.hw, step0, step0 + 1))
                # consumed: don't retain per-step arrays for the whole run
                eng.metrics.drop_step_records(self.hw)
                mt.modeled_time_serial += d_serial
                mt.modeled_time_overlapped += d_overlap
                now += d_overlap if self.overlap else d_serial
                new = np.asarray(res["tokens"])[0]
                full = (new if req.resumed is None else
                        np.concatenate([req.resumed, new]))
                toks, reason = truncate_at_stop(full, req.stop_tokens)
                if res.get("stopped_early") and reason == "length":
                    reason = "deadline"  # cut mid-decode at the SLO
                degraded = eng.metrics.degraded_uses > deg0
                first_tok_time = start + d_first
                # the resumed prefix was generated (and counted) before
                # the crash; only this incarnation's tokens count here
                n_new = len(toks) - req.n_resumed
                mt.generated_tokens += n_new
                mt.prefill_tokens += len(inp)
                mt.decode_steps += n_new
                ttft = first_tok_time - req.arrival_time
                itl = (now - first_tok_time) / max(len(toks) - 1, 1)
                mt.observe_finish(now - req.arrival_time, ttft=ttft, itl=itl)
                attained = False
                if reason == "deadline":
                    mt.deadline_retired += 1
                elif req.slo is None or now <= req.deadline:
                    mt.slo_attained += 1
                    attained = True
                if degraded:
                    mt.degraded_requests += 1
                if tr.enabled:
                    tr.instant("serve.retire", rid=req.rid, reason=reason,
                               tokens=len(toks))
                result = ServeResult(
                    rid=req.rid, tokens=toks, finish_reason=reason,
                    arrival_time=req.arrival_time, start_time=start,
                    finish_time=now, decode_steps=n_new, degraded=degraded,
                )
                if jr is not None:
                    # watermark BEFORE retire so replay sees tokens first
                    jr.watermark(
                        {req.rid: [int(t) for t in toks[req.n_resumed:]]},
                        now)
                    jr.retire(result, plen=len(inp), attained=attained,
                              ttft=ttft, itl=itl)
                results.append(result)
            prev_wave = wave

            wave_idx += 1
            if on_step is not None:
                on_step({"step": wave_idx, "now": now,
                         "backlog": queue.backlog(now), "in_flight": 0,
                         "finished": mt.requests_finished,
                         "generated": mt.generated_tokens})
            if wd is not None and audit_every and wave_idx % audit_every == 0:
                wd.check(in_flight=0)
            if (jr is not None and checkpoint_every
                    and wave_idx % checkpoint_every == 0):
                from ..recovery.checkpoint import save_server_checkpoint
                ck = jr.checkpoint_path(wave_idx)
                save_server_checkpoint(
                    ck, kind="wave", step=wave_idx, now=now,
                    seed=self.seed, policy=self.scheduler.name,
                    pending=queue.pending(), inflight=[],
                    results=results, metrics=mt,
                    engine={"cache": eng.cache_state(),
                            "metrics": eng.metrics.state()})
                jr.rotate(ck, wave_idx, now)

        _reject_unservable(queue, now, mt, results, tr, jr)
        self.drained = should_drain is not None and should_drain()
        if jr is not None and self.drained:
            from ..recovery.checkpoint import save_server_checkpoint
            ck = jr.checkpoint_path(wave_idx)
            save_server_checkpoint(
                ck, kind="wave", step=wave_idx, now=now,
                seed=self.seed, policy=self.scheduler.name,
                pending=queue.pending(), inflight=[],
                results=results, metrics=mt,
                engine={"cache": eng.cache_state(),
                        "metrics": eng.metrics.state()})
            jr.rotate(ck, wave_idx, now)

        stats = eng.cache.stats()
        mt.transfers = eng.metrics.transfers
        mt.transfer_bytes = eng.metrics.transfer_bytes
        mt.prefetch_transfers = eng.metrics.prefetch_transfers
        mt.cache_hits, mt.cache_misses = stats.hits, stats.misses
        mt.modeled_time = now
        mt.wall_time += time.perf_counter() - t_wall0
        return sorted(results, key=lambda r: r.rid), mt
