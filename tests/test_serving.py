"""Continuous-batching serving subsystem: output equivalence with the
static engine, EOS/budget retirement, slot-reuse invariants, scheduler
determinism, and the expert-affinity >= FCFS cache property."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.expert_cache import ModelExpertCache
from repro.inference.engine import Request, ServingEngine
from repro.models.model import init_params
from repro.serving import (
    BatchState,
    ContinuousBatchingServer,
    OffloadedWaveServer,
    RequestQueue,
    ServeRequest,
    TrafficConfig,
    get_scheduler,
    prefill_expert_scores,
    serve_static,
    synthesize_workload,
)
from repro.data.synthetic import ClusterLM, SyntheticConfig


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("granite-moe-1b-a400m-smoke")
    params = init_params(jax.random.key(0), cfg, jnp.float32)
    return cfg, params


def mk_requests(cfg, lens, budgets, *, seed=0, arrivals=None, temps=None):
    rng = np.random.default_rng(seed)
    return [
        ServeRequest(
            rid=i,
            prompt=rng.integers(0, cfg.vocab, lens[i]).astype(np.int32),
            max_new_tokens=budgets[i],
            arrival_time=0.0 if arrivals is None else arrivals[i],
            temperature=0.0 if temps is None else temps[i],
        )
        for i in range(len(lens))
    ]


# ---------------------------------------------------------------------------
# Continuous batching: correctness
# ---------------------------------------------------------------------------


def test_continuous_matches_single_request_engine(setup):
    """In-flight batching must not change any request's tokens: each
    completion equals the request decoded alone through the static
    engine (mixed prompt lengths AND mixed budgets)."""
    cfg, params = setup
    reqs = mk_requests(cfg, lens=[6, 11, 8, 14, 9], budgets=[7, 3, 9, 5, 6])
    srv = ContinuousBatchingServer(cfg, params, n_slots=2, max_len=32)
    results, mt = srv.run(RequestQueue(reqs))
    assert [r.rid for r in results] == [0, 1, 2, 3, 4]
    eng = ServingEngine(cfg, params, max_batch=1)
    for req, res in zip(reqs, results):
        ref = eng.generate_batch(
            [Request(prompt=req.prompt, max_new_tokens=req.max_new_tokens)]
        )[0]
        assert res.finish_reason == "length"
        np.testing.assert_array_equal(res.tokens, ref.tokens)
    assert mt.generated_tokens == sum(r.max_new_tokens for r in reqs)
    assert len(mt.latencies) == len(reqs)


def test_resumed_prefill_of_an_unseen_length_matches_the_eager_path(setup):
    """Each prefill runs as one compiled program per prompt length. Its
    greedy tokens equal the eager prefill's (``ServingEngine``) for
    several lengths, and for resumed requests whose ``prompt + resumed``
    lengths (9, 14) this server has not served before."""
    cfg, params = setup
    reqs = mk_requests(cfg, lens=[6, 11, 8], budgets=[8, 8, 8])
    srv = ContinuousBatchingServer(cfg, params, n_slots=2, max_len=32)
    full, _ = srv.run(RequestQueue(reqs))
    resumed = [ServeRequest(rid=r.rid, prompt=r.prompt, max_new_tokens=8,
                            resumed=f.tokens[:3])
               for r, f in zip(reqs, full)]
    again, _ = srv.run(RequestQueue(resumed))
    eng = ServingEngine(cfg, params, max_batch=1)
    for req, a, b in zip(reqs, full, again):
        ref = eng.generate_batch(
            [Request(prompt=req.prompt, max_new_tokens=8)])[0]
        np.testing.assert_array_equal(a.tokens, ref.tokens)
        np.testing.assert_array_equal(b.tokens, ref.tokens)


def test_a_seen_prompt_length_reuses_its_prefill_program(setup):
    """The first prefill of a prompt length builds its program; a later
    prefill of that length compiles nothing, and
    ``serve_prefill_programs_total`` books one "built" per new length and
    "reused" for every other prefill."""
    from repro.obs import REGISTRY, disable_tracing, enable_tracing

    def programs():
        return {o: REGISTRY.counter("serve_prefill_programs_total",
                                    outcome=o).value
                for o in ("built", "reused")}

    def compiles():
        return REGISTRY.counter("jax_compiles_total",
                                span="serve.prefill.forward").value

    cfg, params = setup
    srv = ContinuousBatchingServer(cfg, params, n_slots=2, max_len=32)
    p0 = programs()
    srv.run(RequestQueue(mk_requests(cfg, lens=[7, 10, 7], budgets=[2, 2, 2])))
    p1 = programs()
    assert (p1["built"] - p0["built"], p1["reused"] - p0["reused"]) == (2, 1)
    tr = enable_tracing()
    try:
        c0 = compiles()
        srv.run(RequestQueue(mk_requests(cfg, lens=[10, 7, 10],
                                         budgets=[2, 2, 2], seed=1)))
        fwd = [s for s in tr.spans() if s.name == "serve.prefill.forward"]
        assert len(fwd) == 3
        assert compiles() == c0
        assert not any("jax_compiles" in s.args for s in fwd)
        srv.run(RequestQueue(mk_requests(cfg, lens=[13], budgets=[2], seed=2)))
        assert compiles() - c0 == 1
    finally:
        disable_tracing()
    p2 = programs()
    assert (p2["built"] - p1["built"], p2["reused"] - p1["reused"]) == (1, 3)


def test_continuous_beats_static_on_mixed_budgets(setup):
    """Acceptance: on a mixed-length workload, continuous batching emits
    the same tokens per request in strictly fewer decode iterations than
    padded static batching."""
    cfg, params = setup
    # equal prompt lengths (so static left-padding is a no-op and the
    # outputs are comparable), strongly mixed decode budgets
    budgets = [3, 12, 5, 9, 4, 11, 6, 8]
    reqs = mk_requests(cfg, lens=[8] * len(budgets), budgets=budgets)
    srv = ContinuousBatchingServer(cfg, params, n_slots=4, max_len=24)
    cont, mt = srv.run(RequestQueue(reqs))
    stat, static_iters = serve_static(cfg, params, reqs, batch_size=4)
    for a, b in zip(cont, stat):
        assert a.rid == b.rid
        np.testing.assert_array_equal(a.tokens, b.tokens)
    assert mt.decode_steps < static_iters, (mt.decode_steps, static_iters)
    # the saving is the point: static pays the chunk-max budget per row
    assert mt.occupancy > 0.5


def test_stop_token_retires_early_and_slot_is_reused(setup):
    """EOS-style retirement: a stop token ends the request mid-budget,
    the completion carries finish_reason='stop', and the freed slot
    serves a queued request."""
    cfg, params = setup
    reqs = mk_requests(cfg, lens=[9, 9, 9], budgets=[10, 10, 10])
    # find the victim's greedy tokens, then declare its 3rd token an EOS
    eng = ServingEngine(cfg, params, max_batch=1)
    ref = eng.generate_batch([Request(prompt=reqs[0].prompt, max_new_tokens=10)])[0]
    reqs[0].stop_tokens = (int(ref.tokens[2]),)
    srv = ContinuousBatchingServer(cfg, params, n_slots=1, max_len=32)
    results, mt = srv.run(RequestQueue(reqs))
    assert results[0].finish_reason == "stop"
    assert len(results[0].tokens) == 3
    np.testing.assert_array_equal(results[0].tokens, ref.tokens[:3])
    # the other two requests ran to budget through the same single slot
    assert [r.finish_reason for r in results[1:]] == ["length", "length"]
    assert all(len(r.tokens) == 10 for r in results[1:])


def test_arrivals_respected_and_latencies_recorded(setup):
    cfg, params = setup
    reqs = mk_requests(cfg, lens=[8, 8, 8], budgets=[4, 4, 4],
                       arrivals=[0.0, 100.0, 100.0])
    srv = ContinuousBatchingServer(cfg, params, n_slots=2, max_len=16)
    results, mt = srv.run(RequestQueue(reqs))
    assert len(results) == 3
    # rid 1/2 cannot start before their arrival on the virtual clock
    assert results[1].start_time >= 100.0 and results[2].start_time >= 100.0
    assert all(r.latency >= 0 for r in results)


def test_per_request_temperature_sampling(setup):
    """Satellite fix: a greedy row must stay greedy even when another
    row in the same batch samples at high temperature."""
    cfg, params = setup
    rng = np.random.default_rng(0)
    p = rng.integers(0, cfg.vocab, 10).astype(np.int32)
    eng = ServingEngine(cfg, params, max_batch=2)
    greedy_ref = eng.generate_batch([Request(p, 8), Request(p, 8)])
    mixed = eng.generate_batch([Request(p, 8, 0.0), Request(p, 8, 2.0)], seed=3)
    np.testing.assert_array_equal(mixed[0].tokens, greedy_ref[0].tokens)
    assert not np.array_equal(mixed[1].tokens, greedy_ref[1].tokens)


def test_continuous_sampling_with_free_slots(setup):
    """Regression: mixed greedy/sampled rows alongside FREE slots must
    not crash key construction, greedy rows must match the greedy
    reference, and request-keyed sampling must be reproducible."""
    cfg, params = setup
    def mk():
        reqs = mk_requests(cfg, lens=[8, 8], budgets=[6, 6])
        reqs[1].temperature = 1.5
        return reqs
    # n_slots=3 > n_requests: one slot stays free throughout
    srv = ContinuousBatchingServer(cfg, params, n_slots=3, max_len=24, seed=5)
    res, _ = srv.run(RequestQueue(mk()))
    eng = ServingEngine(cfg, params, max_batch=1)
    ref = eng.generate_batch([Request(prompt=mk()[0].prompt, max_new_tokens=6)])[0]
    np.testing.assert_array_equal(res[0].tokens, ref.tokens)  # greedy untouched
    # same seed, fresh server -> identical sampled tokens
    srv2 = ContinuousBatchingServer(cfg, params, n_slots=3, max_len=24, seed=5)
    res2, _ = srv2.run(RequestQueue(mk()))
    np.testing.assert_array_equal(res[1].tokens, res2[1].tokens)


def test_generate_batch_honors_stop_tokens(setup):
    cfg, params = setup
    rng = np.random.default_rng(1)
    p = rng.integers(0, cfg.vocab, 9).astype(np.int32)
    eng = ServingEngine(cfg, params, max_batch=1)
    full = eng.generate_batch([Request(prompt=p, max_new_tokens=8)])[0]
    assert full.finish_reason == "length"
    stopped = eng.generate_batch(
        [Request(prompt=p, max_new_tokens=8, stop_tokens=(int(full.tokens[3]),))]
    )[0]
    assert stopped.finish_reason == "stop"
    assert len(stopped.tokens) <= 4
    np.testing.assert_array_equal(stopped.tokens, full.tokens[: len(stopped.tokens)])


# ---------------------------------------------------------------------------
# BatchState invariants
# ---------------------------------------------------------------------------


def test_batch_state_slot_invariants():
    bs = BatchState(2, max_len=16)
    r0 = ServeRequest(rid=0, prompt=np.zeros(4, np.int32), max_new_tokens=3)
    r1 = ServeRequest(rid=1, prompt=np.zeros(4, np.int32), max_new_tokens=2)
    bs.occupy(0, r0, now=1.0)
    assert bs.free_slots() == [1] and bs.active_slots() == [0]
    with pytest.raises(AssertionError):  # double occupancy
        bs.occupy(0, r1, now=1.0)
    with pytest.raises(AssertionError):  # same rid twice
        bs.occupy(1, ServeRequest(rid=0, prompt=np.zeros(2, np.int32)), now=1.0)
    with pytest.raises(AssertionError):  # KV budget exceeded
        bs.occupy(1, ServeRequest(rid=9, prompt=np.zeros(10, np.int32),
                                  max_new_tokens=10), now=1.0)
    # budget retirement
    assert bs.append_token(0, 5) is None
    assert bs.append_token(0, 6) is None
    assert bs.append_token(0, 7) == "length"
    res = bs.retire(0, now=2.0, reason="length")
    assert res.rid == 0 and list(res.tokens) == [5, 6, 7]
    assert bs.free_slots() == [0, 1]
    # stop-token retirement beats budget
    bs.occupy(1, ServeRequest(rid=2, prompt=np.zeros(2, np.int32),
                              max_new_tokens=5, stop_tokens=(42,)), now=3.0)
    assert bs.append_token(1, 42) == "stop"


# ---------------------------------------------------------------------------
# Schedulers + traffic
# ---------------------------------------------------------------------------


def _scored(rid, arrival, experts, *, L=2, E=8, budget=8):
    scores = np.zeros((L, E))
    scores[:, list(experts)] = 1.0
    return ServeRequest(rid=rid, prompt=np.zeros(4, np.int32), max_new_tokens=budget,
                        arrival_time=arrival, expert_scores=scores)


def test_scheduler_ordering_deterministic():
    a = _scored(0, 0.0, {0, 1}, budget=20)
    b = _scored(1, 1.0, {4, 5}, budget=2)
    c = _scored(2, 2.0, {0, 1}, budget=10)
    d = _scored(3, 3.0, {4, 5}, budget=5)
    ready = [d, c, b, a]
    assert [r.rid for r in get_scheduler("fcfs").order(ready)] == [0, 1, 2, 3]
    assert [r.rid for r in get_scheduler("sjf").order(ready)] == [1, 3, 2, 0]
    # affinity: seed with oldest (a), then chain by overlap a->c, then b->d
    aff = get_scheduler("expert-affinity", top_c=2)
    assert [r.rid for r in aff.order(ready)] == [0, 2, 1, 3]
    # hot context steers the seed pick
    assert [r.rid for r in aff.order(ready, hot=[b])][0] == 1
    # requests without scores degrade to FCFS
    plain = [ServeRequest(rid=i, prompt=np.zeros(2, np.int32), arrival_time=float(-i))
             for i in range(3)]
    assert [r.rid for r in get_scheduler("expert-affinity").order(plain)] == [2, 1, 0]


def test_traffic_generator_shapes_and_arrivals():
    lm = ClusterLM(SyntheticConfig(vocab=512, n_clusters=4, seq_len=64, seed=0))
    for arrival in ("poisson", "bursty", "all_at_once"):
        tcfg = TrafficConfig(n_requests=12, arrival=arrival, rate=2.0, burst_size=3,
                             prompt_len=(4, 9), max_new_tokens=(2, 5),
                             n_clusters=2, seed=1)
        reqs = synthesize_workload(lm, tcfg)
        assert len(reqs) == 12
        times = [r.arrival_time for r in reqs]
        assert times == sorted(times)
        assert all(4 <= r.prompt_len <= 9 for r in reqs)
        assert all(2 <= r.max_new_tokens <= 5 for r in reqs)
        assert all(r.cluster in (0, 1) for r in reqs)
        if arrival == "bursty":
            assert len(set(times)) == 4  # 12 requests in bursts of 3
        if arrival == "all_at_once":
            assert set(times) == {0.0}
    # same seed -> same trace
    r1 = synthesize_workload(lm, TrafficConfig(seed=7))
    r2 = synthesize_workload(lm, TrafficConfig(seed=7))
    assert all(np.array_equal(a.prompt, b.prompt) and a.arrival_time == b.arrival_time
               for a, b in zip(r1, r2))


def test_request_queue_semantics():
    reqs = [ServeRequest(rid=i, prompt=np.zeros(2, np.int32), arrival_time=float(i))
            for i in range(3)]
    q = RequestQueue(reqs)
    assert len(q) == 3 and q.next_arrival() == 0.0
    assert [r.rid for r in q.ready(1.5)] == [0, 1]
    assert q.backlog(1.5) == 2
    q.admit(reqs[0])
    assert [r.rid for r in q.ready(1.5)] == [1]
    assert len(q) == 2


def test_serve_request_identity_semantics():
    """ServeRequest/ServeResult carry ndarrays, so the dataclasses must
    use identity eq/hash: a generated __eq__ would crash list.remove
    and `in` with 'truth value of an array is ambiguous' the moment two
    requests share field values (regression for the eq=False hazard)."""
    from repro.serving import ServeResult

    a = ServeRequest(rid=0, prompt=np.zeros(3, np.int32))
    b = ServeRequest(rid=0, prompt=np.zeros(3, np.int32))  # same fields
    assert a != b and a == a
    assert len({a, b}) == 2  # hashable, by identity
    pool = [a, b]
    pool.remove(b)  # would raise on a field-wise __eq__
    assert pool == [a]
    ra = ServeResult(rid=0, tokens=np.zeros(2, np.int32), finish_reason="stop")
    rb = ServeResult(rid=0, tokens=np.zeros(2, np.int32), finish_reason="stop")
    assert ra != rb and len({ra, rb}) == 2


def test_request_queue_out_of_order_push():
    """push keeps the pool arrival-ordered even when arrivals land out
    of order (a late-arriving trace entry must not corrupt ready())."""
    q = RequestQueue()
    times = [3.0, 1.0, 2.0, 0.5, 2.0]
    reqs = [ServeRequest(rid=i, prompt=np.zeros(2, np.int32),
                         arrival_time=t) for i, t in enumerate(times)]
    for r in reqs:
        assert q.push(r)
    assert [r.rid for r in q.ready(10.0)] == [3, 1, 2, 4, 0]
    assert q.next_arrival() == 0.5
    # equal arrival times tie-break by rid, stably
    assert [r.rid for r in q.ready(2.0)] == [3, 1, 2, 4]


def test_request_queue_bound_sheds_latest():
    reqs = [ServeRequest(rid=i, prompt=np.zeros(2, np.int32),
                         arrival_time=float(i)) for i in range(5)]
    q = RequestQueue(reqs, max_pending=2)
    # future arrivals are not backlog: nothing shed at construction
    assert len(q) == 5 and q.shed_count == 0
    assert q.enforce_bound(0.5) == []  # backlog of 1 <= bound
    # three arrived, bound 2 -> the latest arrival is shed
    over = q.enforce_bound(2.5)
    assert [r.rid for r in over] == [2]
    assert q.shed_count == 1 and len(q) == 4
    # a live push over the total bound sheds the latest immediately
    late = ServeRequest(rid=9, prompt=np.zeros(2, np.int32),
                        arrival_time=10.0)
    assert not q.push(late)
    # an early arrival still displaces the latest pending one
    early = ServeRequest(rid=8, prompt=np.zeros(2, np.int32),
                         arrival_time=-1.0)
    assert not q.push(early)
    assert early in q.ready(0.0)
    drained = q.drain_shed()
    assert len(drained) == 3 and q.shed == [] and q.shed_count == 3


def test_request_queue_drop_expired():
    reqs = [ServeRequest(rid=i, prompt=np.zeros(2, np.int32),
                         arrival_time=0.0, slo=slo)
            for i, slo in enumerate([0.5, 2.0, None])]
    q = RequestQueue(reqs)
    expired = q.drop_expired(1.0)
    assert [r.rid for r in expired] == [0]  # slo=2.0 and best-effort stay
    assert len(q) == 2 and q.shed_count == 1
    assert q.drop_expired(1.0) == []


def test_profiling_shim_warns_and_reexports():
    """The deprecated serving.profiling alias must warn on import and
    still forward the scorers API until it is deleted."""
    import importlib
    import warnings

    with warnings.catch_warnings():  # first import may fire it too
        warnings.simplefilter("ignore", DeprecationWarning)
        import repro.serving.profiling as shim
    with pytest.warns(DeprecationWarning, match="scorers"):
        importlib.reload(shim)
    assert shim.prefill_expert_scores is prefill_expert_scores


# ---------------------------------------------------------------------------
# Expert affinity vs FCFS on a clustered workload
# ---------------------------------------------------------------------------


def test_expert_affinity_beats_fcfs_hit_rate_on_clustered_workload():
    """Deterministic scheduler+cache interaction: two clusters with
    disjoint expert preferences arrive interleaved; serving in affinity
    order keeps the per-layer cache hot, FCFS churns it."""
    L, E, C, K, T = 2, 16, 4, 2, 8
    rng = np.random.default_rng(0)
    pools = {0: np.arange(0, 4), 1: np.arange(8, 12)}  # disjoint Top-C sets
    reqs, traces = [], {}
    for i in range(8):
        k = i % 2  # interleaved arrival: worst case for FCFS
        reqs.append(_scored(i, float(i), set(pools[k]), L=L, E=E))
        reqs[-1].cluster = k
        traces[i] = rng.choice(pools[k], (T, L, K))  # routing inside the pool

    def replay(order):
        cache = ModelExpertCache(L, E, capacity=C, policy="lru")
        for r in order:
            for t in range(T):
                for l in range(L):
                    cache.access(l, traces[r.rid][t, l])
        return cache.stats()

    hit_fcfs = replay(get_scheduler("fcfs").order(reqs)).hit_rate
    aff = get_scheduler("expert-affinity", top_c=C)
    hit_aff = replay(aff.order(reqs)).hit_rate
    assert hit_aff >= hit_fcfs
    assert hit_aff > hit_fcfs + 0.05  # decisive, not a tie


def test_offloaded_wave_server_tokens_identical_across_policies(setup):
    """Scheduling changes WHEN experts move, never WHAT is computed: the
    wave server must emit identical tokens under every policy, while
    populating the per-policy cache telemetry."""
    cfg, params = setup
    lm = ClusterLM(SyntheticConfig(vocab=cfg.vocab, seq_len=24, n_clusters=4, seed=3))
    tcfg = TrafficConfig(n_requests=6, arrival="all_at_once", prompt_len=(8, 8),
                         max_new_tokens=(4, 4), n_clusters=3, seed=1)
    E = cfg.moe_spec.num_experts
    outs = {}
    for pol in ("fcfs", "expert-affinity"):
        reqs = synthesize_workload(lm, tcfg)
        prefill_expert_scores(cfg, params, reqs)
        sched = get_scheduler(pol) if pol == "fcfs" else get_scheduler(pol, top_c=2)
        srv = OffloadedWaveServer(cfg, params, capacity=max(E // 2, 1),
                                  scheduler=sched, wave_size=2)
        outs[pol] = srv.run(RequestQueue(reqs))
    res_f, mt_f = outs["fcfs"]
    res_a, mt_a = outs["expert-affinity"]
    for a, b in zip(res_a, res_f):
        assert a.rid == b.rid
        np.testing.assert_array_equal(a.tokens, b.tokens)
    for mt in (mt_f, mt_a):
        assert mt.cache_hits + mt.cache_misses > 0
        assert mt.modeled_time > 0
        assert mt.throughput_tok_s() > 0
        assert len(mt.latencies) == 6
