"""Fleet supervision (PR 10): heartbeats, worker fault kinds, graceful
drain, and the supervisor's restart / hang-detection / failover loop.

Fast unit tests (heartbeat files, fault grammar, in-process drain +
resume) run in the core lane; everything that launches real worker
processes is marked ``fleet`` (its own CI lane — each test pays one
fresh jax import + jit warmup per worker process)."""
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.faults import (
    NULL_FAULT_PLAN,
    FaultPlan,
    parse_fault_spec,
    uninstall_fault_plan,
)
from repro.fleet import (
    HEARTBEAT_NAME,
    FleetConfig,
    FleetSupervisor,
    HeartbeatWriter,
    parse_worker_fault_schedule,
    read_heartbeat,
)
from repro.fleet.supervisor import RESTART_BACKOFF
from repro.models.model import init_params
from repro.recovery import RequestJournal, recover
from repro.serving import ContinuousBatchingServer, RequestQueue, ServeRequest

ARCH = "granite-moe-1b-a400m-smoke"
SRC = str(Path(__file__).resolve().parents[1] / "src")


@pytest.fixture(scope="module")
def setup():
    cfg = get_config(ARCH)
    params = init_params(jax.random.key(0), cfg, jnp.float32)
    return cfg, params


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    uninstall_fault_plan()
    yield
    uninstall_fault_plan()


def mk_requests(cfg, lens, budgets, *, seed=0):
    rng = np.random.default_rng(seed)
    return [
        ServeRequest(rid=i,
                     prompt=rng.integers(0, cfg.vocab, lens[i]).astype(np.int32),
                     max_new_tokens=budgets[i])
        for i in range(len(lens))
    ]


def subproc_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("REPRO_FAULTS", None)
    env.pop("REPRO_JOURNAL", None)
    return env


def wait_for(pred, timeout_s, what):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.25)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------------
# heartbeat files
# ---------------------------------------------------------------------------


def test_heartbeat_atomic_throttled_and_pid_stamped(tmp_path):
    hb = HeartbeatWriter(tmp_path / HEARTBEAT_NAME)
    assert hb.beat(phase="init")
    got = read_heartbeat(tmp_path / HEARTBEAT_NAME)
    assert got["seq"] == 1 and got["phase"] == "init"
    assert got["pid"] == os.getpid()  # the incarnation guard
    # throttle: a beat younger than min_interval_s is suppressed...
    assert not hb.beat(phase="serving", step=3, min_interval_s=60.0)
    assert read_heartbeat(tmp_path / HEARTBEAT_NAME)["seq"] == 1
    # ...but a phase-change beat (interval 0) always publishes
    assert hb.beat(phase="drained", step=3, finished=2)
    got = read_heartbeat(tmp_path / HEARTBEAT_NAME)
    assert got["seq"] == 2 and got["step"] == 3 and got["finished"] == 2
    # atomic replace leaves no tmp litter
    assert sorted(p.name for p in tmp_path.iterdir()) == [HEARTBEAT_NAME]
    assert read_heartbeat(tmp_path / "missing.json") is None


# ---------------------------------------------------------------------------
# worker-level fault grammar: kill= / hang=
# ---------------------------------------------------------------------------


def test_kill_hang_fault_grammar_and_determinism():
    cfg = parse_fault_spec("kill_at=3,seed=1")
    assert cfg.kill_at == 3 and cfg.any_active
    plan = FaultPlan(cfg)
    assert not plan.maybe_kill() and not plan.maybe_kill()
    assert plan.maybe_kill("step")  # third call
    assert plan.counters["kill"] == 1

    cfg = parse_fault_spec("hang_at=2:45")
    assert cfg.hang_at == 2 and cfg.hang_s == 45.0
    plan = FaultPlan(cfg)
    assert plan.maybe_hang() == 0.0
    assert plan.maybe_hang() == 45.0
    assert plan.counters["hang"] == 1

    def kill_point(seed):
        p = FaultPlan(parse_fault_spec(f"kill=0.2,seed={seed}"))
        for i in range(1, 200):
            if p.maybe_kill():
                return i
        return None

    assert kill_point(5) is not None
    assert kill_point(5) == kill_point(5)  # seeded rate is deterministic
    # the null plan never fires and costs nothing
    assert not NULL_FAULT_PLAN.maybe_kill()
    assert NULL_FAULT_PLAN.maybe_hang() == 0.0


def test_parse_worker_fault_schedule():
    sched = parse_worker_fault_schedule("0:kill_at=6;2:hang_at=4:30,seed=1")
    assert set(sched) == {0, 2}
    assert parse_fault_spec(sched[0]).kill_at == 6
    assert parse_fault_spec(sched[2]).hang_s == 30.0
    assert parse_worker_fault_schedule(None) == {}
    assert parse_worker_fault_schedule("") == {}
    with pytest.raises(ValueError):
        parse_worker_fault_schedule("0:frobnicate=1")  # typo fails eagerly


def test_restart_backoff_jittered_capped_decorrelated():
    # capped exponential even at huge attempt counts
    assert RESTART_BACKOFF.backoff(50, salt=0) <= RESTART_BACKOFF.backoff_cap_s
    # deterministic per (salt, attempt); distinct salts decorrelate a
    # correlated failure so the fleet doesn't restart in lockstep
    assert (RESTART_BACKOFF.backoff(2, salt=1)
            == RESTART_BACKOFF.backoff(2, salt=1))
    assert len({RESTART_BACKOFF.backoff(2, salt=s) for s in range(8)}) > 1


# ---------------------------------------------------------------------------
# graceful drain: in-process (fast) — stop admission, final anchored
# checkpoint, token-identical resume
# ---------------------------------------------------------------------------


def test_continuous_drain_then_resume_token_identical(setup, tmp_path):
    cfg, params = setup
    lens, budgets = [6, 9, 7, 11], [8, 5, 10, 6]
    ref, _ = ContinuousBatchingServer(
        cfg, params, n_slots=2, max_len=32).run(
            RequestQueue(mk_requests(cfg, lens, budgets)))

    srv = ContinuousBatchingServer(cfg, params, n_slots=2, max_len=32)
    jr = RequestJournal(tmp_path)
    steps = {"n": 0}

    def on_step(info):
        steps["n"] += 1

    results, mt = srv.run(
        RequestQueue(mk_requests(cfg, lens, budgets)), journal=jr,
        checkpoint_every=3, on_step=on_step,
        should_drain=lambda: steps["n"] >= 4)
    jr.close()
    assert srv.drained and steps["n"] >= 4
    assert len(results) < len(lens), "drain should leave work behind"

    state = recover(tmp_path)
    assert state is not None and state.kind == "continuous"
    assert state.pending, "drain checkpoint should carry live requests"
    srv2 = ContinuousBatchingServer(cfg, params, n_slots=2, max_len=32)
    jr2 = RequestJournal(tmp_path, seen=state.seen_rids)
    rest, mt2 = srv2.run(state.build_queue(None), state.metrics,
                         journal=jr2, resume=state)
    jr2.close()
    assert not srv2.drained  # no drain signal on the second leg
    by = {r.rid: r for r in list(results) + list(rest)}
    assert sorted(by) == [0, 1, 2, 3]
    for a in ref:
        np.testing.assert_array_equal(a.tokens, by[a.rid].tokens)
        assert a.finish_reason == by[a.rid].finish_reason
    assert mt2.generated_tokens == sum(len(r.tokens) for r in ref)


# ---------------------------------------------------------------------------
# subprocess tests: real workers, real signals (fleet CI lane)
# ---------------------------------------------------------------------------


@pytest.mark.fleet
def test_bench_serve_sigterm_drains_checkpoints_and_resumes(tmp_path):
    """SIGTERM mid-serve => exit 0, 'DRAINED' banner, journal holds the
    remainder; a --resume run completes token-identically vs an
    uninterrupted reference run."""
    common = [sys.executable, "-m", "repro.launch.bench_serve",
              "--arch", ARCH, "--n-requests", "8", "--slots", "2",
              "--arrival", "all_at_once", "--prompt-len", "10",
              "--max-new", "10", "--seed", "0"]
    env = subproc_env()

    ref_path = tmp_path / "ref.json"
    subprocess.run(common + ["--out-results", str(ref_path)], env=env,
                   check=True, timeout=300, stdout=subprocess.DEVNULL)
    ref = {r["rid"]: r["tokens"]
           for r in json.loads(ref_path.read_text())["results"]}

    jdir = tmp_path / "journal"
    out1 = tmp_path / "drained.json"
    proc = subprocess.Popen(
        common + ["--journal", str(jdir), "--checkpoint-every", "2",
                  "--out-results", str(out1)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    try:
        # wait for journal evidence that serving is underway, then drain
        wait_for(lambda: '"ev"' in ((jdir / "journal.jsonl").read_text()
                                    if (jdir / "journal.jsonl").exists()
                                    else ""),
                 timeout_s=240, what="journal activity")
        proc.send_signal(signal.SIGTERM)
        stdout, _ = proc.communicate(timeout=240)
    finally:
        proc.kill()
    assert proc.returncode == 0, stdout
    assert "DRAINED on SIGTERM" in stdout

    got = {r["rid"]: r["tokens"]
           for r in json.loads(out1.read_text())["results"]}
    state = recover(jdir)
    assert state is not None
    if state.pending:  # SIGTERM landed mid-serve, not after the fact
        out2 = tmp_path / "resumed.json"
        subprocess.run(common + ["--journal", str(jdir), "--resume",
                                 "--out-results", str(out2)],
                       env=env, check=True, timeout=300,
                       stdout=subprocess.DEVNULL)
        for r in json.loads(out2.read_text())["results"]:
            got[r["rid"]] = r["tokens"]
    assert got == ref


def _fleet_requests(cfg, n=6):
    return mk_requests(cfg, [6, 9, 7, 11, 8, 5][:n], [8, 5, 10, 6, 7, 9][:n])


@pytest.mark.fleet
def test_fleet_kill_restart_token_identical(setup, tmp_path):
    """An injected mid-step kill (os._exit, journal current through the
    last step) is detected as a crash; the restarted incarnation
    recovers from its journal and the fleet finishes everything
    token-identical to a single uninterrupted server."""
    cfg, params = setup
    base = _fleet_requests(cfg)
    ref, _ = ContinuousBatchingServer(cfg, params, n_slots=2, max_len=32).run(
        RequestQueue(_fleet_requests(cfg)))
    ref_tokens = {r.rid: [int(t) for t in r.tokens] for r in ref}

    fcfg = FleetConfig(n_workers=2, arch=ARCH, slots=2, checkpoint_every=2,
                       heartbeat_s=0.2,
                       worker_faults={0: "kill_at=4,seed=0"})
    sup = FleetSupervisor(base, fcfg, tmp_path)
    report = sup.run(max_wall_s=240.0)

    assert report["restarts"]["crash"] >= 1
    assert report["unaccounted"] == [] and not report["pending_checkpointed"]
    assert report["finished"] == len(base)
    got = {int(rid): r["tokens"] for rid, r in report["results"].items()}
    assert got == ref_tokens
    assert len(report["failover_s"]["samples"]) >= 1
    kinds = {e["event"] for e in report["events"]}
    assert "crash" in kinds and "hang_detected" not in kinds
    prom = sup.prometheus_text()
    assert 'worker_restarts_total{reason="crash"} 1' in prom.replace(".0", "")
    assert "fleet_failover_s_bucket" in prom


@pytest.mark.fleet
def test_fleet_hang_detected_distinct_from_crash(setup, tmp_path):
    """A hung worker keeps its process alive (a waitpid loop sees
    nothing) — only heartbeat staleness can catch it. The supervisor
    SIGKILLs, books the restart under reason=hang, and the fleet still
    finishes token-identically."""
    cfg, params = setup
    base = _fleet_requests(cfg)
    ref, _ = ContinuousBatchingServer(cfg, params, n_slots=2, max_len=32).run(
        RequestQueue(_fleet_requests(cfg)))
    ref_tokens = {r.rid: [int(t) for t in r.tokens] for r in ref}

    fcfg = FleetConfig(n_workers=2, arch=ARCH, slots=2, checkpoint_every=2,
                       heartbeat_s=0.2, hang_deadline_s=2.0,
                       worker_faults={0: "hang_at=3:120"})
    sup = FleetSupervisor(base, fcfg, tmp_path)
    report = sup.run(max_wall_s=240.0)

    assert report["restarts"]["hang"] >= 1
    assert report["restarts"]["crash"] == 0  # the distinction under test
    kinds = {e["event"] for e in report["events"]}
    assert "hang_detected" in kinds and "crash" not in kinds
    assert report["unaccounted"] == [] and not report["pending_checkpointed"]
    got = {int(rid): r["tokens"] for rid, r in report["results"].items()}
    assert got == ref_tokens


@pytest.mark.fleet
def test_fleet_supervisor_sigterm_drains_exit_zero(tmp_path):
    """SIGTERM to the fleet launcher: every worker stops admission,
    finishes in-flight, checkpoints and exits 0; the supervisor exits 0
    with every request finished or checkpointed."""
    out = tmp_path / "report.json"
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.bench_fleet",
         "--arch", ARCH, "--workers", "2", "--n-requests", "10",
         "--prompt-len", "8", "--max-new", "10",
         "--dir", str(tmp_path / "fleet"), "--out", str(out)],
        env=subproc_env(), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        def journaled():
            return any(p.stat().st_size > 0 for p in
                       tmp_path.glob("fleet/worker-*/journal/journal.jsonl"))
        wait_for(journaled, timeout_s=240, what="worker journal activity")
        proc.send_signal(signal.SIGTERM)
        stdout, _ = proc.communicate(timeout=240)
    finally:
        proc.kill()
    assert proc.returncode == 0, stdout

    report = json.loads(out.read_text())
    assert report["drained"]
    assert report["unaccounted"] == []
    assert (report["finished"] + len(report["pending_checkpointed"])
            == report["n_requests"])
    for w in report["workers"]:
        assert w["exit_code"] == 0, (w, stdout)


def test_drain_holds_sigterm_until_a_starting_worker_has_beaten(tmp_path):
    """A worker still importing has no SIGTERM handler: the drain waits
    for its first heartbeat (written once the handler is in) before it
    forwards the signal, so the worker drains instead of dying of it."""
    sup = FleetSupervisor([], FleetConfig(n_workers=1), tmp_path)
    sup.setup()
    w = sup.workers[0]
    hb_path = w.dir / HEARTBEAT_NAME
    code = (
        "import signal, time\n"
        "from repro.fleet.heartbeat import HeartbeatWriter\n"
        "time.sleep(1.0)\n"  # still starting up: the default action kills
        "got = []\n"
        "signal.signal(signal.SIGTERM, lambda *_: got.append(1))\n"
        f"HeartbeatWriter({str(hb_path)!r}).beat(phase='init')\n"
        "while not got:\n"
        "    time.sleep(0.01)\n")
    w.proc = subprocess.Popen([sys.executable, "-c", code],
                              env=subproc_env())
    sup.drain()
    assert w.exit_code == 0
    assert read_heartbeat(hb_path)["pid"] == w.proc.pid
    assert [e["event"] for e in sup.events] == ["sigterm"]
