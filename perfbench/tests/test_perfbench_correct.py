"""The harness driving the program's servers on the CPU at tiny sizes:
the comparison that decides ``correct``, its lower-precision control, and
faults planted in the timed path of each kind of cell."""
from __future__ import annotations

import time
from pathlib import Path

import numpy as np
import pytest

from perfbench.lib import serve, traffic
from perfbench.lib.model import load_config

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"


def _run(traffic_file, fault=None, seconds=2.0, seed=2**32 + 7,
         config=DATA / "tiny.json", archs=BENCH / "archs"):
    cj, arch = load_config(config, archs)
    t = traffic.load_traffic(DATA / traffic_file)
    ms = [{"name": "output_tok_s", "unit": "tokens/s"}]
    return serve.run_cell({"name": cj["name"]}, cj, arch, t, ms, BENCH / "metrics",
                          BENCH / ".cache" / "tests", seed, seconds, False,
                          time.perf_counter(), fault=fault)


def test_served_tokens_match_the_reference():
    out = _run("tiny-chat.json")
    assert out["correct"], out["checks"]
    assert out["attempted"] == 12 and out["failed"] == 0
    gap = out["checks"]["mean_logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert out["metrics"]["output_tok_s"]["value"] >= 0  # CPU under load may stamp none in 2 s


def test_offloaded_closed_loop_matches_the_reference():
    out = _run("tiny-solo.json", seconds=0.5)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["counters"]["transfers"] > 0


def _alter_tokens(srv):
    """A token altered where it is produced: greedy picks the next id."""
    import repro.serving.server as server_mod

    real = server_mod.greedy
    server_mod.greedy = lambda logits: (real(logits) + 1) % logits.shape[-1]


def _stale_state(srv):
    """A decode step that returns its state unchanged: the new token's
    keys and values are dropped and no position advances."""
    real = srv._decode_jit
    srv._decode_jit = lambda p, tok, cache: (real(p, tok, cache)[0], cache, {})


@pytest.mark.parametrize("fault", [_alter_tokens, _stale_state],
                         ids=["token_altered", "state_unchanged"])
def test_a_broken_timed_path_is_not_correct(fault):
    import repro.serving.server as server_mod

    real = server_mod.greedy
    try:
        out = _run("tiny-chat.json", fault=fault)
    finally:
        server_mod.greedy = real
    assert not out["correct"]
    gap = out["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault", [None, _alter_tokens], ids=["sound", "token_altered"])
def test_an_architecture_added_as_files_is_judged_by_its_reference(fault):
    """``tinymix`` lives only under ``tests/data``: a leading dense layer,
    then windowed and full attention with a shared expert, served on
    prompts longer than its window."""
    import repro.serving.server as server_mod

    real = server_mod.greedy
    try:
        out = _run("tiny-chat.json", fault=fault, config=DATA / "tinymix.json",
                   archs=DATA / "archs")
    finally:
        server_mod.greedy = real
    gap = out["checks"]["mean_logit_gap"]
    assert out["attempted"] == 12 and out["failed"] == 0
    if fault is None:
        assert out["correct"] and gap["value"] <= gap["limit"], out["checks"]
    else:
        assert not out["correct"] and gap["value"] > gap["limit"]


def _alter_engine_tokens(srv):
    """Offloaded path: the engine's next token altered where it is made."""
    real = srv.engine._next_tok_fn
    srv.engine._next_tok_fn = lambda p, x: (real(p, x) + 1) % srv.engine.cfg.vocab


def _skip_expert_loads(srv):
    """Offloaded path: a swapped-in expert's weights never reach its slab
    slot, so the slot computes with whatever it held before."""
    real = srv.engine._ensure_resident
    srv.engine._ensure_resident = lambda i, needed: (real(i, needed)[0], None)


@pytest.mark.parametrize("fault", [_alter_engine_tokens, _skip_expert_loads],
                         ids=["token_altered", "stale_expert"])
def test_a_broken_offloaded_path_is_not_correct(fault):
    out = _run("tiny-solo.json", fault=fault, seconds=0.5)
    assert not out["correct"]
    gap = out["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("control", ["fp8", "int8"])
def test_the_lower_precision_control_is_not_correct(control):
    """The reference rounded to a lower precision, put in the program's
    place at every position of the served sequences, is judged by the
    rule that judges a run, and comes out not correct."""
    cj, arch = load_config(DATA / "tiny.json", BENCH / "archs")
    t = traffic.load_traffic(DATA / "tiny-chat.json")
    t = {**t, "correct": {**t["correct"], "sample_tokens": 10**6, "max_requests": 10**6}}
    # greedy decoding by the float32 reference itself: gap 0 by construction
    from perfbench.lib import reference

    ref, params = reference.Reference(cj, arch.forward), arch.make_weights(cj, 9)
    rng = np.random.default_rng(4)
    finished = {}
    for i in range(6):
        p = rng.integers(0, 256, 16).astype(np.int32)
        seq = list(p)
        for _ in range(20):
            seq.append(int(np.asarray(ref.logits(params, np.asarray(seq)))[-1].argmax()))
        finished[i] = (p, np.asarray(seq[16:], np.int32))
    got = serve.compare(arch, cj, t, 9, finished, controls=[control])
    gap = got["checks"]["mean_logit_gap"]
    assert got["ok"] and gap["value"] <= gap["limit"]
    ctl = got["controls"][control]
    assert not ctl["ok"]
    assert ctl["checks"]["mean_logit_gap"]["value"] > gap["limit"]
