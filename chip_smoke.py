#!/usr/bin/env python3
"""Proof that the served path runs on one TPU chip.

    python chip_smoke.py

Serves granite-moe-1b-a400m at its published widths (24 layers,
d_model 1024, 32 experts top-8, vocab 49,155; random weights from seed
0, about 5.5 GB in float32) through ``repro.launch.bench_serve``, with
the Pallas kernels compiled for the chip:

  parity  full-width logits under the "auto" backend (compiled Pallas
          flash_attn + moe_gmm) against "ref" (plain XLA) on the chip,
          at a non-aligned and an aligned prompt length;
  A       fits-in-memory: continuous batching, 8 requests over 4 slots,
          prompts of 8-64 tokens, up to 16 new tokens, greedy;
  B       offloaded: the same requests through the expert cache at
          capacity C = E/4 = 8.

Each phase fails the script when its check fails: every request
finished with in-vocabulary tokens, finite logits, compiled Pallas
kernels in the programs that ran (dispatch counter and the compiled
program text), logits within PARITY_TOL, and offloaded transfers > 0.
Wall times are host-clock seconds, compilation included; Eq.-3 clock
values are modeled. Neither is a benchmark result.

Exits non-zero before any model work when JAX finds no TPU. The last
line of stdout is one JSON object naming the device, printed only when
every phase passed.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time
from pathlib import Path

ARCH = "granite-moe-1b-a400m"
SERVE_ARGS = ["--arch", ARCH, "--n-requests", "8", "--slots", "4",
              "--min-prompt-len", "8", "--prompt-len", "64", "--max-new", "16",
              "--arrival", "all_at_once", "--seed", "0",
              "--kernel-backend", "auto"]
CAPACITY = 8  # E/4
PARITY_LENGTHS = (23, 64)
# max |auto - ref| over max |ref| across the logits. On a TPU, XLA's
# default precision rounds float32 matmul operands to bfloat16 (about
# 2^-8 relative per product) on the ref side, and the error compounds
# over 24 layers; a wrong mask or tile shows up as an O(1) ratio.
PARITY_TOL = 0.05
KERNELS = ("flash_attn", "moe_gmm")


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def kernel_counts() -> dict:
    """{op: {"compiled"|"interpret"|"ref": traced call sites}} from the
    dispatch counter, which books the backend each call site ran."""
    from repro.obs.registry import REGISTRY

    def n(op, backend, interpret):
        return REGISTRY.counter("kernel_dispatch_total", op=op,
                                backend=backend, interpret=interpret).value

    return {op: {"compiled": n(op, "pallas", False),
                 "interpret": n(op, "pallas", True),
                 "ref": n(op, "ref", False)} for op in KERNELS}


def check_kernels_ran(phase: str, before: dict) -> None:
    """Prefill attention and the MoE FFN traced only compiled Pallas."""
    after = kernel_counts()
    for op in KERNELS:
        got = {k: after[op][k] - before[op][k] for k in after[op]}
        print(f"  {phase}: {op} traced call sites {got}")
        if got["compiled"] < 1 or got["interpret"] or got["ref"]:
            fail(f"{phase}: {op} did not run only as a compiled Pallas kernel")


def has_kernel(text: str, name: str) -> bool:
    return any(f"%{name}" in line and 'custom_call_target="tpu_custom_call"'
               in line for line in text.splitlines())


def check_served(phase: str, results, vocab: int) -> dict:
    if results is None or len(results) != 8:
        fail(f"{phase}: {0 if results is None else len(results)}/8 requests "
             f"finished")
    for r in results:
        toks = [int(t) for t in r.tokens]
        if r.finish_reason not in ("length", "stop") or not 1 <= len(toks) <= 16:
            fail(f"{phase}: rid {r.rid} ended {r.finish_reason!r} with "
                 f"{len(toks)} tokens")
        if any(not 0 <= t < vocab for t in toks):
            fail(f"{phase}: rid {r.rid} emitted a token outside the vocab")
    return {r.rid: [int(t) for t in r.tokens] for r in results}


def parity(cfg, jax, jnp) -> None:
    from repro.models.model import apply_model, init_params
    from repro.models.runtime import Runtime

    t0 = time.perf_counter()
    params = jax.block_until_ready(
        init_params(jax.random.key(0), cfg, jnp.float32))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"parameter bytes on the device: {nbytes} "
          f"(init wall {time.perf_counter() - t0:.3f} s)")
    for T in PARITY_LENGTHS:
        toks = jax.random.randint(jax.random.key(T), (1, T), 0, cfg.vocab)
        logits = {}
        for backend in ("auto", "ref"):
            rt = Runtime(zero_drop=True, kernel_backend=backend)
            t0 = time.perf_counter()
            exe = jax.jit(lambda p, t: apply_model(p, cfg, t, rt)[0]) \
                .lower(params, toks).compile()
            t1 = time.perf_counter()
            logits[backend] = jax.block_until_ready(exe(params, toks))
            t2 = time.perf_counter()
            text = exe.as_text()
            found = {k: has_kernel(text, k) for k in KERNELS}
            print(f"  parity T={T} {backend}: compile wall {t1 - t0:.3f} s, "
                  f"run wall {t2 - t1:.6f} s, tpu_custom_call {found}")
            if backend == "auto" and not all(found.values()):
                fail(f"parity T={T}: compiled program lacks a Pallas kernel")
            if backend == "ref" and any(found.values()):
                fail(f"parity T={T}: ref program holds a Pallas kernel")
        la, lr = logits["auto"], logits["ref"]
        if not (jnp.isfinite(la).all() and jnp.isfinite(lr).all()):
            fail(f"parity T={T}: non-finite logits")
        ratio = float(jnp.abs(la - lr).max() / jnp.abs(lr).max())
        agree = float((la.argmax(-1) == lr.argmax(-1)).mean())
        print(f"  parity T={T}: max|auto-ref|/max|ref| = {ratio:.6g} "
              f"(tolerance {PARITY_TOL}), argmax agreement {agree:.4f}")
        if not ratio <= PARITY_TOL:
            fail(f"parity T={T}: {ratio} > {PARITY_TOL}")


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); nothing run",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    os.environ.pop("REPRO_KERNEL_BACKEND", None)  # the phases set it

    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch import bench_serve
    cfg = get_config(ARCH)
    print(f"device_kind: {dev.device_kind}")
    print(f"device count: {len(jax.devices())}")
    print(f"jax {jax.__version__}")
    print(f"compile cache: {cache_dir}")

    print("== parity: auto vs ref logits at full width ==")
    parity(cfg, jax, jnp)
    gc.collect()

    print("== phase A: fits-in-memory, kernels auto ==")
    before = kernel_counts()
    t0 = time.perf_counter()
    res_a, mt_a = bench_serve.main(SERVE_ARGS)
    print(f"  phase A wall {time.perf_counter() - t0:.3f} s "
          f"(host clock until every token was on the host; compiles included)")
    toks_a = check_served("A", res_a, cfg.vocab)
    check_kernels_ran("A", before)
    del res_a, mt_a
    gc.collect()

    print("== phase B: offloaded, capacity 8, kernels auto ==")
    before = kernel_counts()
    t0 = time.perf_counter()
    res_b, mt_b = bench_serve.main(
        SERVE_ARGS + ["--offloaded", "--capacity", str(CAPACITY)])
    print(f"  phase B wall {time.perf_counter() - t0:.3f} s "
          f"(host clock until every token was on the host; compiles included)")
    toks_b = check_served("B", res_b, cfg.vocab)
    check_kernels_ran("B", before)
    accesses = mt_b.cache_hits + mt_b.cache_misses
    print(f"  phase B transfers {mt_b.transfers} ({mt_b.transfer_bytes} bytes), "
          f"prefetch {mt_b.prefetch_transfers}, hit rate "
          f"{mt_b.cache_hits}/{accesses} = {mt_b.hit_rate:.4f}")
    print(f"  phase B modeled (Eq. 3, not measured): serial "
          f"{mt_b.modeled_time_serial:.6f} s, overlapped "
          f"{mt_b.modeled_time_overlapped:.6f} s")
    if mt_b.transfers <= 0:
        fail("phase B made no expert transfers")

    same = total = 0
    for rid, ta in toks_a.items():
        tb = toks_b[rid]
        n = min(len(ta), len(tb))
        same += sum(a == b for a, b in zip(ta[:n], tb[:n]))
        total += n
    print(f"greedy tokens agreeing between phases A and B: {same}/{total} "
          f"= {same / total:.4f}")

    print(f"peak_bytes_in_use: {dev.memory_stats()['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
