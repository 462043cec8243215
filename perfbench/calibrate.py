#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 perfbench/calibrate.py --workload granite.chat.fits \
        --seeds 11,12,13 --seconds 25 [--rate 0.9] [--controls int8,fp8]

For each seed, in one process: one run of the cell as ``run.py`` makes
it (at the mix's rate, or at ``--rate``), then the comparison with the
reference on the same sample of served requests. The program is judged
as a run judges it, and so is each control: the reference rounded to a
lower precision (``reference.CONTROLS``) and put in the program's place,
at each position of the same prompts and served tokens taking the token
that it puts first. One JSON line per seed, with each side's number,
its limit and whether it came out correct. The benchmark's own runs
never run a control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, applies, configure_jax, prepare_environment  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--controls", default="int8,fp8")
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    prepare_environment(ROOT)
    jax = configure_jax(ROOT)
    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: no TPU", file=sys.stderr)
        return 3
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from perfbench.lib.model import load_config
    from perfbench.lib.serve import run_cell
    from perfbench.lib.traffic import load_traffic

    here = ROOT / "perfbench"
    cj, arch = load_config(here / "configs" / f"{cell['config']}.json",
                           here / "archs")
    t = load_traffic(here / "traffic" / f"{cell['traffic']}.json")
    if args.rate is not None:
        t = {**t, "rate_rps": args.rate}
    controls = [q for q in args.controls.split(",") if q]
    e2e = [m for m in bench["end_to_end"] if applies(m, cell, ())]
    t0 = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run_cell(cell, cj, arch, t, e2e, here / "metrics", here / ".cache", seed,
                       args.seconds, False, t0, controls=controls)
        cmp = out["compared"]
        print(json.dumps({
            "seed": seed, "rate_rps": t.get("rate_rps"), "correct": out["correct"],
            "attempted": out["attempted"], "failed": out["failed"],
            "tokens": cmp["tokens"], "checks": out["checks"],
            "controls": {q: {"correct": v["ok"], "checks": v["checks"]}
                         for q, v in cmp["controls"].items()},
            "gaps": cmp["gaps"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "counters": out["counters"]}), flush=True)
        del out
        gc.collect()
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
