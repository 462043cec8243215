#!/usr/bin/env python3
"""Run one benchmark cell on the chip this machine holds.

    python3 perfbench/run.py --workload granite.chat.fits --seed 7 \
        --seconds 40 --trace 0

Reads ``BENCHMARK.json`` at the root of the checkout, finds the cell and
its configuration, architecture, traffic and metric files by name, runs
the served path for ``--seconds`` on the wall clock after set-up,
compares what it served with the plain reference, and prints one JSON
object as the last line of stdout. ``--trace 1`` reports the per-layer metrics (from the
program's spans and counters and a device trace of part of the window)
instead of the end-to-end ones.

Exits non-zero and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def applies(metric: dict, cell: dict, e2e_names) -> bool:
    """A metric is read in a cell its ``workloads`` lists; without the
    key, in every cell that reports the end-to-end metric it moves (or,
    for an end-to-end metric, in every cell)."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def prepare_environment(root: Path) -> None:
    """Before JAX is imported: no program switch from the environment,
    and the compile cache at a fixed path inside the checkout."""
    for k in list(os.environ):
        if k.startswith("REPRO_"):
            del os.environ[k]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def configure_jax(root: Path):
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"run.py: no workload {args.workload!r}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    prepare_environment(ROOT)
    jax = configure_jax(ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"run.py: needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform!r} device(s)", file=sys.stderr)
        return 3
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from perfbench.lib.model import load_config
    from perfbench.lib.serve import run_cell
    from perfbench.lib.traffic import load_traffic

    e2e = [m for m in bench["end_to_end"] if applies(m, cell, ())]
    e2e_names = {m["name"] for m in e2e}
    wanted = ([m for m in bench["per_layer"] if applies(m, cell, e2e_names)]
              if args.trace else e2e)
    here = ROOT / "perfbench"
    cj, arch = load_config(here / "configs" / f"{cell['config']}.json",
                           here / "archs")
    out = run_cell(cell, cj, arch,
                   load_traffic(here / "traffic" / f"{cell['traffic']}.json"),
                   wanted, here / "metrics", here / ".cache", args.seed,
                   args.seconds, bool(args.trace), T_START)
    print_result(out, devices, args.trace)
    return 0


def print_result(out: dict, devices, traced: bool) -> None:
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": out["memory_peak_bytes"]}
    if traced:
        device["busy_s"] = out["busy_s"]
        device["window_s"] = out["window_s"]
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"], "device": device,
            "counters": out["counters"]}
    if traced:
        line["breakdown"] = out["breakdown"]
    line["checks"] = out["checks"]
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    sys.exit(main())
