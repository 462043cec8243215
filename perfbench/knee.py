#!/usr/bin/env python3
"""Sweep of offered load for a continuous-serving cell, on the chip.

    python3 perfbench/knee.py --workload granite.chat.fits --seed 5 \
        --burst 48 --seconds 40 --fractions 0.7,0.85,1.0

One process, one server, warmed once. First a burst: ``--burst``
requests of the cell's mix, all due at once; the rate at which they
complete is the capacity C. Then one open-loop window of the mix per
fraction of C, each ``--seconds`` long on the wall clock, served to its
end. One JSON line per step: the offered rate, the completion rate,
how long the queue took to drain after the window closed, and the
quantiles of the time to first token (also for the first and the last
third of the requests, which part ways once the queue grows).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, configure_jax, prepare_environment  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--burst", type=int, default=48)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--fractions", default="0.7,0.85,1.0")
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    prepare_environment(ROOT)
    jax = configure_jax(ROOT)
    if jax.devices()[0].platform != "tpu":
        print("knee.py: no TPU", file=sys.stderr)
        return 3
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from perfbench.lib import clock, readers, serve, stats, traffic
    from perfbench.lib.model import load_config
    from repro.serving import RequestQueue

    here = ROOT / "perfbench"
    cj, arch = load_config(here / "configs" / f"{cell['config']}.json",
                           here / "archs")
    t = traffic.load_traffic(here / "traffic" / f"{cell['traffic']}.json")
    if t["server"]["kind"] != "continuous":
        print("knee.py: sweeps continuous serving only", file=sys.stderr)
        return 2
    V = cj["vocab_size"]
    params = arch.make_weights(cj, args.seed)
    warm = serve._serve_requests(serve._warm_requests(t, V, args.seed))
    srv = serve.build_server(arch, cj, t, params, args.seed, warm)
    del params
    srv.run(RequestQueue(warm))
    print(json.dumps({"step": "setup", "seconds": time.perf_counter() - T_START}),
          flush=True)

    def serve_window(reqs, seconds):
        sreqs = serve._serve_requests(reqs)
        q = clock.WallQueue(sreqs, {r.rid: r.due for r in reqs}, seconds)
        st = clock.Stamps(q)
        t0 = q.start()
        srv.run(q, journal=st)
        rids = [r.rid for r in reqs]
        due = {rid: q.due_at(rid) for rid in rids}
        ttft = stats.ttfts_s(st.token_times, due)
        gaps = stats.token_gaps_s({rid: st.token_times.get(rid, []) for rid in rids})
        order = sorted(rids, key=lambda r: (due[r], r))
        third = max(1, len(order) // 3)
        end = max(st.retired.values()) - t0
        first = stats.ttfts_s(st.token_times, {r: due[r] for r in order[:third]})
        last = stats.ttfts_s(st.token_times, {r: due[r] for r in order[-third:]})
        return {"requests": len(rids), "finished": len(st.retired),
                "tokens": sum(len(v) for v in st.token_times.values()),
                "last_done_s": end, "drain_s": end - seconds,
                "completed_rps": len(st.retired) / end,
                "ttft_p50_ms": readers.ms_percentile(ttft, 50),
                "ttft_p90_ms": readers.ms_percentile(ttft, 90),
                "ttft_p90_ms_first_third": readers.ms_percentile(first, 90),
                "ttft_p90_ms_last_third": readers.ms_percentile(last, 90),
                "itl_p90_ms": readers.ms_percentile(gaps, 90),
                "itl_p95_ms": readers.ms_percentile(gaps, 95)}

    burst = traffic.build_requests({**t, "rate_rps": args.burst / args.seconds},
                                   V, args.seed + 1, args.seconds)
    for r in burst:
        r.due = 0.0
    out = serve_window(burst, 0.0)
    cap = out["completed_rps"]
    print(json.dumps({"step": "burst", **out}), flush=True)
    for i, frac in enumerate(float(f) for f in args.fractions.split(",")):
        rate = frac * cap
        reqs = traffic.build_requests({**t, "rate_rps": rate}, V,
                                      args.seed + 2 + i, args.seconds)
        out = serve_window(reqs, args.seconds)
        print(json.dumps({"step": "window", "fraction": frac, "rate_rps": rate,
                          "offered": len(reqs), **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
