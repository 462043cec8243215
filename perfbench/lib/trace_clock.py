"""Program spans on the device trace's clock.

The program stamps each span's start on the profiler's clock as well as
on ``perf_counter`` (``SpanRecord.t0_ns``: ``time.time_ns()``, the
``CLOCK_REALTIME`` that the profiler stamps host events with). The
profiler stores every host and device event in ns after the session's
start, which its "Task Environment" plane records as
``profile_start_time``. So a span starts in the trace at
``t0_ns - profile_start_time``, with no anchor taken by the benchmark.

A trace without its start time, and spans without ``t0_ns`` (an older
program), place nothing, and the readers that use this clock find
nothing.
"""
from __future__ import annotations

import glob
import sys
from pathlib import Path
from typing import List, Optional, Tuple

ENV_PLANE = "Task Environment"
START_STAT = "profile_start_time"
# where run_cell writes a cell's trace (its work_dir is perfbench/.cache)
TRACE_ROOT = Path(__file__).resolve().parents[1] / ".cache" / "trace"


def profile_start_ns(planes) -> Optional[int]:
    """The session's start (``CLOCK_REALTIME`` ns) from the trace's
    "Task Environment" plane, or None where it is missing."""
    for pl in planes:
        if pl.name == ENV_PLANE:
            for key, value in pl.stats:
                if key == START_STAT:
                    return int(value)
    return None


def _start_of(ctx) -> Optional[int]:
    """``profile_start_ns`` of the cell's trace file, read once per
    trace (a made-up trace may carry it as ``profile_start_ns``)."""
    tr = ctx.trace
    if not hasattr(tr, "profile_start_ns"):
        trace_dir = TRACE_ROOT / ctx.cell["name"]
        files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
        start = None
        if files:
            from jax.profiler import ProfileData

            start = profile_start_ns(ProfileData.from_file(files[-1]).planes)
        if start is None:
            print(f"bench: no profile_start_time under {trace_dir}; "
                  "spans are not placed on the trace's clock", file=sys.stderr)
        tr.profile_start_ns = start
    return tr.profile_start_ns


def placed(ctx, name: str) -> List[Tuple[object, float, float]]:
    """Recorded spans called ``name`` that lie wholly inside the traced
    window, each with its interval on the trace's clock (ns), placed by
    ``t0_ns``."""
    if ctx.trace is None:
        return []
    start = _start_of(ctx)
    if start is None:
        return []
    lo, hi = ctx.trace.window
    out = []
    for s in ctx.spans:
        if s.name != name or not getattr(s, "t0_ns", 0):
            continue
        a = float(s.t0_ns - start)
        b = a + s.dur * 1e9
        if lo <= a and b <= hi:
            out.append((s, a, b))
    return out
