"""Device: median over the prefills wholly inside the traced window of
the time no operation ran on the device within the ``serve.prefill``
span, in ms. Spans are placed on the trace's clock by their ``t0_ns``
(``perfbench/lib/trace_clock.py``)."""
from perfbench.lib import stats, trace_clock


def read(ctx):
    if ctx.trace is None:
        return None
    idle = [(b - a - ctx.trace.busy_in(a, b)) * 1e-6
            for s, a, b in trace_clock.placed(ctx, "serve.prefill")
            if s.args.get("rid") in ctx.due_abs and s.t0 >= ctx.t0]
    return stats.percentile(idle, 50)
