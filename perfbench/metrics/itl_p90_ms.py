"""Every gap between two consecutive tokens of every request attempted,
90th percentile (continuous serving, where tokens stream)."""
from perfbench.lib import readers, stats


def read(ctx):
    if ctx.kind != "continuous":
        return None
    times = {rid: ctx.stamps.token_times.get(rid, []) for rid in ctx.attempted}
    return readers.ms_percentile(stats.token_gaps_s(times), 90)
