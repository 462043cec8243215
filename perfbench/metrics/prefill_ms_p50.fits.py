"""Model step: median duration of the program's ``serve.prefill`` spans
in the window (one prompt's prefill, ended by a host sync)."""
from perfbench.lib import readers


def read(ctx):
    return readers.ms_percentile(
        [s.dur for s in readers.program_spans(ctx, "serve.prefill")
         if s.args.get("rid") in ctx.due_abs], 50)
