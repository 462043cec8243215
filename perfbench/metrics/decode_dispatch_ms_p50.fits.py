"""Model step: median duration of the program's
``serve.decode_step.dispatch`` spans in the window (host work of one
decode step, from its start until the sampled tokens are read back)."""
from perfbench.lib import readers


def read(ctx):
    return readers.ms_percentile(
        [s.dur for s in readers.program_spans(ctx, "serve.decode_step.dispatch")], 50)
