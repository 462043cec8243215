"""Kernel ``flash_attn``: the least time the chip could take for the
causal attention of the traced prefills (the architecture's
``kernel_work``: every layer that runs the kernel, at its own window)
over the device time of the ``flash_attn`` operations inside them, in %."""
from perfbench.lib import readers


def read(ctx):
    sw = [(a, b, ctx.arch.kernel_work(ctx.cj, "flash_attn", s.args["prompt_len"]))
          for s, a, b in readers.traced_spans(ctx, "serve.prefill")]
    return readers.roofline_share(ctx, "flash_attn", sw)
