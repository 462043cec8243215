"""Kernel ``mla_decode``: the least time the chip could take for the
latent attention of the traced decode steps (the architecture's
``kernel_work`` for the step span's ``cached_positions``: the latent
positions that the active rows read, in every layer that runs the
kernel) over the device time of the ``mla_decode`` operations inside
them, in %. None where no decode-step span records ``cached_positions``
(a program without it) or the kernel did not run."""
from perfbench.lib import readers


def read(ctx):
    sw = [(a, b, ctx.arch.kernel_work(ctx.cj, "mla_decode", s.args["cached_positions"]))
          for s, a, b in readers.traced_spans(ctx, "serve.decode_step")
          if "cached_positions" in s.args]
    return readers.roofline_share(ctx, "mla_decode", sw)
