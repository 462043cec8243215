"""Whole decode step on the device: model FLOPs (two per active
parameter, the architecture's ``param_count``) of the active rows'
tokens, over the device-busy time inside the traced
``serve.decode_step`` spans at the chip's bf16 peak, in %."""
from perfbench.lib import readers, work


def read(ctx):
    active = ctx.arch.param_count(ctx.cj)["active"]
    return readers.step_mfu(ctx, "serve.decode_step",
                            lambda s: work.model_flops(s.args.get("active", 0), active))
