"""Whole prefill on the device: model FLOPs of each traced prompt (two
per active parameter per prompt token, the head for the last position
only, which is all that prefill returns; the architecture's
``param_count``) over the device-busy time inside the traced
``serve.prefill`` spans at the chip's bf16 peak, in %."""
from perfbench.lib import readers, work


def read(ctx):
    pc = ctx.arch.param_count(ctx.cj)
    head = pc["head"]

    def flops(s):
        T = s.args.get("prompt_len", 0)
        return work.model_flops(T, pc["active"] - head) + work.model_flops(1, head)

    return readers.step_mfu(ctx, "serve.prefill", flops)
