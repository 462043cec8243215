"""Kernel ``moe_gmm``: the least time the chip could take for the
grouped matmuls of the traced steps (routed rows and the weights of
the experts each call is given, summed over the layers that run it by
the architecture's ``kernel_work``) over the device time of the
``moe_gmm`` operations, in %. Prefill spans route their prompt tokens,
decode spans their active rows."""
from perfbench.lib import readers


def read(ctx):
    work_of = lambda n: ctx.arch.kernel_work(ctx.cj, "moe_gmm", n)
    sw = [(a, b, work_of(s.args["prompt_len"]))
          for s, a, b in readers.traced_spans(ctx, "serve.prefill")]
    sw += [(a, b, work_of(s.args["active"]))
           for s, a, b in readers.traced_spans(ctx, "serve.decode_step")]
    return readers.roofline_share(ctx, "moe_gmm", sw)
