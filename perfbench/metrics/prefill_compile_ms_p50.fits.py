"""Model step: median over the window's prefills of the time JAX spent
tracing, lowering, compiling and reading the persistent compile cache
(span args ``jax_trace_s``, ``jax_lower_s``, ``jax_compile_s``,
``jax_cache_s``) on ``serve.prefill`` and its ``serve.prefill.*``
children. A prefill without children is from a program that does not
charge compiles to spans, and is not read."""
from perfbench.lib import readers

KEYS = ("jax_trace_s", "jax_lower_s", "jax_compile_s", "jax_cache_s")


def read(ctx):
    kids = [s for s in ctx.spans if s.name.startswith("serve.prefill.")]
    per = []
    for p in readers.program_spans(ctx, "serve.prefill"):
        if p.args.get("rid") not in ctx.due_abs:
            continue
        inside = [c for c in kids if c.tid == p.tid and c.depth == p.depth + 1
                  and p.t0 <= c.t0 and c.t0 + c.dur <= p.t0 + p.dur]
        if inside:
            per.append(sum(s.args.get(k, 0.0) for s in [p] + inside for k in KEYS))
    return readers.ms_percentile(per, 50)
