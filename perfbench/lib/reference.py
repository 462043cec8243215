"""The comparison that decides ``correct``, and the pieces that every
architecture's plain reference shares.

Each architecture module under ``perfbench/archs/`` writes its forward
pass from the configuration file alone, in straightforward
``jax.numpy`` with every matmul in float32 at HIGHEST precision,
importing nothing of the program and taking only the benchmark's own
weights. The helpers here are its parts: RMSNorm in the (1 + w) form,
rotary positions, and the rounding of a lower-precision control.

The comparison: for each served token, the gap by which the
reference's logit for it lies below the reference's best logit at that
position, given the prompt and the tokens served before it. Greedy
serving of the exact model has gap 0 everywhere; rounding moves a
token only where two logits nearly tie, so a gap is small and most are
0. Their mean over the sample grows with the square of the rounding
error (how often a tie flips times how far), which is what sets a
lower precision apart from the program's.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import numpy as np

BUCKET = 512  # sequences pad to a multiple of this: few shapes, few compiles


def rms(x, w, eps):
    import jax.numpy as jnp

    dt = x.dtype
    x32 = x.astype(jnp.float32)
    y = x32 * (1.0 / jnp.sqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps))
    return (y * (1.0 + w.astype(jnp.float32))).astype(dt)


def rope(x, theta):
    """Rotate halves: x (T, H, hd) at positions 0..T-1, angles in f32."""
    import jax.numpy as jnp

    T, _, hd = x.shape
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * jnp.asarray(inv)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1).astype(x.dtype)


CONTROLS = ("int8", "fp8")  # the precisions a control may round to


def round_to(x, axis, kind):
    """Symmetric rounding to ``kind`` along ``axis``, one scale per slice
    (the slice's largest magnitude maps to the format's largest value),
    returned in float32."""
    import jax.numpy as jnp

    top = 127.0 if kind == "int8" else 448.0
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    if kind == "int8":
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def rounding(quant: str):
    """``(act, wq)`` for a control named ``quant`` ("" for none): ``act``
    rounds a projection's input per row, ``wq`` a matmul weight per
    output channel, each to that format and back to float32."""
    if not quant:
        return (lambda a: a), (lambda w: w)
    return (lambda a: round_to(a, -1, quant)), (lambda w: round_to(w, -2, quant))


class Reference:
    """Jitted passes of an architecture's ``forward``, one compile per
    padded length bucket."""

    def __init__(self, cj: dict, forward: Callable):
        import jax

        self.cj = cj
        self._fwd = {q: jax.jit(lambda p, t, q=q: forward(cj, p, t, q))
                     for q in ("",) + CONTROLS}

    def logits(self, params, seq: np.ndarray, quant: str = ""):
        """(len(seq), V) logits; the sequence is padded at its end to a
        bucket, which causal attention keeps from earlier positions."""
        import jax.numpy as jnp

        n = len(seq)
        padded = np.zeros(-(-n // BUCKET) * BUCKET, np.int32)
        padded[:n] = seq
        return self._fwd[quant](params, jnp.asarray(padded))[:n]


def served_gaps(ref: Reference, params, prompt: np.ndarray,
                served: Sequence[int], controls: Sequence[str] = ()
                ) -> Dict[str, np.ndarray]:
    """Per served position: the reference's best logit minus its logit
    for the served token (``program``), and, for each control named in
    ``controls``, the same gap for the token that the reference rounded
    to that precision puts first (the control in the program's place)."""
    import jax.numpy as jnp

    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    at = np.arange(len(prompt) - 1, len(seq))  # positions predicting served
    lg = ref.logits(params, seq)[at]
    best = jnp.max(lg, -1)
    rows = jnp.arange(len(at))
    out = {"program": np.asarray(best - lg[rows, served])}
    for q in controls:
        pick = jnp.argmax(ref.logits(params, seq, q)[at], -1)
        out[q] = np.asarray(best - lg[rows, pick])
    return out


def pick_sample(finished: Dict[int, tuple], seed: int, min_tokens: int,
                max_requests: int) -> List[int]:
    """Request ids to compare: the one with the most served tokens, then
    others in an order drawn from the seed, until ``min_tokens`` served
    tokens or ``max_requests`` requests. ``finished`` maps rid to
    (prompt, served tokens)."""
    if not finished:
        return []
    rids = sorted(finished)
    longest = max(rids, key=lambda r: (len(finished[r][1]), -r))
    rest = [r for r in np.random.default_rng([seed, 0xC0]).permutation(rids)
            if r != longest]
    out, n = [longest], len(finished[longest][1])
    for r in rest:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(int(r))
        n += len(finished[r][1])
    return out
