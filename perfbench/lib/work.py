"""Operations and bytes that the algorithm needs, from shapes alone.

Counts are at the configuration's dtype (float32, 4 bytes) and follow
the algorithm, not an implementation: a grouped expert matmul is
charged for the rows routed to it and the weights of the experts it is
given, never for padded rows or tiles. So a kernel that pads or skips
work moves its roofline share, not its work.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

F32 = 4


class Work(NamedTuple):
    flops: float
    bytes: float

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)

    def min_seconds(self, peak_flops: float, peak_bytes_s: float) -> float:
        return max(self.flops / peak_flops, self.bytes / peak_bytes_s)

    def bound(self, peak_flops: float, peak_bytes_s: float) -> str:
        return ("compute" if self.flops / peak_flops >= self.bytes / peak_bytes_s
                else "memory")


ZERO = Work(0.0, 0.0)


def gmm(rows: int, k: int, n: int, experts: int) -> Work:
    """One grouped matmul: ``rows`` routed (token, expert) rows of width
    ``k`` times the (k, n) weights of each of ``experts`` experts."""
    return Work(2.0 * rows * k * n, F32 * (experts * k * n + rows * (k + n)))


def moe_gmms(tokens: int, d: int, f: int, experts: int, top_k: int) -> Work:
    """The three grouped matmuls of one MoE layer (gate, up, down) of
    width ``d`` and expert width ``f``, for ``tokens`` tokens each routed
    to ``top_k`` of the ``experts`` experts held."""
    rows = tokens * top_k
    return gmm(rows, d, f, experts) + gmm(rows, d, f, experts) + gmm(rows, f, d, experts)


def causal_attention(tokens: int, hq: int, hkv: int, hd: int,
                     window: Optional[int] = None) -> Work:
    """One layer's causal self-attention over a prompt of ``tokens``, with
    ``hq`` query and ``hkv`` key/value heads of ``hd``: scores and the
    weighted sum for the keys each query sees, reading q, k and v once
    and writing the output once. Query t sees t + 1 keys, or
    ``min(t + 1, window)`` in a sliding window."""
    if window is None or window >= tokens:
        pairs = tokens * (tokens + 1) / 2
    else:
        pairs = window * (window + 1) / 2 + (tokens - window) * window
    return Work(4.0 * hq * hd * pairs, F32 * tokens * hd * (2 * hq + 2 * hkv))


def times(n: int, w: Work) -> Work:
    """``w`` done ``n`` times, as in ``n`` identical layers."""
    return Work(n * w.flops, n * w.bytes)


def model_flops(tokens: int, active_params: int) -> float:
    """Model FLOPs: two per active parameter per token."""
    return 2.0 * active_params * tokens
