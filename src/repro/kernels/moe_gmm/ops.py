"""Dispatching wrapper for the grouped expert matmul (auto tile selection
+ fallback to the oracle for degenerate shapes)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..dispatch import KernelChoice, pick_tile, record, resolve
from .kernel import gmm as _gmm_kernel
from .ref import gmm_ref


@functools.partial(jax.jit, static_argnames=("interpret",))
def gmm_pallas(a, b, group_sizes=None, interpret: bool = False):
    """The kernel with TPU-legal tiles: rows are the whole of M up to
    128, else 128-row tiles over M padded by the kernel (the token
    count M is arbitrary, so a divisor of it may be unaligned); N and K
    take 128-lane multiples that divide them."""
    E, M, K = a.shape
    N = b.shape[-1]
    if group_sizes is not None:
        group_sizes = jnp.asarray(group_sizes, jnp.int32)
    return _gmm_kernel(a, b, bm=min(M, 128), bn=pick_tile(N, 128, 128),
                       bk=pick_tile(K, 512, 128), interpret=interpret,
                       group_sizes=group_sizes)


def gmm(a, b, interpret: Optional[bool] = None, use_ref: bool = False,
        backend: Optional[str] = None, group_sizes=None):
    """a (E, M, K) @ b (E, K, N) -> (E, M, N).

    ``backend``: "ref" | "pallas" | "auto" (None keeps the legacy
    ``use_ref``/``interpret`` semantics, resolving "pallas").

    ``group_sizes`` (E,): valid row counts per group. Rows past the count
    must already be zero in ``a`` (slot-dispatch buffers guarantee this);
    the Pallas path then skips M-tiles of empty/short groups. The
    reference path is oblivious (zero rows contribute zeros)."""
    E, M, K = a.shape
    N = b.shape[-1]
    choice = resolve("moe_gmm", backend or ("ref" if use_ref else "pallas"),
                     interpret=interpret)
    if M * N * K == 0:
        choice = KernelChoice("ref", False)
    record("moe_gmm", choice)
    if not choice.use_pallas:
        return gmm_ref(a, b)
    return gmm_pallas(a, b, group_sizes, choice.interpret)
