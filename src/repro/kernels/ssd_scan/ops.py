"""Dispatching wrapper for the SSD chunked-scan kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..dispatch import record, resolve
from .kernel import ssd_scan as _ssd_kernel
from .ref import ssd_scan_ref


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_pallas(x, dt, A, Bm, Cm, init, chunk: int, interpret: bool):
    T = x.shape[1]
    cl = chunk
    while T % cl:
        cl //= 2
    return _ssd_kernel(x, dt, A, Bm, Cm, init, chunk=max(cl, 1),
                       interpret=interpret)


def ssd(x, dt, A, Bm, Cm, *, init=None, chunk: int = 128,
        interpret: Optional[bool] = None, use_ref: bool = False,
        backend: Optional[str] = None):
    """x (B,T,H,P), dt (B,T,H), A (H,), Bm/Cm (B,T,N) shared or
    (B,T,G,N) per-group, ``init`` (B,H,P,N) optional initial SSM state
    -> (y, final_state)."""
    choice = resolve("ssd_scan", backend or ("ref" if use_ref else "pallas"),
                     interpret=interpret)
    record("ssd_scan", choice)
    if not choice.use_pallas:
        return ssd_scan_ref(x, dt, A, Bm, Cm, init)
    return ssd_pallas(x, dt, A, Bm, Cm, init=init, chunk=chunk,
                      interpret=choice.interpret)


def ssd_pallas(x, dt, A, Bm, Cm, *, init=None, chunk: int = 128,
               interpret: bool = False):
    """The kernel, shapes as :func:`ssd`."""
    if Bm.ndim == 3:  # shared across heads == one group
        Bm = Bm[:, :, None]
        Cm = Cm[:, :, None]
    B, T, H, P = x.shape
    N = Bm.shape[-1]
    if init is None:
        init = jnp.zeros((B, H, P, N), jnp.float32)
    return _ssd_pallas(x, dt, A, Bm, Cm, init, chunk, interpret)
