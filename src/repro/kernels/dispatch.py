"""Kernel-dispatch subsystem: one place that decides, per op, whether the
hot path runs the Pallas kernel or the pure-jnp reference, and in which
execution mode.

  * platform autodetection — compiled Pallas on TPU, ``interpret=True``
    everywhere else, so callers never pass ``interpret=`` by hand.

  * a per-op backend registry — every op resolves a spec string
    ``"ref" | "pallas" | "auto"`` (optionally per-op:
    ``"ref,moe_gmm=pallas"``) into a concrete :class:`KernelChoice`.
    ``auto`` means "run the Pallas kernel wherever it supports the
    shapes: compiled on TPU, interpret elsewhere". The environment
    variable ``REPRO_KERNEL_BACKEND`` overrides whatever the caller
    (usually ``Runtime.kernel_backend``) configured.

  * the ``kernel_dispatch_total`` counter — :func:`record` books the
    backend a call site actually traced, after any shape bail-out, so
    a kernel that fell back to the reference is never counted as run.
"""
from __future__ import annotations

import os
from typing import NamedTuple, Optional

import jax

# Every kernel family registered with the dispatcher. Consumers ask for
# one of these names; unknown names are an error so typos fail loudly.
OPS = ("flash_attn", "int4_matmul", "mla_decode", "moe_gmm", "ssd_scan")

BACKENDS = ("ref", "pallas", "auto")

ENV_VAR = "REPRO_KERNEL_BACKEND"


def pick_tile(v: int, pref: int, align: int) -> int:
    """Largest divisor of ``v`` that is <= ``pref`` and a multiple of
    ``align``; ``v`` itself when ``v <= pref`` or no such divisor exists
    (a block as long as the whole dim is always legal on TPU, which
    otherwise wants 8-row and 128-lane multiples)."""
    if v <= pref:
        return v
    t = pref - pref % align
    while t >= align:
        if v % t == 0:
            return t
        t -= align
    return v


# ---------------------------------------------------------------------------
# Platform autodetection
# ---------------------------------------------------------------------------


def default_platform() -> str:
    """'tpu' | 'gpu' | 'cpu' — the platform kernels would execute on."""
    return jax.default_backend()


def interpret_default(platform: Optional[str] = None) -> bool:
    """Pallas TPU kernels compile only on TPU; everywhere else they run
    under the (slow but exact) interpreter."""
    return (platform or default_platform()) != "tpu"


# ---------------------------------------------------------------------------
# Per-op backend resolution
# ---------------------------------------------------------------------------


class KernelChoice(NamedTuple):
    backend: str  # "ref" | "pallas"
    interpret: bool  # meaningful only when backend == "pallas"

    @property
    def use_pallas(self) -> bool:
        return self.backend == "pallas"


def parse_spec(spec: Optional[str]) -> dict:
    """``"auto"`` / ``"ref,moe_gmm=pallas"`` -> {"*": ..., op: ...}.

    A bare backend name sets the global default ("*"); ``op=backend``
    entries override per op. Only explicitly-named keys appear in the
    result (callers supply the "ref" fallback). Whitespace-tolerant.
    Unknown ops/backends raise.
    """
    out: dict = {}
    if not spec:
        return out
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        if "=" in part:
            op, _, backend = part.partition("=")
            op, backend = op.strip(), backend.strip()
            if op not in OPS:
                raise ValueError(f"unknown kernel op {op!r} (known: {OPS})")
        else:
            op, backend = "*", part
        if backend not in BACKENDS:
            raise ValueError(
                f"unknown kernel backend {backend!r} (known: {BACKENDS})"
            )
        out[op] = backend
    return out


def op_backend(op: str, spec: Optional[str]) -> str:
    """The configured backend ("ref"|"pallas"|"auto") for ``op`` under
    ``spec``, after applying the ``REPRO_KERNEL_BACKEND`` env override.

    Env entries win per key: a per-op-only override (``flash_attn=ref``)
    adjusts that op and leaves the caller's spec in force for the rest;
    a bare backend name overrides the global default."""
    if op not in OPS:
        raise ValueError(f"unknown kernel op {op!r} (known: {OPS})")
    table = parse_spec(spec)
    env = os.environ.get(ENV_VAR)
    if env:
        table.update(parse_spec(env))
    return table.get(op, table.get("*", "ref"))


def resolve(
    op: str,
    spec: Optional[str] = None,
    *,
    interpret: Optional[bool] = None,
    platform: Optional[str] = None,
) -> KernelChoice:
    """Resolve (op, backend spec) -> concrete :class:`KernelChoice`.

    ``interpret=None`` autodetects from the platform; an explicit bool is
    honoured (tests force interpret=True regardless of platform).
    """
    backend = op_backend(op, spec)
    if backend == "auto":
        backend = "pallas"
    if backend == "ref":
        return KernelChoice("ref", False)
    if interpret is None:
        interpret = interpret_default(platform)
    return KernelChoice("pallas", bool(interpret))


def record(op: str, choice: KernelChoice) -> None:
    """Book the backend a call site runs, once per trace and after any
    fallback: a labeled counter (always) plus a trace instant (when
    tracing is enabled)."""
    from ..obs.registry import REGISTRY
    from ..obs.trace import get_tracer

    REGISTRY.counter(
        "kernel_dispatch_total", "kernel backend traced per call site",
        op=op, backend=choice.backend, interpret=choice.interpret,
    ).inc()
    tr = get_tracer()
    if tr.enabled:
        tr.instant("kernel.dispatch", op=op, backend=choice.backend,
                   interpret=choice.interpret)
