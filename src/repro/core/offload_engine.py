"""Offloaded MoE inference engine (paper Sec 3.2, Eq. 3).

TPU adaptation of the paper's VRAM/DRAM split (DESIGN.md Sec 2):

  * resident pool  — per-layer expert cache in accelerator memory
                     (optionally HQQ-INT4 quantized, Sec 3.2 / D.5)
  * offload pool   — host memory (``pinned_host`` on real TPU; numpy here)
  * miss           — host->device DMA, counted and costed by Eq. 3

The engine iterates blocks in Python (per-layer control is the point:
the cache manager must interpose *between* the router and the expert
computation) and its outputs match ``model.decode_step`` bit-for-bit
when the cache is large enough.

Two implementations share the cache/metrics substrate:

``impl="slab"`` (default) — the hot path. Residents live in per-layer
*slabs*: stacked device buffers ``(C, d, f)`` (fp32, or the INT4
``matmul_layout`` triplet under a Pallas backend) updated in place via
a donated ``.at[slot].set`` so a fetch never reallocates or retraces.
Each MoE layer runs two jitted calls: attention + router (one trace per
block kind), then — after the vectorized host-side cache accounting
(``LayerExpertCache.access_batch``) syncs the slab — one grouped
``moe_gmm`` over all experts at once (tokens sorted into per-slot
buffers; LoRA rides as a batched low-rank term).

``impl="dict"`` — the pre-rewrite engine: per-expert dict-of-arrays
residents, per-token Python cache accounting, eager per-expert matmuls.
Kept as the reproduction-scale baseline ``benchmarks/offload_bench.py``
measures the slab engine against.

Beyond the serial Eq. 3 clock, :class:`EngineMetrics` records per-step,
per-MoE-layer transfer events, from which an *overlapped* clock models
cross-layer prefetch hiding: layer ``l``'s router output issues layer
``l+1``'s fetches, so a step costs
``t_tx[0] + sum_l max(t_compute_l, t_tx[l+1])`` (FloE-style pipeline;
always <= the serial clock).
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import BlockSpec, MLASpec, ModelConfig
from ..models.blocks import apply_block_full
from ..models.common import rms_norm, silu
from ..models.mlp import apply_mlp
from ..models.model import compute_logits, embed_tokens
from ..models.moe import (Dispatch, combine_tokens, dispatch_tokens,
                          router_probs, top_k_route)
from ..models.runtime import Runtime
from ..obs.trace import get_tracer
from ..faults import FetchPolicy, get_fault_plan
from ..kernels.dispatch import record
from .expert_cache import ModelExpertCache
from .little_expert import LittleExpertBank
from .quant import (QTensor, dequantize_linear, matmul_layout, qmatmul,
                    quant_bytes, quantize_linear)


def _obs_sync(x):
    """Fence async dispatch at span boundaries when tracing, so spans
    measure the work they wrap instead of whatever the scheduler
    happened to drain later; a no-op (async preserved) otherwise."""
    if get_tracer().enabled:
        jax.block_until_ready(x)
    return x

def _quiet_donation(fn):
    """Slab updates donate the old buffer; CPU backends fall back to
    copying and warn — the donation is still correct (and free on TPU).
    Suppress that one warning around OUR donated calls only, instead of
    mutating the process-global warning filters at import time."""
    def wrapped(*args, **kwargs):
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return fn(*args, **kwargs)
    return wrapped


# ---------------------------------------------------------------------------
# Hardware profile (v5e target; see DESIGN.md Sec 2 for constants)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class HardwareProfile:
    """Eq.-3 constants. The defaults are the documented target, one TPU
    v5e chip (peaks below); the link, latency, host and efficiency terms
    are assumptions, not measurements."""
    name: str = "tpu-v5e"
    peak_flops: float = 197e12  # bf16
    hbm_bw: float = 819e9
    host_link_bw: float = 32e9  # host<->device DMA (PCIe-gen4-like)
    transfer_latency: float = 30e-6  # per-transfer fixed cost
    host_flops: float = 2e12  # host-side expert execution (Fiddler mode)
    mfu: float = 0.4  # assumed compute efficiency for Eq. 3


PCIE5_H100 = HardwareProfile(
    name="h100-pcie5", peak_flops=989e12, hbm_bw=3350e9, host_link_bw=64e9
)

# Published per-chip peaks, keyed by jax's ``device_kind``. TPU v5e:
# 197 TFLOP/s bf16 and 819 GB/s HBM (Google Cloud documentation, "TPU v5e").
TPU_PEAKS = {
    "TPU v5 lite": {"name": "tpu-v5e", "peak_flops": 197e12, "hbm_bw": 819e9},
}


def hardware_profile(device=None) -> HardwareProfile:
    """The Eq.-3 profile for ``device`` (default: the first JAX device).
    A TPU takes its peaks from :data:`TPU_PEAKS` by ``device_kind``, and
    a kind not in the table is an error, never a guess. Any other
    platform gets the documented v5e target profile."""
    device = device or jax.devices()[0]
    if device.platform != "tpu":
        return HardwareProfile()
    try:
        return HardwareProfile(**TPU_PEAKS[device.device_kind])
    except KeyError:
        raise ValueError(
            f"no Eq.-3 peaks for TPU kind {device.device_kind!r}; add its "
            f"published numbers to TPU_PEAKS") from None


# ---------------------------------------------------------------------------
# Metrics: serial Eq. 3 clock + overlapped prefetch clock
# ---------------------------------------------------------------------------


@dataclass
class EngineMetrics:
    decode_tokens: int = 0
    transfers: int = 0
    transfer_bytes: int = 0
    prefetch_transfers: int = 0
    prefetch_bytes: int = 0
    host_executed: int = 0
    compute_flops: float = 0.0
    wall_time: float = 0.0
    prefill_wall_time: float = 0.0  # host seconds spent in prefill steps
    host_time: float = 0.0  # modeled host-side expert execution (set in generate)
    # resilience accounting (PR 8): modeled seconds lost to injected
    # transfer spikes, failed fetch attempts and retry backoff; counts of
    # retries, failed attempts and little-expert substitutions
    fault_delay_s: float = 0.0
    fetch_retries: int = 0
    fetch_failures: int = 0
    degraded_uses: int = 0
    # per engine step (prefill counts as one, then one per decode step):
    # total flops and per-MoE-layer demand-transfer counts/bytes — the
    # event records behind the overlapped clock — plus that step's
    # injected fault delay (charged serially on both clocks: a stalled
    # retry blocks the wave either way)
    step_flops: List[float] = field(default_factory=list)
    step_tx: List[np.ndarray] = field(default_factory=list)
    step_tx_bytes: List[np.ndarray] = field(default_factory=list)
    step_fault_delay: List[float] = field(default_factory=list)
    # overlapped-clock seconds of records dropped via drop_step_records
    # (keeps modeled_time_overlapped cumulative after trimming)
    overlapped_dropped: float = 0.0
    # cumulative per-MoE-layer transfer totals (moe_idx -> count/bytes).
    # Unlike the per-step event records these survive drop_step_records,
    # so obs.reconcile can build its per-layer table for long-lived
    # engines (the wave server drops records per request)
    layer_tx: Dict[int, int] = field(default_factory=dict)
    layer_tx_bytes: Dict[int, int] = field(default_factory=dict)
    layer_prefetch_tx: Dict[int, int] = field(default_factory=dict)
    layer_prefetch_bytes: Dict[int, int] = field(default_factory=dict)

    # -- recording ---------------------------------------------------------
    def begin_step(self, n_moe_layers: int) -> None:
        self.step_flops.append(0.0)
        self.step_tx.append(np.zeros(n_moe_layers, np.int64))
        self.step_tx_bytes.append(np.zeros(n_moe_layers, np.int64))
        self.step_fault_delay.append(0.0)

    def add_fault_delay(self, seconds: float) -> None:
        self.fault_delay_s += seconds
        if self.step_fault_delay:
            self.step_fault_delay[-1] += seconds

    def add_flops(self, flops: float) -> None:
        self.compute_flops += flops
        if self.step_flops:
            self.step_flops[-1] += flops

    def add_demand_transfers(self, moe_idx: int, n: int, nbytes: int) -> None:
        self.transfers += n
        self.transfer_bytes += nbytes
        self.layer_tx[moe_idx] = self.layer_tx.get(moe_idx, 0) + n
        self.layer_tx_bytes[moe_idx] = (
            self.layer_tx_bytes.get(moe_idx, 0) + nbytes)
        if self.step_tx:
            self.step_tx[-1][moe_idx] += n
            self.step_tx_bytes[-1][moe_idx] += nbytes

    def add_prefetch_transfers(self, moe_idx: int, n: int, nbytes: int) -> None:
        """Proactive (predictor-driven) transfers: real link traffic, but
        charged outside the demand clocks — tracked per layer for the
        reconciliation table."""
        self.prefetch_transfers += n
        self.prefetch_bytes += nbytes
        self.layer_prefetch_tx[moe_idx] = (
            self.layer_prefetch_tx.get(moe_idx, 0) + n)
        self.layer_prefetch_bytes[moe_idx] = (
            self.layer_prefetch_bytes.get(moe_idx, 0) + nbytes)

    def drop_step_records(self, hw: HardwareProfile) -> None:
        """Discard the per-step event records so long-lived engines (the
        wave server) don't retain one array pair per decode step. The
        records' overlapped seconds are folded into
        ``overlapped_dropped`` first, so :meth:`modeled_time_overlapped`
        stays cumulative — exact as long as the same ``hw`` is used
        throughout, which the engine's own ``self.hw`` guarantees."""
        self.overlapped_dropped += self.overlapped_span(hw)
        self.step_flops.clear()
        self.step_tx.clear()
        self.step_tx_bytes.clear()
        self.step_fault_delay.clear()

    # -- clocks ------------------------------------------------------------
    def modeled_time(self, hw: HardwareProfile) -> float:
        """Eq. 3, serial: Time_decode ~ Time_compute + N_miss * Time_transfer."""
        t_compute = self.compute_flops / (hw.peak_flops * hw.mfu)
        t_transfer = (
            self.transfer_bytes / hw.host_link_bw
            + self.transfers * hw.transfer_latency
        )
        return t_compute + t_transfer + self.host_time + self.fault_delay_s

    def serial_span(self, hw: HardwareProfile, start_step: int = 0,
                    end_step: Optional[int] = None) -> float:
        """Serial Eq.-3 seconds of steps[start_step:end_step] only (no
        host time): per-step flops + every demand transfer. The
        per-request time-to-first-token is the serial span of just the
        prefill step."""
        speed = hw.peak_flops * hw.mfu
        total = 0.0
        for flops, tx, txb, fd in zip(self.step_flops[start_step:end_step],
                                      self.step_tx[start_step:end_step],
                                      self.step_tx_bytes[start_step:end_step],
                                      self.step_fault_delay[start_step:end_step]):
            total += flops / speed
            total += float(txb.sum()) / hw.host_link_bw
            total += float(tx.sum()) * hw.transfer_latency
            total += fd
        return total

    def overlapped_span(self, hw: HardwareProfile, start_step: int = 0,
                        end_step: Optional[int] = None) -> float:
        """Overlapped-clock seconds of steps[start_step:end_step] only
        (no host time) — lets callers accumulate deltas instead of
        re-walking the whole history per request."""
        speed = hw.peak_flops * hw.mfu
        total = 0.0
        for flops, tx, txb, fd in zip(self.step_flops[start_step:end_step],
                                      self.step_tx[start_step:end_step],
                                      self.step_tx_bytes[start_step:end_step],
                                      self.step_fault_delay[start_step:end_step]):
            total += fd  # retry stalls serialize: nothing hides them
            L = len(tx)
            if L == 0:
                total += flops / speed
                continue
            t_tx = txb / hw.host_link_bw + tx * hw.transfer_latency
            seg = flops / speed / L
            t = float(t_tx[0])  # the first layer's fetches hide nothing
            for l in range(L):
                t += max(seg, float(t_tx[l + 1]) if l + 1 < L else 0.0)
            total += t
        return total

    def modeled_time_overlapped(self, hw: HardwareProfile) -> float:
        """Eq. 3 with cross-layer prefetch hiding: layer ``l``'s router
        output issues layer ``l+1``'s fetches, so a step's transfers
        overlap the previous layer's compute —
        ``t_step = t_tx[0] + sum_l max(t_compute_l, t_tx[l+1])``
        with the step's compute split uniformly over its MoE layers.
        Always <= :meth:`modeled_time` (``max(a, b) <= a + b``)."""
        if not self.step_flops and not self.overlapped_dropped:
            return self.modeled_time(hw)
        return self.overlapped_dropped + self.overlapped_span(hw) + self.host_time

    def throughput(self, hw: HardwareProfile, batch: int = 1,
                   overlap: bool = False) -> float:
        t = self.modeled_time_overlapped(hw) if overlap else self.modeled_time(hw)
        return (self.decode_tokens * batch) / max(t, 1e-12)

    # -- durable state (recovery checkpoints) ------------------------------
    _STATE_SCALARS = (
        "decode_tokens", "transfers", "transfer_bytes", "prefetch_transfers",
        "prefetch_bytes", "host_executed", "compute_flops", "wall_time",
        "prefill_wall_time", "host_time", "fault_delay_s", "fetch_retries",
        "fetch_failures", "degraded_uses", "overlapped_dropped",
    )
    _STATE_LAYER_DICTS = (
        "layer_tx", "layer_tx_bytes", "layer_prefetch_tx",
        "layer_prefetch_bytes",
    )

    def state(self) -> dict:
        """Cumulative counters as a plain dict (per-step event records
        are transient and deliberately excluded — a restored engine
        starts with a clean step history). Layer-dict keys become
        strings so the snapshot survives msgpack strict-key decoding."""
        out = {k: getattr(self, k) for k in self._STATE_SCALARS}
        for k in self._STATE_LAYER_DICTS:
            out[k] = {str(i): v for i, v in getattr(self, k).items()}
        return out

    def load_state(self, state: dict) -> None:
        for k in self._STATE_SCALARS:
            if k in state:
                setattr(self, k, state[k])
        for k in self._STATE_LAYER_DICTS:
            if k in state:
                setattr(self, k, {int(i): v for i, v in state[k].items()})

    # -- obs ---------------------------------------------------------------
    def publish(self, registry=None, **labels) -> None:
        """Publish the scalar counters onto a metrics registry (the
        global one by default) as labeled gauges. Purely additive — the
        existing dict/attribute contracts are untouched."""
        from ..obs.registry import REGISTRY

        reg = registry if registry is not None else REGISTRY
        g = lambda name, v: reg.gauge("engine_" + name, **labels).set(v)
        g("decode_tokens", self.decode_tokens)
        g("transfers", self.transfers)
        g("transfer_bytes", self.transfer_bytes)
        g("prefetch_transfers", self.prefetch_transfers)
        g("prefetch_bytes", self.prefetch_bytes)
        g("host_executed", self.host_executed)
        g("compute_flops", self.compute_flops)
        g("wall_time_s", self.wall_time)
        g("prefill_wall_time_s", self.prefill_wall_time)
        g("host_time_s", self.host_time)
        g("fault_delay_s", self.fault_delay_s)
        g("fetch_retries", self.fetch_retries)
        g("fetch_failures", self.fetch_failures)
        g("degraded_uses", self.degraded_uses)


def _pad_bucket(n: int) -> int:
    """Smallest power of two >= n — pads variable expert counts to a
    handful of shapes so the batched-fetch / overflow jits stay cached."""
    return 1 << (max(n, 1) - 1).bit_length()


# ---------------------------------------------------------------------------
# Resident slab: stacked per-layer expert buffers with a slot free-list
# ---------------------------------------------------------------------------


class ExpertSlab:
    """Stacked device-resident expert weights for ONE MoE layer.

    ``buffers`` is a pytree whose leaves all carry a leading slot axis of
    size ``C`` (fp: ``wg/wu/wd (C, d, f)``; INT4 ``matmul_layout``:
    packed/scale/zero triplets). Slots are recycled through a free-list
    and overwritten in place by a donated ``.at[slot].set`` — residency
    changes never reallocate the slab or retrace the compute."""

    def __init__(self, num_experts: int, capacity: int, buffers):
        self.E = num_experts
        self.C = capacity
        self.buffers = buffers
        self.residents: set = set()
        self.free: List[int] = list(range(capacity - 1, -1, -1))
        # expert id -> slot (C == "absent" sentinel; also the dispatch
        # drop index), and slot -> expert id (for slot-keyed LoRA gather)
        self.slot_of_expert = np.full(num_experts, capacity, np.int32)
        self.slot_expert = np.zeros(max(capacity, 1), np.int32)
        self.last_use: Dict[int, int] = {}  # physical LRU over compute use
        self.tick = 0
        self._dev: Optional[tuple] = None  # cached device copies of the maps
        # compact-variant index uploads keyed by (active experts, their
        # slots) — keys encode the current assignment, so entries never
        # go stale when slots are recycled
        self._compact_maps: Dict[tuple, tuple] = {}

    def drop(self, e: int) -> None:
        slot = int(self.slot_of_expert[e])
        self.slot_of_expert[e] = self.C
        self.free.append(slot)
        self.residents.discard(e)
        self.last_use.pop(e, None)
        self._dev = None

    def claim(self, e: int) -> int:
        """Assign a free slot to expert ``e`` (bookkeeping only — the
        caller writes the buffers, possibly for many slots at once)."""
        slot = self.free.pop()
        self.slot_of_expert[e] = slot
        self.slot_expert[slot] = e
        self.residents.add(e)
        self._dev = None
        return slot

    def device_maps(self) -> tuple:
        """(slot_of_expert (E,), slot_expert (C,)) as device arrays,
        re-uploaded only after residency changes."""
        if self._dev is None:
            self._dev = (jnp.asarray(self.slot_of_expert),
                         jnp.asarray(self.slot_expert))
        return self._dev


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class OffloadedMoEEngine:
    """Greedy decoding with a per-layer offloaded expert cache."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        capacity: int,
        policy: str = "lfu",
        gamma: float = 0.9,
        quantized: bool = False,
        quant_group: int = 32,
        hw: Optional[HardwareProfile] = None,
        cpu_execute: bool = False,
        stream_all: bool = False,
        lora=None,
        lora_scale: float = 1.0,
        kernel_backend: str = "ref",
        impl: str = "slab",
        little_experts: bool = False,
        little_rank: int = 8,
        little_quantized: bool = False,
        fetch_policy: Optional[FetchPolicy] = None,
        pressure_frac: float = 0.75,
    ):
        assert cfg.has_router, "offload engine needs an MoE architecture"
        assert impl in ("slab", "dict"), impl
        for name, b in cfg.block_defs.items():
            if isinstance(b.attn, MLASpec):
                raise NotImplementedError(
                    f"offload engine: block {name!r} has MLASpec attention "
                    "(a latent KV cache), which the engine does not run")
            if b.moe is not None and b.moe.held < b.moe.num_experts:
                raise NotImplementedError(
                    f"offload engine: block {name!r} holds a share of its "
                    f"experts ({b.moe.held} of {b.moe.num_experts})")
        self.cfg = cfg
        self.rt = Runtime(zero_drop=True, kernel_backend=kernel_backend)
        self.kernel_backend = kernel_backend
        self.hw = hw or hardware_profile()
        self.capacity = capacity
        self.quantized = quantized
        self.quant_group = quant_group
        self.cpu_execute = cpu_execute
        self.stream_all = stream_all
        self.lora = lora
        self.lora_scale = lora_scale
        self.impl = impl
        self.fetch_policy = fetch_policy or FetchPolicy()
        # deadline pressure: once a request has burned this fraction of
        # its Eq.-3 budget, remaining misses go all-little (quality 0)
        self.pressure_frac = pressure_frac
        self._step_quality = 1.0  # effective per-step quality dial
        self._gen_step = 0

        # ---- unstack the scanned groups into a flat per-layer list -----
        self.layers: List[dict] = []  # {"name", "spec", "params", "moe_idx"}
        self.moe_layer_ids: List[int] = []
        for gi, g in enumerate(cfg.layout):
            gparams = params["groups"][f"g{gi}"]
            glora = (lora or {}).get(f"g{gi}", {})
            for r in range(g.repeats):
                for pi, bname in enumerate(g.pattern):
                    b = cfg.block_defs[bname]
                    if b.kind == "shared_attn":
                        lp = params["shared"]
                        ll = None
                    else:
                        lp = jax.tree.map(lambda a: a[r], gparams[f"p{pi}"])
                        ll = (
                            jax.tree.map(lambda a: a[r], glora[f"p{pi}"])
                            if f"p{pi}" in glora
                            else None
                        )
                    entry = {"name": bname, "spec": b, "params": lp, "lora": ll}
                    if b.moe is not None:
                        entry["moe_idx"] = len(self.moe_layer_ids)
                        self.moe_layer_ids.append(len(self.layers))
                    self.layers.append(entry)

        self.params_top = {
            k: v for k, v in params.items() if k in ("embed", "lm_head", "final_norm")
        }
        self.moe_spec = cfg.moe_spec
        E = self.moe_spec.num_experts

        # ---- split expert weights: host store + resident buffers -------
        self.host_store: List[Dict[int, dict]] = []  # per moe layer: eid -> weights
        self.host_arrays: List[Dict[str, np.ndarray]] = []  # stacked (E, ...) fp
        self.resident: List[Dict[int, dict]] = []  # dict impl: eid -> device weights
        self.expert_bytes_fp = 0
        self.expert_bytes_q = 0
        for li in self.moe_layer_ids:
            ffn = self.layers[li]["params"]["ffn"]
            # contiguous stacked host copy: per-expert entries are views,
            # and the slab engine's batched fetch gathers rows directly
            arrs = {k: np.asarray(ffn[k]) for k in ("wg", "wu", "wd")}
            self.host_arrays.append(arrs)
            store = {}
            for e in range(E):
                w = {k: arrs[k][e] for k in ("wg", "wu", "wd")}
                if quantized:
                    # groups along the contraction axis (quantize_linear)
                    # so misses can run the fused dequant-matmul kernel
                    wq = {k: quantize_linear(jnp.asarray(v), group=quant_group,
                                             iters=4)
                          for k, v in w.items()}
                    store[e] = {"q": jax.tree.map(np.asarray, wq,
                                                  is_leaf=lambda x: isinstance(x, jax.Array))}
                    if e == 0 and li == self.moe_layer_ids[0]:
                        self.expert_bytes_q = sum(quant_bytes(q) for q in wq.values())
                else:
                    store[e] = w
                if e == 0 and li == self.moe_layer_ids[0]:
                    self.expert_bytes_fp = sum(v.nbytes for v in w.values())
            self.host_store.append(store)
            self.resident.append({})
            # remove expert weights from the per-layer device params (keep
            # router + shared expert, which are always resident)
            keep = {k: v for k, v in ffn.items() if k in ("router", "shared")}
            self.layers[li]["params"] = {**self.layers[li]["params"], "ffn": keep}

        self.expert_bytes = self.expert_bytes_q if quantized else self.expert_bytes_fp
        self.cache = ModelExpertCache(
            len(self.moe_layer_ids), E, capacity, policy=policy, gamma=gamma
        )
        self.metrics = EngineMetrics()
        self._flops_per_token = cfg.param_counts()["active"] * 2  # fwd only

        # always-resident low-rank distillates: the degraded-mode tier
        # substituted on fetch failure, capacity miss, or deadline
        # pressure (one extra little slab per MoE layer; LoRA deltas are
        # folded in at build time so compute never re-applies them)
        self.little: Optional[LittleExpertBank] = None
        if little_experts:
            self.little = LittleExpertBank(
                self.host_arrays, rank=little_rank,
                lora=[self.layers[li]["lora"] for li in self.moe_layer_ids],
                lora_scale=lora_scale, quantized=little_quantized,
                quant_group=quant_group)

        self._quant_pallas = (
            quantized and self.rt.kernel_choice("int4_matmul").use_pallas
        )
        if impl == "slab":
            self._init_slabs()
            self._jit_cache: Dict[tuple, Any] = {}
            self._embed_fn = jax.jit(
                lambda p, t, pe=None: embed_tokens(p, cfg, t, pe))
            self._next_tok_fn = jax.jit(
                lambda p, x: jnp.argmax(
                    compute_logits(p, cfg, x, self.rt)[:, -1:], -1
                ).astype(jnp.int32))
        else:
            self._embed_fn = lambda p, t, pe=None: embed_tokens(p, cfg, t, pe)
            self._next_tok_fn = lambda p, x: jnp.argmax(
                compute_logits(p, cfg, x, self.rt)[:, -1:], -1
            ).astype(jnp.int32)

    # ------------------------------------------------------------------
    # shared host-store -> device-weight materialization
    # ------------------------------------------------------------------
    def _device_weights(self, store: dict) -> dict:
        """Move one expert's host weights onto the device. Under a Pallas
        backend quantized experts stay INT4 (the compute runs the fused
        dequant matmul); under "ref" they dequantize ONCE here so the
        per-token matmuls don't repeat full-weight dequant work."""
        if self.quantized:
            qt = {k: QTensor(*[jnp.asarray(x) if isinstance(x, np.ndarray) else x
                               for x in v]) for k, v in store["q"].items()}
            if self._quant_pallas:
                return {k: matmul_layout(v) for k, v in qt.items()}
            return {k: dequantize_linear(v, jnp.float32) for k, v in qt.items()}
        return {k: jnp.asarray(v) for k, v in store.items()}

    def _slab_leaves(self, w: dict) -> dict:
        """Device weights -> the slab's per-expert leaf structure."""
        if self._quant_pallas:
            return {k: {"packed": v.packed, "scale": v.scale, "zero": v.zero}
                    for k, v in w.items()}
        return w

    # ------------------------------------------------------------------
    # slab impl
    # ------------------------------------------------------------------
    def _init_slabs(self):
        E, C = self.moe_spec.num_experts, self.capacity
        tmpl = self._slab_leaves(self._device_weights(self.host_store[0][0]))
        # fresh buffers per layer: the donating update consumes its input,
        # so slabs must never alias each other's device arrays
        self._slabs = [
            ExpertSlab(E, C, jax.tree.map(
                lambda a: jnp.zeros((C,) + a.shape, a.dtype), tmpl))
            for _ in self.moe_layer_ids
        ]
        # one trace serves every layer and every slot: the slab buffers are
        # donated so the update happens in place (no reallocation)
        self._slab_set = _quiet_donation(jax.jit(
            lambda bufs, w, slot: jax.tree.map(
                lambda s, x: s.at[slot].set(x), bufs, w),
            donate_argnums=(0,),
        ))
        # batched variant: K experts land in one host->device transfer and
        # one donated scatter (slot padding = C, dropped). jit re-traces
        # per bucket size, and bucket sizes are powers of two, so the
        # trace count stays O(log E)
        self._slab_scatter = _quiet_donation(jax.jit(
            lambda bufs, ws, slots: jax.tree.map(
                lambda s, w: s.at[slots].set(w, mode="drop"), bufs, ws),
            donate_argnums=(0,),
        ))

    def _stack_host(self, moe_idx: int, eids: List[int], bucket: int) -> dict:
        """Stack fp host weights for ``eids`` into (bucket, ...) arrays —
        one DMA's worth of contiguous expert rows. Padding repeats the
        first expert (finite values, one gather, no zero-fill): padded
        scatter slots are dropped, and padded overflow groups receive
        zero token rows, so the pad content never reaches an output."""
        idx = np.full(bucket, eids[0], np.int64)
        idx[: len(eids)] = eids
        return {k: a[idx] for k, a in self.host_arrays[moe_idx].items()}

    def _sync_slab(self, moe_idx: int) -> int:
        """Mirror the cache manager's resident set into the device slab."""
        slab = self._slabs[moe_idx]
        target = self.cache.layers[moe_idx].resident
        for e in [e for e in slab.residents if e not in target]:
            slab.drop(e)
        new = sorted(target - slab.residents)
        if not new:
            return 0
        if self.quantized:  # per-expert: leaves differ per projection
            for e in new:
                leaves = self._slab_leaves(
                    self._device_weights(self.host_store[moe_idx][e]))
                slab.buffers = self._slab_set(slab.buffers, leaves,
                                              slab.claim(e))
            return len(new)
        bucket = _pad_bucket(len(new))
        ws = self._stack_host(moe_idx, new, bucket)
        slots = np.full(bucket, slab.C, np.int32)
        for i, e in enumerate(new):
            slots[i] = slab.claim(e)
        slab.buffers = self._slab_scatter(slab.buffers, ws,
                                          jnp.asarray(slots))
        return len(new)

    def _ensure_resident(self, moe_idx: int, needed: List[int]):
        """Physically load as many of ``needed`` as fit into the slab.

        The *modeled* residency/transfer accounting is entirely the cache
        manager's (``access_batch`` above); the slab is the physical pool
        of C device slots behind it, and between steps it may retain any
        C experts. Retaining by recency of *compute use* minimizes real
        host->device traffic: the token-sequential accounting can stream
        more experts through its C logical slots than survive a batch,
        and mirroring that churn would re-fetch weights the slab already
        holds. Returns (missing, update): the experts that still did not
        fit (served by the overflow bucket), and the pending slab load —
        stacked host rows + target slots — which the NEXT compute call
        applies in-jit so a fetch costs no extra launch. Slot
        bookkeeping is committed here; only the buffer write is
        deferred."""
        slab = self._slabs[moe_idx]
        slab.tick += 1
        if slab.residents.issuperset(needed):  # warm fast path
            for e in needed:
                slab.last_use[e] = slab.tick
            return [], None
        needed_set = set(needed)
        new = [e for e in needed if e not in slab.residents]
        update = None
        if new:
            evictable = sorted(
                (e for e in slab.residents if e not in needed_set),
                key=lambda e: slab.last_use.get(e, -1))
            load = new[: len(slab.free) + len(evictable)]
            while len(slab.free) < len(load):
                slab.drop(evictable.pop(0))
            if load:
                bucket = _pad_bucket(len(load))
                ws = self._stack_host(moe_idx, load, bucket)
                slots = np.full(bucket, slab.C, np.int32)
                for i, e in enumerate(load):
                    slots[i] = slab.claim(e)
                update = (ws, jnp.asarray(slots))
        for e in needed:
            if e in slab.residents:
                slab.last_use[e] = slab.tick
        return [e for e in needed if e not in slab.residents], update

    def _pre_decode_body(self, b: BlockSpec, p, x, cache, pos):
        from ..models.attention import decode_attend

        cfg = self.cfg
        h = rms_norm(p["ln1"], x, cfg.norm_eps)
        y, new_cache = decode_attend(p["mixer"], b.attn, h, cache, pos,
                                     b.attn.window)
        xa = x + y
        h2 = rms_norm(p["ln2"], xa, cfg.norm_eps)
        B, T, dm = h2.shape
        h2f = h2.reshape(B * T, dm)
        probs = router_probs(p["ffn"], h2f, b.moe)
        gates, eids = top_k_route(probs, b.moe.top_k)
        return xa, h2f, gates, eids, new_cache

    def _jit_pre_decode(self, b: BlockSpec):
        return jax.jit(partial(self._pre_decode_body, b))

    def _jit_pre_full(self, b: BlockSpec):
        cfg, rt = self.cfg, self.rt

        def fn(p, x, positions, n_slots):
            from ..models.attention import attend_full, cache_from_prefill

            h = rms_norm(p["ln1"], x, cfg.norm_eps)
            y, (k, v) = attend_full(p["mixer"], b.attn, h, positions,
                                    b.attn.window, return_kv=True, rt=rt)
            kv = cache_from_prefill(k, v, b.attn, n_slots)
            xa = x + y
            h2 = rms_norm(p["ln2"], xa, cfg.norm_eps)
            B, T, dm = h2.shape
            h2f = h2.reshape(B * T, dm)
            probs = router_probs(p["ffn"], h2f, b.moe)
            gates, eids = top_k_route(probs, b.moe.top_k)
            return xa, h2f, gates, eids, kv

        return jax.jit(fn, static_argnames=("n_slots",))

    def _dequant_slab_mat(self, leaves: dict) -> jax.Array:
        """INT4 matmul_layout slab (packed (C, K//2, N)) -> fp32 (C, K, N):
        the kernel oracle's dequant, vmapped over the slot axis — one
        source of truth for the packing."""
        from ..kernels.int4_matmul.ref import dequant_ref

        return jax.vmap(lambda p, s, z: dequant_ref(p, s, z, self.quant_group))(
            leaves["packed"], leaves["scale"], leaves["zero"])

    def _group_core(self, dequant: bool):
        """The grouped compute shared by the resident-slab step and the
        overflow step: sort the token top-k assignments by slot, run ONE
        grouped matmul per projection over all slots at once, add LoRA
        as a slot-gathered batched low-rank term, gate-combine."""
        sc = self.lora_scale
        choice = self.rt.kernel_choice("moe_gmm")

        def low_rank(x, a, b_, out_dtype):
            t = jnp.einsum("cnd,cdr->cnr", x.astype(jnp.float32),
                           a.astype(jnp.float32))
            return (sc * jnp.einsum("cnr,crf->cnf", t,
                                    b_.astype(jnp.float32))).astype(out_dtype)

        def core(slabs, lora, soe, slot_expert, h2f, gates, eids):
            C = slot_expert.shape[0]
            N, K = eids.shape
            slots = soe[eids]  # (N, K); == C where the expert is absent
            flat = slots.reshape(N * K)
            oh = jax.nn.one_hot(flat, C + 1, dtype=jnp.int32)
            sizes = oh.sum(0)[:C]  # tokens per slot (ragged gmm groups)
            if N == 1:
                # single-token step (the wave server's shape): every
                # active slot's buffer row IS the token — no sort/scatter
                buf = jnp.broadcast_to(h2f[None], (C, 1, h2f.shape[-1]))
                buf = buf * (sizes > 0)[:, None, None].astype(buf.dtype)
                d = None
            else:
                pos = (jnp.cumsum(oh, axis=0) * oh).sum(-1) - 1
                keep = (flat < C).reshape(N, K)
                d = Dispatch(
                    eids=slots,
                    pos=jnp.where(keep, pos.reshape(N, K), 0),
                    gates=jnp.where(keep, gates, 0.0),
                    cap=N,
                )
                buf = dispatch_tokens(d, h2f, C)  # (C, N, d) slot-sorted
            if dequant:
                wg, wu, wd = (self._dequant_slab_mat(slabs[k])
                              for k in ("wg", "wu", "wd"))
            else:
                wg, wu, wd = slabs["wg"], slabs["wu"], slabs["wd"]
            record("moe_gmm", choice)
            if choice.use_pallas:
                from ..kernels.moe_gmm import ops as gmm_ops

                mm = partial(gmm_ops.gmm_pallas, group_sizes=sizes,
                             interpret=choice.interpret)
            else:
                mm = lambda a, w: jnp.einsum("cnd,cdf->cnf", a, w)
            hg = mm(buf, wg)
            hu = mm(buf, wu)
            if lora is not None:
                au = lora["wu"]["a"][slot_expert]
                bu = lora["wu"]["b"][slot_expert]
                hu = hu + low_rank(buf, au, bu, hu.dtype)
            h_act = silu(hg) * hu
            yb = mm(h_act, wd)
            if lora is not None:
                ad = lora["wd"]["a"][slot_expert]
                bd = lora["wd"]["b"][slot_expert]
                yb = yb + low_rank(h_act, ad, bd, yb.dtype)
            if N == 1:  # gate-combine by direct slot gather
                safe = jnp.minimum(flat, C - 1)
                g1 = jnp.where(flat < C, gates[0], 0.0)
                gathered = yb[safe, 0]  # (K, d)
                return jnp.einsum(
                    "kd,k->d", gathered.astype(jnp.float32), g1
                )[None].astype(yb.dtype)
            return combine_tokens(d, yb)  # (N, d)

        return core

    @staticmethod
    def _apply_slab_update(slabs, update):
        """Apply a deferred fetch (stacked rows + slots; pad slots == C
        are dropped) to the slab buffers, inside the compute jit."""
        if update is None:
            return slabs
        ws, slots = update
        return jax.tree.map(lambda s, w: s.at[slots].set(w, mode="drop"),
                            slabs, ws)

    def _jit_moe_apply(self, b: BlockSpec):
        """Resident-slab per-MoE-layer step (+ the shared expert).
        Applies the layer's pending slab load first (donated buffers, so
        in place), then computes. Assignments whose expert is not in the
        slab (within-batch capacity overflow, degenerate C < K,
        cpu/stream modes) are dropped here and served by the overflow
        step. Returns (y, updated slab buffers)."""
        spec = b.moe
        core = self._group_core(self._quant_pallas)

        def fn(ffn, lora, slabs, update, soe, slot_expert, h2f, gates, eids):
            slabs = self._apply_slab_update(slabs, update)
            y = core(slabs, lora, soe, slot_expert, h2f, gates, eids)
            if spec.shared_d_ff:
                y = y + apply_mlp(ffn["shared"], h2f)
            return y, slabs

        return _quiet_donation(jax.jit(fn, donate_argnums=(2,)))

    def _jit_moe_overflow(self, b: BlockSpec):
        """Grouped compute over an ephemeral stacked bucket of experts
        the slab could not hold this step (fp weights, no shared)."""
        core = self._group_core(False)

        def fn(lora, ws, soe, slot_expert, h2f, gates, eids):
            return core(ws, lora, soe, slot_expert, h2f, gates, eids)

        return jax.jit(fn)

    def _jit_moe_compact(self, b: BlockSpec):
        """Like the resident-slab step, but over a gathered bucket of the
        ACTIVE slots only. The reference grouped matmul cannot skip empty
        groups the way the ragged Pallas kernel does, so when this step
        touches far fewer experts than the slab holds (small decode
        batches, large C), gathering G slots and computing (G, N, ...)
        beats streaming all C slots' weights through the einsum."""
        spec = b.moe
        core = self._group_core(self._quant_pallas)

        def fn(ffn, lora, slabs, update, group_slots, soe_g, group_expert,
               h2f, gates, eids):
            slabs = self._apply_slab_update(slabs, update)
            w = jax.tree.map(lambda s: s[group_slots], slabs)
            y = core(w, lora, soe_g, group_expert, h2f, gates, eids)
            if spec.shared_d_ff:
                y = y + apply_mlp(ffn["shared"], h2f)
            return y, slabs

        return _quiet_donation(jax.jit(fn, donate_argnums=(2,)))

    def _jit_fused_dec(self, b_l: BlockSpec, b_next: BlockSpec, compact: bool):
        """Layer l's grouped MoE apply + residual + layer l+1's
        attention/router in ONE jitted call — the decode hot loop runs
        one launch (and one host sync) per MoE layer instead of two."""
        spec = b_l.moe
        core = self._group_core(self._quant_pallas)

        def fn(ffn, lora, slabs, update, maps, h2f, gates, eids, xa,
               p_next, cache_next, pos):
            slabs = self._apply_slab_update(slabs, update)
            if compact:
                gs, soe_g, ge = maps
                w = jax.tree.map(lambda s: s[gs], slabs)
                y = core(w, lora, soe_g, ge, h2f, gates, eids)
            else:
                soe, se = maps
                y = core(slabs, lora, soe, se, h2f, gates, eids)
            if spec.shared_d_ff:
                y = y + apply_mlp(ffn["shared"], h2f)
            B = xa.shape[0]
            x = xa + y.reshape(B, -1, xa.shape[-1])
            return (*self._pre_decode_body(b_next, p_next, x, cache_next, pos),
                    slabs)

        return _quiet_donation(jax.jit(fn, donate_argnums=(2,)))

    def _jitted(self, kind: str, bname: str):
        key = (kind, bname)
        if key not in self._jit_cache:
            b = self.cfg.block_defs[bname]
            maker = {"pre_dec": self._jit_pre_decode,
                     "pre_full": self._jit_pre_full,
                     "moe": self._jit_moe_apply,
                     "moe_compact": self._jit_moe_compact,
                     "moe_over": self._jit_moe_overflow}[kind]
            self._jit_cache[key] = maker(b)
        return self._jit_cache[key]

    def _jitted_fused(self, bname_l: str, bname_next: str, compact: bool):
        key = ("fused_dec", bname_l, bname_next, compact)
        if key not in self._jit_cache:
            self._jit_cache[key] = self._jit_fused_dec(
                self.cfg.block_defs[bname_l], self.cfg.block_defs[bname_next],
                compact)
        return self._jit_cache[key]

    # ------------------------------------------------------------------
    # resilience: fault-injected transfer trials + the quality dial
    # ------------------------------------------------------------------
    def _resilience_active(self) -> bool:
        """One cheap guard for every hot-path hook: with no fault plan
        installed and the quality dial at 1.0, every resilience branch
        is skipped and decode is bit-for-bit the unmodified engine."""
        return get_fault_plan().enabled or (
            self.little is not None and self._step_quality < 1.0)

    def _degrade_roll(self, moe_idx: int, e: int) -> bool:
        """Deterministic per-(layer, expert, step) quality roll: True
        means substitute the little expert instead of fetching the big
        one. quality 1.0 never degrades by choice; 0.0 always does."""
        q = self._step_quality
        if q >= 1.0:
            return False
        h = (moe_idx * 0x9E3779B1 ^ e * 0x85EBCA77
             ^ self._gen_step * 0xC2B2AE3D) & 0xFFFFFFFF
        h ^= h >> 16
        h = (h * 0x45D9F3B) & 0xFFFFFFFF
        h ^= h >> 16
        return (h / 2.0**32) >= q

    def _guard_fetch(self, moe_idx: int, eids, *, prefetch: bool = False):
        """Fault-plan transfer trials for each expert in ``eids``.
        Charges modeled fault delay for latency spikes, failed attempts
        (the failed DMA burned real link time) and retry backoff;
        returns the experts whose fetch was abandoned once the retry
        budget or per-fetch deadline ran out. Demand fetches without a
        little bank cannot degrade — they retry until success (the
        no-resilience baseline the chaos bench measures), bounded only
        by the policy's hard_cap. Prefetches are always bounded
        best-effort: an abandoned prefetch just stays cold."""
        plan = get_fault_plan()
        if not plan.enabled:
            return []
        pol = self.fetch_policy
        m = self.metrics
        per_try = (self.expert_bytes / self.hw.host_link_bw
                   + self.hw.transfer_latency)
        can_degrade = prefetch or self.little is not None
        dropped = []
        for e in eids:
            spent, attempt = 0.0, 0
            while True:
                spike = plan.transfer_spike(moe_idx)
                if spike:
                    m.add_fault_delay(spike)
                if not plan.fetch_fails(moe_idx):
                    break
                m.fetch_failures += 1
                delay = per_try + pol.backoff(attempt)
                spent += delay
                m.add_fault_delay(delay)
                attempt += 1
                if can_degrade and not pol.attempts_allowed(attempt, spent):
                    dropped.append(e)
                    break
                if attempt >= pol.hard_cap:  # runaway guard only
                    break
                m.fetch_retries += 1
        return dropped

    def _degrade_misses(self, moe_idx: int, missed):
        """Resilience verdicts over one step's modeled misses: the
        quality roll first — an expert degraded by choice is never
        fetched, so it skips the fault trial and pays nothing — then
        fault trials on whatever still wants the link. Degraded experts
        leave the modeled resident set (they were never fetched, so
        future steps re-miss them honestly) and their transfers go
        uncharged. Returns (degraded_ids, n_charged)."""
        uniq = sorted(set(int(e) for e in missed))
        degraded = set()
        if self.little is not None and self._step_quality < 1.0:
            degraded = {e for e in uniq if self._degrade_roll(moe_idx, e)}
        degraded |= set(self._guard_fetch(
            moe_idx, [e for e in uniq if e not in degraded]))
        if not degraded:
            return [], len(missed)
        resident = self.cache.layers[moe_idx].resident
        for e in degraded:
            resident.discard(e)
        self.metrics.degraded_uses += len(degraded)
        n_charged = sum(1 for e in missed if int(e) not in degraded)
        return sorted(degraded), n_charged

    def _miss_verdict(self, moe_idx: int, e: int) -> bool:
        """Single-miss degrade verdict for the token-sequential dict
        path: the quality roll first (degrading by choice skips the
        fetch and its fault trial entirely), then the fault-plan
        trial."""
        if self.little is not None and self._degrade_roll(moe_idx, e):
            return True
        return bool(self._guard_fetch(moe_idx, [e]))

    def _apply_storm(self, frac: float) -> None:
        """Eviction storm: a co-tenant thrashes device memory — drop a
        ``frac`` fraction of every layer's residents (modeled AND
        physical), forcing re-misses on the next touch."""
        plan = get_fault_plan()
        for moe_idx, cache in enumerate(self.cache.layers):
            for v in plan.storm_victims(cache.resident, frac):
                cache.resident.discard(v)
                cache.evictions += 1
                if self.impl == "slab":
                    slab = self._slabs[moe_idx]
                    if v in slab.residents:
                        slab.drop(v)
                else:
                    self.resident[moe_idx].pop(v, None)

    def _guard_prefetch(self) -> None:
        """Fault trials for the pending prefetch loads (cache residents
        not yet physically present): abandoned experts are dropped from
        the modeled resident set before the physical sync, so they stay
        cold and may demand-miss later — no substitution, prefetch is
        best-effort by definition."""
        for moe_idx in range(len(self.moe_layer_ids)):
            target = self.cache.layers[moe_idx].resident
            if self.impl == "slab":
                have = self._slabs[moe_idx].residents
            else:
                have = self.resident[moe_idx].keys()
            new = sorted(e for e in target if e not in have)
            for e in self._guard_fetch(moe_idx, new, prefetch=True):
                target.discard(e)

    # ------------------------------------------------------------------
    def _fetch(self, moe_idx: int, eid: int, *, prefetch: bool = False):
        """Host -> device transfer of one expert (dict impl; simulated DMA)."""
        name = "moe.prefetch" if prefetch else "moe.fetch"
        with get_tracer().span(name, layer=moe_idx, experts=1):
            store = self.host_store[moe_idx][eid]
            w = _obs_sync(self._device_weights(store))
        nbytes = self.expert_bytes_q if self.quantized else self.expert_bytes_fp
        self.resident[moe_idx][eid] = w
        if prefetch:
            self.metrics.add_prefetch_transfers(moe_idx, 1, nbytes)
        else:
            self.metrics.add_demand_transfers(moe_idx, 1, nbytes)
        # enforce the device budget: drop non-cached residents
        cached = self.cache.layers[moe_idx].resident
        for stale in [e for e in self.resident[moe_idx] if e not in cached and e != eid]:
            del self.resident[moe_idx][stale]

    def prefetch(self, scores: np.ndarray):
        """Predictor-driven proactive cache load (Sec 3.2). scores (L, E)."""
        with get_tracer().span("engine.prefetch"):
            self.cache.prefill_from_scores(scores)
            if get_fault_plan().enabled:
                self._guard_prefetch()
            if self.impl == "slab":
                for moe_idx in range(len(self.moe_layer_ids)):
                    with get_tracer().span("moe.prefetch", layer=moe_idx):
                        added = self._sync_slab(moe_idx)
                        if added:
                            _obs_sync(self._slabs[moe_idx].buffers)
                    self.metrics.add_prefetch_transfers(
                        moe_idx, added, added * self.expert_bytes)
                return
            for moe_idx, cache in enumerate(self.cache.layers):
                for e in cache.resident:
                    if e not in self.resident[moe_idx]:
                        self._fetch(moe_idx, e, prefetch=True)

    # ------------------------------------------------------------------
    # recovery: durable cache state, warm revival, integrity audit
    # ------------------------------------------------------------------
    def cache_state(self) -> List[dict]:
        """Per-layer cache snapshots (resident set + policy scores) for
        a recovery checkpoint — the MELINOE-valuable state a cold
        restart would otherwise re-pay in transfer churn."""
        return self.cache.state()

    def revive(self, cache_state: List[dict], *, warm: bool = True) -> dict:
        """Restore a checkpointed cache and (``warm=True``) physically
        prefetch the checkpointed resident set back into the device
        slabs before serving resumes — the restart path that preserves
        the warmed expert placement instead of cold-starting.

        Returns ``{"loaded", "bytes", "modeled_s"}`` so callers can
        charge the revival DMA to their clock (the loads are counted as
        prefetch transfers, same as a predictor prefetch)."""
        self.cache.load_state(cache_state, resident=warm)
        loaded = 0
        if warm:
            with get_tracer().span("engine.revive"):
                if self.impl == "slab":
                    for moe_idx in range(len(self.moe_layer_ids)):
                        added = self._sync_slab(moe_idx)
                        if added:
                            _obs_sync(self._slabs[moe_idx].buffers)
                            self.metrics.add_prefetch_transfers(
                                moe_idx, added, added * self.expert_bytes)
                        loaded += added
                else:
                    for moe_idx, cache in enumerate(self.cache.layers):
                        for e in sorted(cache.resident):
                            if e not in self.resident[moe_idx]:
                                self._fetch(moe_idx, e, prefetch=True)
                                loaded += 1
        nbytes = loaded * self.expert_bytes
        modeled = (nbytes / self.hw.host_link_bw
                   + loaded * self.hw.transfer_latency)
        return {"loaded": loaded, "bytes": nbytes, "modeled_s": modeled}

    def resync_slabs(self) -> int:
        """Self-heal: force physical residency back in line with the
        cache manager's accounting. Drops stale physical residents (and,
        slab impl, reloads missing cached experts). Only the watchdog
        calls this, on detected drift — routine syncing would defeat the
        slab's LRU-of-compute-use retention."""
        healed = 0
        if self.impl == "slab":
            for moe_idx in range(len(self.moe_layer_ids)):
                slab = self._slabs[moe_idx]
                target = self.cache.layers[moe_idx].resident
                drift = len(set(slab.residents) - target)
                healed += drift + self._sync_slab(moe_idx)
        else:
            for moe_idx, cache in enumerate(self.cache.layers):
                res = self.resident[moe_idx]
                for e in [e for e in res if e not in cache.resident]:
                    del res[e]
                    healed += 1
        return healed

    def audit(self) -> List[tuple]:
        """Integrity check (watchdog contract): cross-checks the slab
        free-list / slot maps against the cache manager's accounting.
        Returns ``(severity, message)`` tuples — ``"hard"`` violations
        mean corrupted bookkeeping (fail fast), ``"drift"`` means
        physical residency exceeds the modeled budget (self-healable via
        :meth:`resync_slabs`). NOTE: slab residents *not* in the cache
        manager's set are normal, not drift — the slab deliberately
        retains evicted experts by compute-use LRU (see
        ``_ensure_resident``) — so only budget/bookkeeping breaks count."""
        v: List[tuple] = []
        for msg in self.cache.audit():
            v.append(("hard", f"cache: {msg}"))
        E = self.moe_spec.num_experts
        if self.impl == "slab":
            for moe_idx, slab in enumerate(self._slabs):
                pre = f"slab[L{moe_idx}]"
                if len(slab.free) + len(slab.residents) != slab.C:
                    v.append(("hard", f"{pre}: free {len(slab.free)} + "
                              f"resident {len(slab.residents)} != C {slab.C}"))
                used = []
                for e in slab.residents:
                    s = int(slab.slot_of_expert[e])
                    if not (0 <= s < slab.C):
                        v.append(("hard", f"{pre}: resident {e} has no slot"))
                    elif int(slab.slot_expert[s]) != e:
                        v.append(("hard", f"{pre}: slot map mismatch for "
                                  f"expert {e} (slot {s} claims "
                                  f"{int(slab.slot_expert[s])})"))
                    else:
                        used.append(s)
                if sorted(used + list(slab.free)) != list(range(slab.C)):
                    v.append(("hard", f"{pre}: slots not a disjoint "
                              f"partition of free + used"))
                ghosts = [e for e in range(E)
                          if int(slab.slot_of_expert[e]) != slab.C
                          and e not in slab.residents]
                if ghosts:
                    v.append(("hard", f"{pre}: non-resident experts with "
                              f"slots: {ghosts[:8]}"))
        else:
            for moe_idx, cache in enumerate(self.cache.layers):
                res = self.resident[moe_idx]
                stale = sorted(set(res) - cache.resident)
                if stale:
                    v.append(("drift", f"dict[L{moe_idx}]: physical residents "
                              f"outside the cache budget: {stale[:8]}"))
                if len(res) > self.capacity + len(stale):
                    v.append(("hard", f"dict[L{moe_idx}]: {len(res)} residents "
                              f"exceed capacity {self.capacity}"))
        return v

    # ------------------------------------------------------------------
    # dict impl MoE forward (the pre-rewrite reference path)
    # ------------------------------------------------------------------
    def _moe_forward(self, moe_idx: int, layer: dict, h2):
        """h2 (B, T, d) -> (B, T, d) expert output under the cache."""
        tr = get_tracer()
        b = layer["spec"]
        spec = b.moe
        B, T, dm = h2.shape
        h2f = h2.reshape(B * T, dm)
        with tr.span("moe.pre", layer=moe_idx):
            probs = router_probs(layer["params"]["ffn"], h2f, spec)
            gates, eids = top_k_route(probs, spec.top_k)
            eids_np = np.asarray(eids)

        # --- cache accounting: token-sequential accesses ---------------
        # the account span brackets the whole loop; demand fetches nest
        # their own moe.fetch spans inside it, so reconciliation treats
        # moe.account as informational rather than additive
        degraded: set = set()
        resilient = self._resilience_active()
        with tr.span("moe.account", layer=moe_idx, tokens=B * T):
            for n in range(B * T):
                if self.stream_all:
                    self.metrics.add_demand_transfers(
                        moe_idx, spec.top_k, spec.top_k * self.expert_bytes)
                else:
                    missed = self.cache.access(moe_idx, eids_np[n])
                    for e in missed:
                        e = int(e)
                        if self.cpu_execute:
                            # Fiddler mode: run the expert on the host instead
                            # of transferring (cost model; see baselines)
                            self.metrics.host_executed += 1
                        elif resilient and self._miss_verdict(moe_idx, e):
                            # abandoned fetch / quality roll: serve the
                            # little expert, stay modeled-non-resident
                            self.cache.layers[moe_idx].resident.discard(e)
                            if e not in degraded:
                                self.metrics.degraded_uses += 1
                            degraded.add(e)
                        else:
                            # a later successful fetch supersedes an
                            # earlier give-up for the same expert
                            degraded.discard(e)
                            self._fetch(moe_idx, e)

        # --- actual computation (exact, using whatever weights) --------
        needed = set(int(e) for e in np.unique(eids_np)) - degraded

        def weight_for(e):  # cpu_execute / stream_all paths still need weights
            w = self.resident[moe_idx].get(e)
            return w if w is not None else self._device_weights(
                self.host_store[moe_idx][e])

        with tr.span("moe.compute", layer=moe_idx, experts=len(needed)):
            out = self._per_expert_contrib(h2f, gates, eids, sorted(needed),
                                           weight_for, layer["lora"])
            if degraded:
                with tr.span("moe.degraded", layer=moe_idx,
                             experts=len(degraded)):
                    out = out + self.little.contrib(
                        moe_idx, h2f, gates, eids, sorted(degraded))
            y = out.astype(h2.dtype)
            if spec.shared_d_ff:
                y = y + apply_mlp(layer["params"]["ffn"]["shared"], h2f)
            _obs_sync(y)
        return y.reshape(B, T, dm), probs.reshape(B, T, -1)

    def _per_expert_contrib(self, h2f, gates, eids, expert_ids, weight_for,
                            lora):
        """The eager per-expert gated-MLP loop shared by the dict engine
        and the slab engine's quantized overflow path: gate-massed fp32
        accumulation over ``expert_ids``, LoRA as a separate low-rank
        term, fused dequant matmul for INT4 weights."""
        out = jnp.zeros_like(h2f, dtype=jnp.float32)

        def mm(x, w):
            if isinstance(w, jax.Array):
                return x @ w
            return qmatmul(x, w, backend=self.kernel_backend)

        for e in expert_ids:
            w = weight_for(e)
            hg, hu = mm(h2f, w["wg"]), mm(h2f, w["wu"])
            if lora is not None:
                sc = self.lora_scale
                hu = hu + sc * ((h2f @ lora["wu"]["a"][e]) @ lora["wu"]["b"][e]).astype(hu.dtype)
            h_act = silu(hg) * hu
            ye = mm(h_act, w["wd"])
            if lora is not None:
                sc = self.lora_scale
                ye = ye + sc * ((h_act @ lora["wd"]["a"][e]) @ lora["wd"]["b"][e]).astype(ye.dtype)
            gate_mass = jnp.where(eids == e, gates, 0.0).sum(-1)  # (N,)
            out = out + gate_mass[:, None] * ye.astype(jnp.float32)
        return out

    # ------------------------------------------------------------------
    # slab impl MoE forward
    # ------------------------------------------------------------------
    def _prep_moe(self, moe_idx: int, layer: dict, xa, h2f, gates, eids):
        """Host half of the per-MoE-layer step: cache accounting +
        physical residency + compute-variant choice. Returns the pending
        record :meth:`_finish_moe` (or a fused call) consumes."""
        tr = get_tracer()
        degraded: List[int] = []
        with tr.span("moe.account", layer=moe_idx):
            eids_np = np.asarray(eids)
            N, K = eids_np.shape

            # --- cache accounting: one vectorized call per layer per step
            if self.stream_all:
                self.metrics.add_demand_transfers(
                    moe_idx, N * K, N * K * self.expert_bytes)
            else:
                missed = self.cache.layers[moe_idx].access_batch(eids_np)
                if self.cpu_execute:
                    self.metrics.host_executed += len(missed)
                elif missed:
                    if self._resilience_active():
                        degraded, n_charged = self._degrade_misses(
                            moe_idx, missed)
                    else:
                        n_charged = len(missed)
                    if n_charged:
                        self.metrics.add_demand_transfers(
                            moe_idx, n_charged,
                            n_charged * self.expert_bytes)

        # --- physical residency: load what this step computes ----------
        slab = self._slabs[moe_idx]
        needed = sorted(set(eids_np.ravel().tolist()))
        if degraded:
            dset = set(degraded)
            needed = [e for e in needed if e not in dset]
            # a degraded expert must never be served from a stale
            # physical slot the slab happened to retain
            for e in degraded:
                if e in slab.residents:
                    slab.drop(e)
        update = None
        with tr.span("moe.fetch", layer=moe_idx):
            if self.cpu_execute or self.stream_all:
                # host-executed / streamed experts never persist on device:
                # everything runs through the per-step overflow bucket
                missing = [e for e in needed if e not in slab.residents]
            elif self.quantized:
                # quantized leaves are heterogeneous; mirror the manager
                if missed:
                    self._sync_slab(moe_idx)
                    _obs_sync(slab.buffers)
                missing = [e for e in needed if e not in slab.residents]
            else:
                missing, update = self._ensure_resident(moe_idx, needed)
                if update is not None and tr.enabled:
                    # slab fetches are fused into the next compute launch
                    # by design; under tracing, stage the host rows onto
                    # the device here so the fetch span measures the DMA
                    # instead of leaking it into the compute span
                    ws, slots = update
                    ws = jax.tree.map(jnp.asarray, ws)
                    jax.block_until_ready(ws)
                    update = (ws, slots)

        in_slab = [e for e in needed if e in slab.residents]
        G = _pad_bucket(len(in_slab))
        if 2 * G < slab.C:
            # few active slots: gather them and compute (G, N, ...) —
            # cheaper than streaming all C slots through the ref einsum.
            # Routing is sticky step-to-step, so the tiny index uploads
            # are cached by active-set key.
            key = (tuple(in_slab), tuple(int(slab.slot_of_expert[e])
                                         for e in in_slab))
            cache = slab._compact_maps
            maps = cache.get(key)
            if maps is None:
                E = self.moe_spec.num_experts
                group_slots = np.zeros(G, np.int32)
                soe_g = np.full(E, G, np.int32)
                group_expert = np.zeros(G, np.int32)
                for i, e in enumerate(in_slab):
                    group_slots[i] = slab.slot_of_expert[e]
                    soe_g[e] = i
                    group_expert[i] = e
                if len(cache) > 256:  # routing revisits few active sets
                    cache.clear()
                maps = cache[key] = (jnp.asarray(group_slots),
                                     jnp.asarray(soe_g),
                                     jnp.asarray(group_expert))
            variant = "compact"
        else:
            variant, maps = "full", slab.device_maps()
        return {"moe_idx": moe_idx, "layer": layer, "xa": xa, "h2f": h2f,
                "gates": gates, "eids": eids, "missing": missing,
                "degraded": degraded, "variant": variant, "maps": maps,
                "slab": slab, "update": update}

    def _finish_moe(self, p: dict):
        """Device half of the per-MoE-layer step, standalone: grouped
        compute (+ overflow for experts the slab could not serve: the
        |needed| > C spillover, degenerate C < K, cpu_execute,
        stream_all — transiently-on-device experts run through an
        ephemeral stacked bucket, or per expert with the fused dequant
        kernel when quantized) and the residual add."""
        layer, h2f, gates, eids = p["layer"], p["h2f"], p["gates"], p["eids"]
        kind = "moe_compact" if p["variant"] == "compact" else "moe"
        tr = get_tracer()
        with tr.span("moe.compute", layer=p["moe_idx"], variant=p["variant"]):
            y, p["slab"].buffers = self._jitted(kind, layer["name"])(
                layer["params"]["ffn"], layer["lora"], p["slab"].buffers,
                p["update"], *p["maps"], h2f, gates, eids,
            )
            _obs_sync(y)
        if p["missing"]:
            with tr.span("moe.spillover", layer=p["moe_idx"],
                         experts=len(p["missing"])):
                if self.quantized:
                    extra = self._eager_contrib(p["moe_idx"], layer, h2f,
                                                gates, eids, p["missing"])
                else:
                    extra = self._overflow_group(p["moe_idx"], layer, h2f,
                                                 gates, eids, p["missing"])
                y = _obs_sync(y + extra.astype(y.dtype))
        if p["degraded"]:
            with tr.span("moe.degraded", layer=p["moe_idx"],
                         experts=len(p["degraded"])):
                extra = self.little.contrib(p["moe_idx"], h2f, gates, eids,
                                            p["degraded"])
                y = _obs_sync(y + extra.astype(y.dtype))
        xa = p["xa"]
        B = xa.shape[0]
        return xa + y.reshape(B, -1, xa.shape[-1])

    def _overflow_group(self, moe_idx, layer, h2f, gates, eids, missing):
        E = self.moe_spec.num_experts
        bucket = _pad_bucket(len(missing))
        ws = self._stack_host(moe_idx, missing, bucket)
        soe = np.full(E, bucket, np.int32)
        se = np.zeros(bucket, np.int32)
        for i, e in enumerate(missing):
            soe[e] = i
            se[i] = e
        return self._jitted("moe_over", layer["name"])(
            layer["lora"], ws, jnp.asarray(soe), jnp.asarray(se),
            h2f, gates, eids,
        )

    def _eager_contrib(self, moe_idx, layer, h2f, gates, eids, missing):
        return self._per_expert_contrib(
            h2f, gates, eids, missing,
            lambda e: self._device_weights(self.host_store[moe_idx][e]),
            layer["lora"])

    # ------------------------------------------------------------------
    def _forward_layers_slab(self, x, positions, caches, decode_pos=None):
        """Pipelined layer walk for the slab engine: while layer l's MoE
        apply is still pending, the host finishes l's cache accounting,
        then ONE fused jitted call runs l's grouped compute together
        with layer l+1's attention/router (decode path, no overflow).
        Falls back to split calls at pipeline boundaries."""
        tr = get_tracer()
        pending = None
        for idx, layer in enumerate(self.layers):
            b = layer["spec"]
            if not (b.moe is not None and b.kind == "attn_moe"):
                if pending is not None:
                    x = self._finish_moe(pending)
                    pending = None
                x = self._block_forward(layer, x, positions, caches, idx,
                                        decode_pos)
                continue
            if pending is None:
                with tr.span("moe.pre", layer=layer["moe_idx"]):
                    if decode_pos is None:
                        xa, h2f, gates, eids, caches[idx] = self._jitted(
                            "pre_full", layer["name"])(
                                layer["params"], x, positions,
                                n_slots=self._n_slots)
                    else:
                        xa, h2f, gates, eids, caches[idx] = self._jitted(
                            "pre_dec", layer["name"])(
                                layer["params"], x, caches[idx], decode_pos)
                    _obs_sync(eids)
            elif (decode_pos is not None and not pending["missing"]
                  and not pending["degraded"]):
                # one launch: pending layer's grouped compute + THIS
                # layer's attention/router — the span charges it to the
                # pending layer (its compute dominates)
                pl = pending["layer"]
                with tr.span("moe.compute", layer=pending["moe_idx"],
                             variant=pending["variant"], fused=True):
                    (xa, h2f, gates, eids, caches[idx],
                     pending["slab"].buffers) = self._jitted_fused(
                        pl["name"], layer["name"],
                        pending["variant"] == "compact")(
                            pl["params"]["ffn"], pl["lora"],
                            pending["slab"].buffers, pending["update"],
                            pending["maps"], pending["h2f"], pending["gates"],
                            pending["eids"], pending["xa"], layer["params"],
                            caches[idx], decode_pos)
                    _obs_sync(eids)
            else:
                x = self._finish_moe(pending)
                with tr.span("moe.pre", layer=layer["moe_idx"]):
                    if decode_pos is None:
                        xa, h2f, gates, eids, caches[idx] = self._jitted(
                            "pre_full", layer["name"])(
                                layer["params"], x, positions,
                                n_slots=self._n_slots)
                    else:
                        xa, h2f, gates, eids, caches[idx] = self._jitted(
                            "pre_dec", layer["name"])(
                                layer["params"], x, caches[idx], decode_pos)
                    _obs_sync(eids)
            pending = self._prep_moe(layer["moe_idx"], layer, xa, h2f,
                                     gates, eids)
        if pending is not None:
            x = self._finish_moe(pending)
        return x

    def _block_forward(self, layer: dict, x, positions, caches, idx, decode_pos=None):
        """One block, full-seq (decode_pos None) or single-step. Under
        ``impl="slab"`` the attn_moe blocks never reach this method —
        :meth:`_forward_layers_slab` handles them."""
        cfg, b = self.cfg, layer["spec"]
        p = layer["params"]
        tr = get_tracer()
        if b.kind == "mamba":
            with tr.span("engine.block", kind="mamba", idx=idx):
                if decode_pos is None:
                    x2, aux = apply_block_full(p, cfg, b, x, positions, self.rt,
                                               want_cache=True, cache_slots=0)
                    caches[idx] = aux["kv"]
                    return _obs_sync(x2)
                from ..models.mamba2 import apply_mamba_decode

                h = rms_norm(p["ln1"], x, cfg.norm_eps)
                y, caches[idx] = apply_mamba_decode(p["mixer"], h, caches[idx],
                                                    b.ssm)
                return _obs_sync(x + y)

        # attention part
        from ..models.attention import attend_full, cache_from_prefill, decode_attend

        # attention + norms of a MoE block count toward that layer's
        # "pre" compute; dense blocks get their own engine.block span
        if b.moe is not None:
            ctx = tr.span("moe.pre", layer=layer["moe_idx"])
        else:
            ctx = tr.span("engine.block", kind=b.kind, idx=idx)
        with ctx:
            h = rms_norm(p["ln1"], x, cfg.norm_eps)
            if decode_pos is None:
                y, (k, v) = attend_full(p["mixer"], b.attn, h, positions,
                                        b.attn.window, return_kv=True, rt=self.rt)
                caches[idx] = cache_from_prefill(k, v, b.attn, self._n_slots)
            else:
                y, caches[idx] = decode_attend(p["mixer"], b.attn, h, caches[idx],
                                               decode_pos, b.attn.window)
            x = x + y
            h2 = _obs_sync(rms_norm(p["ln2"], x, cfg.norm_eps))
        if b.moe is not None:
            y2, _ = self._moe_forward(layer["moe_idx"], layer, h2)
        else:
            with tr.span("engine.block", kind="ffn", idx=idx):
                y2 = _obs_sync(apply_mlp(p["ffn"], h2))
        return x + y2

    # ------------------------------------------------------------------
    def generate(self, prompt_tokens, max_new_tokens: int,
                 prefix_embed=None, *, quality: float = 1.0,
                 deadline_s: Optional[float] = None) -> dict:
        """Greedy decoding. prompt_tokens (B, T) int32. Returns dict with
        tokens, metrics, throughput (Eq. 3 model).

        ``quality`` (the per-request quality-vs-latency dial, needs a
        little bank) sets the fraction of cache misses served by the big
        expert: 1.0 = always exact, 0.0 = always the little distillate.
        ``deadline_s`` bounds this call's serial Eq.-3 seconds: past
        ``pressure_frac`` of the budget remaining misses go all-little,
        and once the budget is spent decoding stops early
        (``stopped_early`` in the result)."""
        t0 = time.perf_counter()
        tr = get_tracer()
        cfg = self.cfg
        plan = get_fault_plan()
        self._gen_step = 0
        self._step_quality = quality if self.little is not None else 1.0
        elapsed = 0.0  # serial Eq.-3 seconds of this call's steps
        stopped_early = False
        toks = jnp.asarray(prompt_tokens)
        B, T = toks.shape
        L_moe = len(self.moe_layer_ids)
        self._n_slots = T + max_new_tokens + (prefix_embed.shape[1] if prefix_embed is not None else 0)

        # prefill
        with tr.span("engine.prefill", batch=B, prompt_len=T, impl=self.impl):
            self.metrics.begin_step(L_moe)
            with tr.span("engine.embed"):
                x = _obs_sync(self._embed_fn(self.params_top, toks,
                                             prefix_embed))
            Tt = x.shape[1]
            positions = jnp.broadcast_to(jnp.arange(Tt), (B, Tt))
            caches: List[Any] = [None] * len(self.layers)
            if self.impl == "slab":
                x = self._forward_layers_slab(x, positions, caches)
            else:
                for idx, layer in enumerate(self.layers):
                    x = self._block_forward(layer, x, positions, caches, idx)
            self.metrics.add_flops(self._flops_per_token * B * Tt)
            with tr.span("engine.logits"):
                next_tok = self._next_tok_fn(self.params_top, x)
                jax.block_until_ready(next_tok)
        # like wall_time, per-generate-call (the other counters accumulate)
        self.metrics.prefill_wall_time = time.perf_counter() - t0
        elapsed += self.metrics.serial_span(self.hw,
                                            len(self.metrics.step_flops) - 1)

        out_tokens = [next_tok]
        pos = jnp.asarray(Tt, jnp.int32)
        for step in range(max_new_tokens - 1):
            if deadline_s is not None:
                if elapsed >= deadline_s:
                    stopped_early = True
                    break
                if (self.little is not None
                        and elapsed >= self.pressure_frac * deadline_s):
                    self._step_quality = 0.0  # deadline pressure
            if plan.enabled:
                plan.maybe_crash("engine.decode")
                frac = plan.eviction_storm()
                if frac:
                    self._apply_storm(frac)
            self._gen_step = step + 1
            with tr.span("engine.decode_step", step=step, batch=B,
                         impl=self.impl):
                self.metrics.begin_step(L_moe)
                with tr.span("engine.embed"):
                    x = _obs_sync(self._embed_fn(self.params_top, next_tok))
                if self.impl == "slab":
                    x = self._forward_layers_slab(x, positions, caches,
                                                  decode_pos=pos)
                else:
                    for idx, layer in enumerate(self.layers):
                        x = self._block_forward(layer, x, positions, caches, idx, decode_pos=pos)
                with tr.span("engine.logits"):
                    next_tok = _obs_sync(self._next_tok_fn(self.params_top, x))
                out_tokens.append(next_tok)
                pos = pos + 1
                self.metrics.decode_tokens += 1
                self.metrics.add_flops(self._flops_per_token * B)
            elapsed += self.metrics.serial_span(
                self.hw, len(self.metrics.step_flops) - 1)
        self.metrics.decode_tokens += 1
        self.metrics.wall_time = time.perf_counter() - t0
        self._step_quality = 1.0

        m = self.metrics
        m.host_time = (
            m.host_executed * (3 * 2 * cfg.d_model * self.moe_spec.d_ff) / self.hw.host_flops
        )
        return {
            "tokens": jnp.concatenate(out_tokens, axis=1),
            "metrics": m,
            "stopped_early": stopped_early,
            "cache_stats": self.cache.stats(),
            "transfers_per_layer": self.cache.transfers_per_layer(),
            "throughput_tok_s": m.throughput(self.hw, batch=B),
            "throughput_overlapped_tok_s": m.throughput(self.hw, batch=B, overlap=True),
            "modeled_time_s": m.modeled_time(self.hw),
            "modeled_time_overlapped_s": m.modeled_time_overlapped(self.hw),
        }
