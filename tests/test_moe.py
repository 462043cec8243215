"""MoE dispatch: capacity semantics + equivalence with a dense oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline container: deterministic local fallback
    from _hypothesis_shim import given, settings, strategies as st

from repro.configs.base import MoESpec
from repro.models.moe import (
    apply_moe_local,
    combine_tokens,
    dispatch_tokens,
    init_moe,
    make_dispatch,
    router_probs,
    top_k_route,
)
from repro.models.runtime import Runtime


def dense_oracle(params, x, spec, gates, eids):
    """y_n = sum_k gate_{nk} E_{e_{nk}}(x_n) with NO capacity drops."""
    from repro.models.common import silu

    outs = []
    for e in range(spec.num_experts):
        h = silu(x @ params["wg"][e]) * (x @ params["wu"][e])
        outs.append(h @ params["wd"][e])
    stack = jnp.stack(outs)  # (E, N, d)
    y = jnp.zeros_like(x)
    for k in range(spec.top_k):
        y = y + gates[:, k : k + 1] * jnp.take_along_axis(
            stack, eids[:, k][None, :, None], axis=0
        )[0]
    return y


def test_local_moe_matches_dense_oracle_zero_drop():
    spec = MoESpec(num_experts=8, top_k=2, d_ff=32)
    d = 16
    params = init_moe(jax.random.key(0), d, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (24, d))
    probs = router_probs(params, x, spec)
    gates, eids = top_k_route(probs, spec.top_k)
    y, _ = apply_moe_local(params, x, spec, Runtime(zero_drop=True), probs=probs)
    ref = dense_oracle(params, x, spec, gates, eids)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4, rtol=1e-3)


def test_shared_expert_added():
    spec = MoESpec(num_experts=4, top_k=1, d_ff=16, num_shared=2, shared_d_ff=32)
    d = 8
    params = init_moe(jax.random.key(0), d, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (6, d))
    y, _ = apply_moe_local(params, x, spec, Runtime(zero_drop=True))
    # zero out shared weights -> output changes
    p2 = dict(params, shared=jax.tree.map(jnp.zeros_like, params["shared"]))
    y2, _ = apply_moe_local(p2, x, spec, Runtime(zero_drop=True))
    assert float(jnp.abs(y - y2).max()) > 1e-5


@given(st.integers(0, 100), st.integers(1, 4), st.integers(1, 8))
@settings(max_examples=20, deadline=None)
def test_dispatch_positions_unique_and_bounded(seed, K, cap):
    E, N = 8, 16
    spec = MoESpec(num_experts=E, top_k=K, d_ff=8)
    probs = jax.nn.softmax(jax.random.normal(jax.random.key(seed), (N, E)), -1)
    gates, eids = top_k_route(probs, K)
    d = make_dispatch(gates, eids, spec, cap)
    kept = np.asarray(d.eids) < E
    pos = np.asarray(d.pos)
    assert (pos[kept] < cap).all()
    # (expert, slot) pairs of kept assignments are unique
    pairs = list(zip(np.asarray(d.eids)[kept], pos[kept]))
    assert len(pairs) == len(set(pairs))
    # dropped assignments have zero gate
    assert (np.asarray(d.gates)[~kept] == 0).all()


def test_capacity_drop_loses_lowest_priority():
    """Tokens are dispatched in order; overflow drops later tokens."""
    spec = MoESpec(num_experts=2, top_k=1, d_ff=4)
    # all 4 tokens pick expert 0
    gates = jnp.ones((4, 1))
    eids = jnp.zeros((4, 1), jnp.int32)
    d = make_dispatch(gates, eids, spec, cap=2)
    kept = np.asarray(d.eids)[:, 0] < 2
    assert kept.tolist() == [True, True, False, False]


def test_dispatch_combine_roundtrip_identity():
    """dispatch + identity expert + combine == gate-scaled input sum."""
    spec = MoESpec(num_experts=4, top_k=2, d_ff=4)
    N, dm = 8, 6
    x = jax.random.normal(jax.random.key(0), (N, dm))
    probs = jax.nn.softmax(jax.random.normal(jax.random.key(1), (N, spec.num_experts)), -1)
    gates, eids = top_k_route(probs, spec.top_k)
    d = make_dispatch(gates, eids, spec, cap=N)
    buf = dispatch_tokens(d, x, spec.num_experts)
    y = combine_tokens(d, buf)
    ref = x * gates.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5, rtol=1e-4)


def test_lora_delta_changes_expert_output():
    spec = MoESpec(num_experts=4, top_k=2, d_ff=16)
    d = 8
    params = init_moe(jax.random.key(0), d, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (10, d))
    rt = Runtime(zero_drop=True)
    y0, _ = apply_moe_local(params, x, spec, rt)
    lora = {
        "wu": {"a": jax.random.normal(jax.random.key(2), (4, d, 2)) * 0.1,
               "b": jax.random.normal(jax.random.key(3), (4, 2, 16)) * 0.1},
        "wd": {"a": jnp.zeros((4, 16, 2)), "b": jnp.zeros((4, 2, d))},
    }
    y1, _ = apply_moe_local(params, x, spec, rt, lora=lora, lora_scale=1.0)
    assert float(jnp.abs(y0 - y1).max()) > 1e-6
    # zero adapters are exactly a no-op
    zl = jax.tree.map(jnp.zeros_like, lora)
    y2, _ = apply_moe_local(params, x, spec, rt, lora=zl, lora_scale=1.0)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y2), atol=1e-6)


# ---------------------------------------------------------------------------
# One chip's share of the experts
# ---------------------------------------------------------------------------


def _share(params, spec, first, held):
    """The layer as the chip holding experts ``first .. first+held-1``
    runs it: its own experts, and the router's outputs renumbered so that
    they come first (the router keeps every output)."""
    from dataclasses import replace

    p = dict(params, router=jnp.roll(params["router"], -first, axis=1),
             **{w: params[w][first:first + held] for w in ("wg", "wu", "wd")})
    return p, replace(spec, num_held=held)


@pytest.mark.parametrize("backend", ["ref", "auto"])
def test_two_expert_shares_add_up_to_the_uncut_layer(backend):
    """Experts 0-3 on one chip and 4-7 on the other, each routing over all
    8 with top-3: their parts, with the shared expert that both run
    counted once, add up to the layer holding all 8."""
    spec = MoESpec(num_experts=8, top_k=3, d_ff=16, num_shared=1, shared_d_ff=16)
    d = 12
    params = init_moe(jax.random.key(0), d, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (20, d))
    rt = Runtime(zero_drop=True, kernel_backend=backend)
    whole, _ = apply_moe_local(params, x, spec, rt)
    from repro.models.mlp import apply_mlp

    shared = apply_mlp(params["shared"], x)
    parts = [apply_moe_local(p, x, s, rt)[0]
             for p, s in (_share(params, spec, first, 4) for first in (0, 4))]
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] - shared),
                               np.asarray(whole), atol=1e-5, rtol=1e-4)
    # each share alone misses what the other chip's experts add
    assert float(jnp.abs(parts[0] - whole).max()) > 1e-3


def test_every_expert_held_traces_no_share_mask():
    """With every expert held the layer builds the computation it built
    before shares existed: naming the count changes nothing, and only a
    share adds the comparison with the held count and the two selects
    that drop other chips' routes."""
    from collections import Counter
    from dataclasses import replace

    spec = MoESpec(num_experts=8, top_k=2, d_ff=16)
    d = 12
    params = init_moe(jax.random.key(0), d, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (6, d))
    rt = Runtime(zero_drop=True)

    def jaxpr(s, p):
        return jax.make_jaxpr(lambda p, x: apply_moe_local(p, x, s, rt))(p, x)

    def prims(j):  # primitives, inside nested jits too
        out = Counter()
        for e in j.jaxpr.eqns:
            out[e.primitive.name] += 1
            if "jaxpr" in e.params:
                out += prims(e.params["jaxpr"])
        return out

    full = jaxpr(spec, params)
    assert str(jaxpr(replace(spec, num_held=8), params)) == str(full)
    p4, s4 = _share(params, spec, 0, 4)
    share = prims(jaxpr(s4, p4))
    extra = share - prims(full)
    assert extra["lt"] == 1 and extra["select_n"] == 2
    assert not prims(full) - share


def test_the_sharded_path_refuses_an_expert_share():
    from repro.models.moe import apply_moe_sharded

    spec = MoESpec(num_experts=8, top_k=2, d_ff=16, num_held=4)
    params = init_moe(jax.random.key(0), 12, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (6, 12))
    with pytest.raises(NotImplementedError, match="expert share"):
        apply_moe_sharded(params, x, spec, Runtime(zero_drop=True))
