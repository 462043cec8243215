"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.kernels

from repro.kernels.int4_matmul import int4_matmul, quantize_matmul_weight
from repro.kernels.int4_matmul.ref import dequant_ref, int4_matmul_ref
from repro.kernels.moe_gmm import gmm, gmm_ref
from repro.kernels.ssd_scan import ssd, ssd_scan_ref


# ---------------------------------------------------------------------------
# int4 dequant matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "M,K,N,group,bm,bn,bk",
    [
        (64, 128, 96, 32, 32, 32, 64),
        (256, 512, 256, 64, 128, 128, 512),
        (8, 256, 128, 64, 8, 128, 128),
        (128, 1024, 64, 128, 64, 64, 256),
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_int4_matmul_vs_ref(M, K, N, group, bm, bn, bk, dtype):
    x = jax.random.normal(jax.random.key(1), (M, K)).astype(dtype)
    w = jax.random.normal(jax.random.key(2), (K, N)) * 0.05
    qw = quantize_matmul_weight(w, group)
    ref = int4_matmul_ref(x, qw.packed, qw.scale, qw.zero, group)
    out = int4_matmul(x, qw.packed, qw.scale, qw.zero, group=group,
                      bm=bm, bn=bn, bk=bk, interpret=True)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


def test_int4_pack_roundtrip_and_quality():
    K, N, group = 256, 64, 64
    w = jax.random.normal(jax.random.key(0), (K, N)) * 0.1
    qw = quantize_matmul_weight(w, group)
    assert qw.packed.shape == (K // 2, N) and qw.packed.dtype == jnp.uint8
    wd = dequant_ref(qw.packed, qw.scale, qw.zero, group)
    err = float(jnp.abs(wd - w).mean())
    rng = float(w.max() - w.min())
    assert err < rng / 15  # better than one quantization bin on average


# ---------------------------------------------------------------------------
# grouped expert matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "E,M,K,N", [(4, 64, 128, 96), (8, 33, 256, 128), (2, 7, 64, 32), (1, 128, 512, 64)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_vs_ref(E, M, K, N, dtype):
    a = jax.random.normal(jax.random.key(0), (E, M, K)).astype(dtype)
    b = jax.random.normal(jax.random.key(1), (E, K, N)).astype(dtype)
    out = gmm(a, b, interpret=True)
    ref = gmm_ref(a, b)
    tol = 1e-4 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize(
    "E,M,K,N,sizes",
    [
        (4, 64, 128, 96, (0, 17, 64, 3)),
        (8, 33, 256, 128, (33, 0, 0, 5, 12, 33, 1, 0)),
        (2, 7, 64, 32, (0, 0)),
    ],
)
def test_gmm_ragged_group_sizes(E, M, K, N, sizes):
    """Ragged groups: rows >= sizes[e] are zero in a (the slot-dispatch
    contract); the kernel skips those tiles and must still match the
    dense reference on the full output."""
    a = jax.random.normal(jax.random.key(0), (E, M, K), jnp.float32)
    mask = (np.arange(M)[None, :] < np.asarray(sizes)[:, None])[..., None]
    a = a * mask
    b = jax.random.normal(jax.random.key(1), (E, K, N), jnp.float32)
    out = gmm(a, b, interpret=True, group_sizes=jnp.asarray(sizes, jnp.int32))
    ref = gmm_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4,
                               rtol=1e-4)
    # the zeroed tail of every group stays exactly zero in the output
    for e, s in enumerate(sizes):
        assert not np.asarray(out)[e, s:].any()


# ---------------------------------------------------------------------------
# SSD chunked scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "B,T,H,P,N,chunk",
    [(2, 64, 3, 16, 8, 16), (1, 128, 2, 32, 16, 32), (3, 96, 1, 8, 4, 32)],
)
def test_ssd_vs_sequential_ref(B, T, H, P, N, chunk):
    x = jax.random.normal(jax.random.key(2), (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(3), (B, T, H)))
    A = -jnp.exp(jax.random.normal(jax.random.key(4), (H,)) * 0.3)
    Bm = jax.random.normal(jax.random.key(5), (B, T, N)) * 0.5
    Cm = jax.random.normal(jax.random.key(6), (B, T, N)) * 0.5
    y, fin = ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, fr = ssd_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=5e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(fr), atol=5e-4, rtol=1e-3)


def test_ssd_state_isolation_across_batch_heads():
    """The VMEM-carried state must reset between (batch, head) programs."""
    B, T, H, P, N = 2, 32, 2, 8, 4
    x = jnp.zeros((B, T, H, P)).at[0].set(
        jax.random.normal(jax.random.key(7), (T, H, P)) * 3
    )
    dt = jax.nn.softplus(jnp.ones((B, T, H)))
    A = -jnp.ones((H,))
    Bm = jnp.ones((B, T, N)) * 0.3
    Cm = jnp.ones((B, T, N)) * 0.3
    y, fin = ssd(x, dt, A, Bm, Cm, chunk=8, interpret=True)
    # batch 1 has zero input -> zero output and zero final state
    assert float(jnp.abs(y[1]).max()) == 0.0
    assert float(jnp.abs(fin[1]).max()) == 0.0


# ---------------------------------------------------------------------------
# Flash attention (forward)
# ---------------------------------------------------------------------------

from repro.kernels.flash_attn import attention_ref, flash


@pytest.mark.parametrize(
    "B,T,Hkv,G,hd,cap,win,bq,bk",
    [
        (2, 64, 2, 2, 16, None, None, 16, 16),
        (1, 128, 1, 4, 32, 50.0, None, 32, 32),
        (2, 96, 2, 1, 16, None, 32, 32, 16),
        (1, 64, 2, 2, 16, 30.0, 24, 16, 16),
    ],
)
def test_flash_attn_vs_ref(B, T, Hkv, G, hd, cap, win, bq, bk):
    q = jax.random.normal(jax.random.key(0), (B, T, Hkv, G, hd))
    k = jax.random.normal(jax.random.key(1), (B, T, Hkv, hd))
    v = jax.random.normal(jax.random.key(2), (B, T, Hkv, hd))
    out = flash(q, k, v, softcap=cap, window=win, bq=bq, bk=bk, interpret=True)
    ref = attention_ref(q, k, v, softcap=cap, window=win)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("hd,hv,scale", [(192, 128, 0.114721), (24, 16, None)])
def test_flash_attn_value_width_and_scale_vs_ref(hd, hv, scale):
    """Latent attention's prefill: q/k wider than v, an explicit scale."""
    q = jax.random.normal(jax.random.key(0), (1, 40, 2, 1, hd))
    k = jax.random.normal(jax.random.key(1), (1, 40, 2, hd))
    v = jax.random.normal(jax.random.key(2), (1, 40, 2, hv))
    out = flash(q, k, v, bq=16, bk=16, interpret=True, scale=scale)
    ref = attention_ref(q, k, v, scale=scale)
    assert out.shape == (1, 40, 2, 1, hv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


def test_flash_attn_default_scale_is_unchanged_at_equal_widths():
    q = jax.random.normal(jax.random.key(0), (2, 64, 2, 2, 16))
    k = jax.random.normal(jax.random.key(1), (2, 64, 2, 16))
    v = jax.random.normal(jax.random.key(2), (2, 64, 2, 16))
    plain = flash(q, k, v, bq=16, bk=16, interpret=True)
    named = flash(q, k, v, bq=16, bk=16, interpret=True, scale=16**-0.5)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(named))
    np.testing.assert_allclose(np.asarray(plain),
                               np.asarray(attention_ref(q, k, v)), atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# Latent-attention decode
# ---------------------------------------------------------------------------

from repro.kernels.mla_decode import mla_decode_pallas, mla_decode_ref


@pytest.mark.parametrize("S,pos", [(24, [0, 7, 23]), (1536, [3, 600, 1535]),
                                   (1024, [1100, 511, 512])],
                         ids=["one_block", "ragged", "wrapped"])
def test_mla_decode_vs_ref(S, pos):
    """Rows at different positions (a block boundary, the last slot, and
    one past the cache's length, which reads every slot), with unwritten
    slots (``slot_pos`` -1) among the positions a row reads."""
    B, H, R, P = 3, 4, 32, 8
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (B, H, R))
    q_pe = jax.random.normal(ks[1], (B, H, P))
    c_kv = jax.random.normal(ks[2], (B, S, R))
    k_pe = jax.random.normal(ks[3], (B, S, P))
    pos = jnp.asarray(pos, jnp.int32)
    at = jnp.arange(S)[None]
    written = jnp.where(pos[:, None] < S, at, pos[:, None] - (pos[:, None] - at) % S)
    slot_pos = jnp.where(written <= pos[:, None], written, -1).astype(jnp.int32)
    slot_pos = slot_pos.at[:, 1].set(-1)  # a slot never written
    # what a row must not read: past its position, and the unwritten slot
    c_kv = jnp.where((slot_pos < 0)[..., None], 1e4, c_kv)
    out = mla_decode_pallas(q, q_pe, c_kv, k_pe, slot_pos, pos, scale=0.3,
                            interpret=True)
    ref = mla_decode_ref(q, q_pe, c_kv, k_pe, slot_pos, pos, scale=0.3)
    assert out.shape == (B, H, R) and float(jnp.abs(ref).max()) < 10
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)
