"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops

R = np.random.default_rng(0)


def relerr(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return np.max(np.abs(a - b)) / (np.abs(b).max() + 1e-6)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,L,S,hd", [
    (1, 2, 1, 128, 128, 64),
    (2, 4, 2, 256, 256, 64),
    (1, 8, 8, 128, 384, 128),   # MHA, rectangular
    (2, 4, 1, 128, 128, 128),   # MQA
])
@pytest.mark.parametrize("kwargs", [
    dict(causal=True),
    dict(causal=True, window=64),
    dict(causal=True, softcap=30.0),
    dict(causal=False),
])
def test_flash_attention_sweep(dtype, B, Hq, Hkv, L, S, hd, kwargs):
    q = jnp.asarray(R.normal(size=(B * Hq, L, hd)), dtype)
    k = jnp.asarray(R.normal(size=(B * Hkv, S, hd)), dtype)
    v = jnp.asarray(R.normal(size=(B * Hkv, S, hd)), dtype)
    a = ops.flash_attention(q, k, v, n_q_heads=Hq, n_kv_heads=Hkv,
                            bq=128, bk=128, **kwargs)
    b = ops.flash_attention(q, k, v, n_q_heads=Hq, n_kv_heads=Hkv,
                            impl="ref", **kwargs)
    assert relerr(a, b) < TOL[dtype], kwargs


def test_flash_attention_q_offset_decodelike():
    B, Hq, Hkv, L, S, hd = 1, 2, 2, 128, 256, 64
    q = jnp.asarray(R.normal(size=(B * Hq, L, hd)), jnp.float32)
    k = jnp.asarray(R.normal(size=(B * Hkv, S, hd)), jnp.float32)
    v = jnp.asarray(R.normal(size=(B * Hkv, S, hd)), jnp.float32)
    a = ops.flash_attention(q, k, v, n_q_heads=Hq, n_kv_heads=Hkv, q_offset=128)
    b = ops.flash_attention(q, k, v, n_q_heads=Hq, n_kv_heads=Hkv, q_offset=128,
                            impl="ref")
    assert relerr(a, b) < 2e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,S,hd,window", [
    (2, 4, 2, 256, 64, 0),
    (1, 8, 1, 128, 128, 0),
    (2, 4, 4, 256, 64, 64),
    (3, 2, 2, 384, 128, 128),
])
def test_decode_attention_sweep(dtype, B, Hq, Hkv, S, hd, window):
    q = jnp.asarray(R.normal(size=(B, Hq, hd)), dtype)
    kc = jnp.asarray(R.normal(size=(B, S, Hkv, hd)), dtype)
    vc = jnp.asarray(R.normal(size=(B, S, Hkv, hd)), dtype)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
    pos = jnp.where(pos < S - 40, pos, -1)  # empty tail slots
    cur = jnp.int32(S - 41)
    a = ops.decode_attention(q, kc, vc, pos, cur, n_q_heads=Hq, n_kv_heads=Hkv,
                             window=window, bs=128)
    b = ops.decode_attention(q, kc, vc, pos, cur, n_q_heads=Hq, n_kv_heads=Hkv,
                             window=window, impl="ref")
    assert relerr(a, b) < TOL[dtype]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("G,M,K,N", [
    (2, 128, 512, 128),
    (4, 256, 256, 256),
    (8, 128, 1024, 128),
])
def test_grouped_matmul_sweep(dtype, G, M, K, N):
    x = jnp.asarray(R.normal(size=(G, M, K)), dtype)
    w = jnp.asarray(R.normal(size=(G, K, N)), dtype)
    a = ops.grouped_matmul(x, w, bm=128, bn=128, bk=256)
    b = ops.grouped_matmul(x, w, impl="ref")
    assert relerr(a, b) < TOL[dtype] * np.sqrt(K)


@pytest.mark.parametrize("B,L,W,bl,bw", [
    (1, 256, 256, 128, 128),
    (2, 512, 512, 256, 512),
    (3, 128, 384, 128, 128),
])
def test_rg_lru_sweep(B, L, W, bl, bw):
    a_ = jnp.asarray(R.uniform(0.2, 0.999, size=(B, L, W)), jnp.float32)
    b_ = jnp.asarray(R.normal(size=(B, L, W)), jnp.float32)
    out = ops.rg_lru(a_, b_, bl=bl, bw=bw)
    ref = ops.rg_lru(a_, b_, impl="ref")
    assert relerr(out, ref) < 1e-4


@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 24), k=st.integers(1, 4), p_log=st.integers(6, 10),
       seed=st.integers(0, 99))
def test_time_flow_lookup_property(n, k, p_log, seed):
    """Random tables with the contiguous-valid-slot invariant: kernel output
    is bit-identical to the oracle."""
    rng = np.random.default_rng(seed)
    P = 2 ** p_log
    nv = rng.integers(0, k + 1, size=(n, n))
    tbl_n = np.full((n, n, k), -1, np.int32)
    tbl_d = np.zeros((n, n, k), np.int32)
    for i in range(n):
        for j in range(n):
            tbl_n[i, j, :nv[i, j]] = rng.integers(0, n, nv[i, j])
            tbl_d[i, j, :nv[i, j]] = rng.integers(0, 8, nv[i, j])
    node = rng.integers(0, n, P).astype(np.int32)
    dst = rng.integers(0, n, P).astype(np.int32)
    h = rng.integers(0, 2 ** 31, P).astype(np.uint32)
    args = [jnp.asarray(x) for x in (tbl_n, tbl_d, node, dst, h)]
    an, ad = ops.time_flow_lookup(*args, bp=min(P, 256), interpret=True)
    bn, bd = ops.time_flow_lookup(*args, impl="ref")
    assert (np.asarray(an) == np.asarray(bn)).all()
    assert (np.asarray(ad) == np.asarray(bd)).all()
