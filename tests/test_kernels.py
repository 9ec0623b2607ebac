"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in the TPU
interpreter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_fwd_pallas
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.gating_topk.ops import gating_topk
from repro.kernels.gating_topk.ref import gating_topk_ref
from repro.kernels.grouped_gemm.kernel import grouped_matmul_pallas
from repro.kernels.grouped_gemm.ops import grouped_matmul
from repro.kernels.grouped_gemm.ref import grouped_matmul_ref
from repro.kernels.ssd_scan.ops import ssd_chunk_scan
from repro.kernels.ssd_scan.ref import ssd_chunk_ref

pytestmark = pytest.mark.usefixtures("tpu_interpret")


@pytest.mark.parametrize("G,M,K,N", [
    (1, 128, 128, 128),
    (4, 128, 256, 128),
    (2, 256, 384, 512),
    (8, 8, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_gemm_sweep(G, M, K, N, dtype):
    kx = jax.random.PRNGKey(0)
    kw = jax.random.PRNGKey(1)
    x = jax.random.normal(kx, (G, M, K), dtype)
    w = jax.random.normal(kw, (G, K, N), dtype)
    out = grouped_matmul_pallas(x, w, bm=min(128, M))
    ref = grouped_matmul_ref(x, w)
    tol = 1e-4 if dtype == jnp.float32 else 2e-1
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_grouped_gemm_padding_wrapper():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 100, 200))
    w = jax.random.normal(jax.random.PRNGKey(1), (2, 200, 300))
    out = grouped_matmul(x, w)
    ref = grouped_matmul_ref(x, w)
    np.testing.assert_allclose(np.array(out), np.array(ref), rtol=1e-4,
                               atol=1e-3)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S,d,bq,bk", [
    (256, 64, 128, 128),
    (512, 128, 128, 256),
    (384, 64, 128, 128),
])
def test_flash_sweep(causal, S, d, bq, bk):
    if S % bq or S % bk:
        pytest.skip("blocks must divide")
    B, H = 2, 2
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, d))
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, d)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, d)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, d)
    out = flash_fwd_pallas(qf, kf, vf, causal=causal, bq=bq, bk=bk)
    ref = attention_ref(q, k, v, causal=causal)
    ref = ref.transpose(0, 2, 1, 3).reshape(B * H, S, d)
    np.testing.assert_allclose(np.array(out), np.array(ref), rtol=1e-4,
                               atol=1e-4)


def test_flash_gqa_wrapper():
    B, S, H, Hkv, d = 2, 256, 4, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, d))
    out = flash_attention(q, k, v, causal=True, bq=128, bk=128)
    kr = jnp.repeat(k, H // Hkv, 2)
    vr = jnp.repeat(v, H // Hkv, 2)
    ref = attention_ref(q, kr, vr, causal=True)
    np.testing.assert_allclose(np.array(out), np.array(ref), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("B,nc,Q,H,P,N", [
    (1, 2, 16, 2, 8, 16),
    (2, 4, 32, 4, 16, 8),
])
def test_ssd_scan_sweep(B, nc, Q, H, P, N):
    key = jax.random.PRNGKey(0)
    xs = jax.random.normal(key, (B, nc, Q, H, P)) * 0.5
    Bm = jax.random.normal(jax.random.PRNGKey(1), (B, nc, Q, H, N)) * 0.5
    Cm = jax.random.normal(jax.random.PRNGKey(2), (B, nc, Q, H, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(3),
                                           (B, nc, Q, H)))
    da = -dt * 0.4
    y, fin = ssd_chunk_scan(xs, Bm, Cm, dt, da)
    y_ref, fin_ref = ssd_chunk_ref(xs, Bm, Cm, dt, da)
    np.testing.assert_allclose(np.array(y), np.array(y_ref), rtol=3e-4,
                               atol=3e-4)
    np.testing.assert_allclose(np.array(fin), np.array(fin_ref), rtol=3e-4,
                               atol=3e-4)


def test_ssd_scan_initial_state():
    B, nc, Q, H, P, N = 1, 2, 8, 2, 4, 8
    key = jax.random.PRNGKey(0)
    xs = jax.random.normal(key, (B, nc, Q, H, P)) * 0.5
    Bm = jax.random.normal(jax.random.PRNGKey(1), (B, nc, Q, H, N)) * 0.5
    Cm = jax.random.normal(jax.random.PRNGKey(2), (B, nc, Q, H, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(3),
                                           (B, nc, Q, H)))
    da = -dt * 0.4
    s0 = jax.random.normal(jax.random.PRNGKey(4), (B, H, N, P))
    y, fin = ssd_chunk_scan(xs, Bm, Cm, dt, da, initial_state=s0)
    y_ref, fin_ref = ssd_chunk_ref(xs, Bm, Cm, dt, da, initial_state=s0)
    np.testing.assert_allclose(np.array(y), np.array(y_ref), rtol=3e-4,
                               atol=3e-4)


@pytest.mark.parametrize("score_fn", ["softmax", "sigmoid"])
@pytest.mark.parametrize("T,E,k", [(256, 32, 2), (512, 128, 8), (96, 16, 4)])
def test_gating_topk_sweep(score_fn, T, E, k):
    logits = jax.random.normal(jax.random.PRNGKey(0), (T, E))
    ids, w, cnt = gating_topk(logits, k, score_fn=score_fn, bt=64)
    ids_r, w_r, cnt_r = gating_topk_ref(logits, k, score_fn=score_fn)
    assert np.array_equal(np.array(ids), np.array(ids_r))
    np.testing.assert_allclose(np.array(w), np.array(w_r), rtol=1e-5,
                               atol=1e-6)
    assert np.array_equal(np.array(cnt), np.array(cnt_r))


def test_grouped_ffn_kernel_path_matches_einsum():
    from repro.moe.expert import grouped_ffn

    G, C, D, F = 2, 128, 128, 256
    xs = jax.random.normal(jax.random.PRNGKey(0), (G, C, D))
    valid = jnp.arange(C)[None, :] < jnp.array([[100], [128]])
    w1 = jax.random.normal(jax.random.PRNGKey(1), (G, D, F)) * 0.05
    w3 = jax.random.normal(jax.random.PRNGKey(2), (G, D, F)) * 0.05
    w2 = jax.random.normal(jax.random.PRNGKey(3), (G, F, D)) * 0.05
    out_k = grouped_ffn(xs, valid, w1, w3, w2, use_kernel=True)
    out_e = grouped_ffn(xs, valid, w1, w3, w2, use_kernel=False)
    np.testing.assert_allclose(np.array(out_k), np.array(out_e), rtol=1e-4,
                               atol=1e-4)
