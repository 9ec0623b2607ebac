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
from repro.kernels.grouped_gemm.ops import grouped_ffn
from repro.kernels.grouped_gemm.ref import grouped_ffn_ref
from repro.kernels.ssd_scan.ops import ssd_chunk_scan
from repro.kernels.ssd_scan.ref import ssd_chunk_ref

pytestmark = pytest.mark.usefixtures("tpu_interpret")


def _slot_buffers(counts, C, D, F, dtype, seed=0):
    """Front-packed slot buffers (zero past each count) and weights."""
    counts = jnp.asarray(counts, jnp.int32)
    G = counts.shape[0]
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    valid = jnp.arange(C)[None, :] < counts[:, None]
    xs = jnp.where(valid[..., None],
                   jax.random.normal(ks[0], (G, C, D), dtype), 0)
    w1 = (jax.random.normal(ks[1], (G, D, F)) * D ** -0.5).astype(dtype)
    w3 = (jax.random.normal(ks[2], (G, D, F)) * D ** -0.5).astype(dtype)
    w2 = (jax.random.normal(ks[3], (G, F, D)) * F ** -0.5).astype(dtype)
    return counts, valid, xs, w1, w3, w2


def _routed_counts(pairs, G, C, seed):
    """``pairs`` routed over G slots with a skew, each at most C."""
    r = np.random.default_rng(seed)
    p = np.exp(r.normal(0, 0.8, G))
    return np.minimum(r.multinomial(pairs, p / p.sum()), C)


# (counts, C, D, F, max_rows, layers): counts of 0, 1, past one row tile,
# not a multiple of it and exactly C; every slot empty; two replica slots
# with zero rows under decode's bound; decode's cap_slot 8; GLM-like 130
# and qwen3-like 34 slots at small D and F (F tiles 3 x 128 and 3 x 512),
# the latter's weights one layer of a scanned segment's 4-layer stacks.
_FFN_CASES = {
    "counts": ([0, 1, 130, 256, 77], 256, 128, 256, None, None),
    "empty": ([0, 0, 0], 24, 128, 128, None, None),
    "replicas": ([0, 0], 8, 128, 128, 32, None),
    "decode": ([0, 3, 8, 1, 0, 8], 8, 128, 256, 20, None),
    "glm-like": (_routed_counts(48, 130, 16, 1), 16, 128, 384, 48, None),
    "qwen3-like": (_routed_counts(40, 34, 16, 2), 16, 128, 1536, 40, 4),
}


@pytest.mark.parametrize("case", sorted(_FFN_CASES))
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)])
def test_counted_ffn_matches_dense(case, dtype, tol):
    """The count-aware kernel equals the dense einsum on every filled row,
    and counts as run the filled row tiles alone."""
    counts, C, D, F, max_rows, layers = _FFN_CASES[case]
    counts, valid, xs, w1, w3, w2 = _slot_buffers(counts, C, D, F, dtype)
    ref = grouped_ffn_ref(xs, w1, w3, w2)
    layer = None
    if layers is not None:
        # The weights as layer 2 of stacks whose other layers differ.
        layer = jnp.asarray(2, jnp.int32)
        w1, w3, w2 = (jnp.stack([w * (i + 0.5) for i in range(layers)])
                      .at[2].set(w) for w in (w1, w3, w2))
    out, rows = grouped_ffn(xs, counts, w1, w3, w2, max_rows=max_rows,
                            layer=layer)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    bm = min(C, 128)
    assert int(rows) == bm * int(((counts + bm - 1) // bm).sum())
    got = np.where(valid[..., None], np.asarray(out, np.float32), 0)
    np.testing.assert_allclose(got, np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_counted_ffn_gradients_are_the_dense_paths():
    """The kernel's custom VJP gives the dense formulation's gradients for
    x, w1, w3 and w2 under a loss that reads only the filled rows."""
    counts, valid, xs, w1, w3, w2 = _slot_buffers([0, 5, 24, 13], 24, 128,
                                                  256, jnp.float32, seed=4)
    mask = valid[..., None]

    def loss(ffn):
        return lambda x, a, b, c: (jnp.where(mask, ffn(x, a, b, c), 0)
                                   ** 2).sum()

    counted = loss(lambda *a: grouped_ffn(a[0], counts, *a[1:])[0])
    args = (xs, w1, w3, w2)
    got = jax.grad(counted, argnums=(0, 1, 2, 3))(*args)
    want = jax.grad(loss(grouped_ffn_ref), argnums=(0, 1, 2, 3))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-5)


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
