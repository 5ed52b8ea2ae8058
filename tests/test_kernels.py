"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

# deliberately NOT marked slow: op-level interpret-mode checks run in
# seconds, and the CI backend-parity lane gates PRs on exactly this file


def _mk(seed, shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,K,W1,H,KV,hd,S,bs",
    [(1, 1, 1, 1, 1, 16, 32, 16),     # degenerate: plain decode
     (2, 3, 4, 4, 2, 32, 64, 32),     # GQA
     (1, 5, 3, 8, 1, 64, 128, 64),    # MQA
     (2, 2, 6, 4, 4, 32, 96, 32),     # MHA, 3 blocks
     (1, 25, 4, 4, 2, 32, 64, 64)])   # paper-scale k
def test_spec_attention_sweep(B, K, W1, H, KV, hd, S, bs, dtype):
    q = _mk(0, (B, K, W1, H, hd), dtype)
    kc = _mk(1, (B, S, KV, hd), dtype)
    vc = _mk(2, (B, S, KV, hd), dtype)
    kt = _mk(3, (B, K, W1, KV, hd), dtype)
    vt = _mk(4, (B, K, W1, KV, hd), dtype)
    cur = jnp.asarray(np.random.default_rng(0).integers(0, S + 1, B),
                      jnp.int32)
    out = ops.spec_attention_op(q, kc, vc, kt, vt, cur, w1=W1, block_s=bs,
                                interpret=True)
    want = ops.spec_attention_ref_op(q, kc, vc, kt, vt, cur, w1=W1)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_spec_attention_empty_cache():
    """cur_len == 0: only the tail (incl. the leading token) attends."""
    B, K, W1, H, KV, hd, S = 1, 2, 3, 2, 1, 16, 32
    q = _mk(0, (B, K, W1, H, hd), jnp.float32)
    kc = jnp.zeros((B, S, KV, hd))
    vc = jnp.zeros((B, S, KV, hd))
    kt = _mk(1, (B, K, W1, KV, hd), jnp.float32)
    vt = _mk(2, (B, K, W1, KV, hd), jnp.float32)
    cur = jnp.zeros((B,), jnp.int32)
    out = ops.spec_attention_op(q, kc, vc, kt, vt, cur, w1=W1, block_s=32,
                                interpret=True)
    want = ops.spec_attention_ref_op(q, kc, vc, kt, vt, cur, w1=W1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("q,w,L", [(1, 3, 64), (2, 5, 128), (3, 8, 256),
                                   (1, 1, 32), (2, 5, 1100), (3, 8, 2500),
                                   (1, 130, 1500), (2, 5, 2048)])
def test_ngram_match_sweep(q, w, L):
    """One tile and several, a padded tail tile, an L of whole tiles, and
    windows that cross a lane row (s > 128) all match the oracle bit for
    bit."""
    rng = np.random.default_rng(q * 100 + w)
    B = 2
    buf = jnp.asarray(rng.integers(0, 6, (B, L)), jnp.int32)
    qs = rng.integers(0, L - q)
    query = buf[:, qs:qs + q]
    cur = jnp.asarray(rng.integers(q, L + 1, B), jnp.int32)
    m, h = ops.ngram_match_op(buf, query, cur, w=w, interpret=True)
    bufp = jnp.concatenate([buf, jnp.full((B, q + w), -1, jnp.int32)], 1)
    m_r, h_r = jax.vmap(lambda b, qq, c: ref.ngram_match_ref(
        b, qq, c[None], w=w))(bufp, query, cur)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(m_r))
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h_r))


def test_ngram_match_agrees_with_drafter_hash():
    """Kernel hash must equal the drafter's jnp hash (same constants)."""
    from repro.core.drafters import _gram_matrix, _hash_rows
    L, q, w = 64, 2, 3
    rng = np.random.default_rng(3)
    buf = jnp.asarray(rng.integers(0, 5, (1, L)), jnp.int32)
    query = buf[:, 10:12]
    cur = jnp.asarray([50], jnp.int32)
    m, h = ops.ngram_match_op(buf, query, cur, w=w, interpret=True)
    grams = _gram_matrix(buf[0], q + w)
    h_drafter = _hash_rows(grams[:, q:])
    np.testing.assert_array_equal(np.asarray(h[0, :grams.shape[0]]),
                                  np.asarray(h_drafter))


@pytest.mark.parametrize("Bt,T,di,ds,chunk,bd",
                         [(2, 32, 16, 4, 8, 8), (1, 64, 32, 16, 16, 32),
                          (2, 16, 8, 2, 16, 8)])
def test_mamba_scan_kernel_sweep(Bt, T, di, ds, chunk, bd):
    ks = jax.random.split(jax.random.PRNGKey(T + di), 6)
    u = jax.random.normal(ks[0], (Bt, T, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, T, di)))
    A = -jnp.exp(jax.random.normal(ks[2], (di, ds)) * 0.3)
    B = jax.random.normal(ks[3], (Bt, T, ds))
    C = jax.random.normal(ks[4], (Bt, T, ds))
    D = jnp.ones((di,))
    h0 = jax.random.normal(ks[5], (Bt, di, ds))
    y_k, h_k = ops.mamba_scan_op(u, dt, A, B, C, D, h0, chunk=chunk,
                                 block_d=bd, interpret=True)
    y_r, h_r = ref.mamba_scan_ref(u, dt, A, B, C, D, h0)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                               rtol=2e-4, atol=2e-4)


def test_mamba_scan_kernel_matches_model_layer():
    """Kernel output == the model's selective_scan (the production path)."""
    from repro.models.mamba import selective_scan
    Bt, T, di, ds = 1, 32, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    u = jax.random.normal(ks[0], (Bt, T, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, T, di)))
    A = -jnp.exp(jax.random.normal(ks[2], (di, ds)) * 0.3)
    B = jax.random.normal(ks[3], (Bt, T, ds))
    C = jax.random.normal(ks[4], (Bt, T, ds))
    D = jnp.ones((di,))
    h0 = jnp.zeros((Bt, di, ds))
    y_k, h_k = ops.mamba_scan_op(u, dt, A, B, C, D, h0, chunk=8,
                                 block_d=8, interpret=True)
    y_m, h_m = selective_scan(u, dt, A, B, C, D, h0, chunk=8)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_m),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_m),
                               rtol=2e-4, atol=2e-4)
