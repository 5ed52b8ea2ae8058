"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .hashing import hash_step


def spec_attention_ref(q, k_cache, v_cache, k_tail, v_tail, cur_len, *,
                       w1: int, tail_mask=None) -> jnp.ndarray:
    """Same contract as spec_attention_call, computed densely in f32.

    q: (B,H,KW1,hd); k/v_cache: (B,KV,S,hd); k/v_tail: (B,KV,KW1,hd);
    cur_len: (B,).  ``tail_mask``: optional static (KW1, KW1) bool tail
    visibility (tree ancestor mask) replacing the per-row causal mask.
    """
    B, H, KW1, hd = q.shape
    KV, S = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, KV, G, KW1, hd)
    scale = 1.0 / (hd ** 0.5)
    lc = jnp.einsum("bngqh,bnsh->bngqs", qf,
                    k_cache.astype(jnp.float32)) * scale
    valid = (jnp.arange(S)[None, :] < cur_len[:, None])
    lc = jnp.where(valid[:, None, None, None, :], lc, -1e30)
    lt = jnp.einsum("bngqh,bnth->bngqt", qf,
                    k_tail.astype(jnp.float32)) * scale
    if tail_mask is None:
        qi = jnp.arange(KW1)
        same_row = (qi[:, None] // w1) == (qi[None, :] // w1)
        causal = (qi[None, :] % w1) <= (qi[:, None] % w1)
        tail_mask = same_row & causal
    lt = jnp.where(jnp.asarray(tail_mask, bool), lt, -1e30)
    logits = jnp.concatenate([lc, lt], axis=-1)
    w = jax.nn.softmax(logits, axis=-1)
    out = (jnp.einsum("bngqs,bnsh->bngqh", w[..., :S],
                      v_cache.astype(jnp.float32))
           + jnp.einsum("bngqt,bnth->bngqh", w[..., S:],
                        v_tail.astype(jnp.float32)))
    return out.reshape(B, H, KW1, hd).astype(q.dtype)


def ngram_match_ref(buf_padded: jnp.ndarray, query: jnp.ndarray,
                    cur_len: jnp.ndarray, *, w: int):
    """Oracle for the match/hash sweep of one sequence.

    buf_padded: (L+q+w,) (-1 padded); returns (match (L,), hash (L,))."""
    q = query.shape[0]
    L = buf_padded.shape[0] - q - w
    pos = jnp.arange(L)
    match = jnp.ones((L,), bool)
    for j in range(q):
        match = match & (buf_padded[j:j + L] == query[j])
    match = match & (pos + q + w <= cur_len[0])
    h = jnp.zeros((L,), jnp.uint32)
    for j in range(w):
        h = hash_step(h, buf_padded[q + j:q + j + L])
    return match.astype(jnp.int32), h


def mamba_scan_ref(u, dt, A, B, C, D, h0):
    """Oracle for mamba_scan_call: sequential recurrence in f32."""
    uf, dtf = u.astype(jnp.float32), dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)

    def step(h, xs):
        u_t, dt_t, b_t, c_t = xs
        dA = jnp.exp(dt_t[..., None] * Af)              # (Bt, di, ds)
        h = dA * h + (dt_t * u_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("bds,bs->bd", h, c_t)
        return h, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in
               (uf, dtf, B.astype(jnp.float32), C.astype(jnp.float32)))
    hT, ys = jax.lax.scan(step, h0.astype(jnp.float32), xs)
    y = jnp.moveaxis(ys, 0, 1) + uf * D.astype(jnp.float32)
    return y, hT
