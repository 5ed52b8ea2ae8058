"""jit'd public wrappers around the Pallas kernels.

These handle layout transposition, cache/block padding and batching only;
backend SELECTION (xla vs pallas, interpret forcing, eligibility) lives one
level up in ``kernels/dispatch.py``, which is what production code calls.
On this CPU container the kernels run with ``interpret=True``; on a real
TPU the default resolves to ``interpret=False``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .ngram_match import LANE as NGRAM_LANE
from .ngram_match import TILE as NGRAM_TILE
from .ngram_match import ngram_match_call
from .spec_attention import (DEFAULT_BLOCK_S, paged_spec_attention_call,
                             spec_attention_call)


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jnp.ndarray, axis: int, mult: int, value=0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), size


@functools.partial(jax.jit,
                   static_argnames=("w1", "block_s", "interpret",
                                    "tail_mask"))
def spec_attention_op(q, k_cache, v_cache, k_tail, v_tail, cur_len, *,
                      w1: int, block_s: int = DEFAULT_BLOCK_S,
                      interpret: bool | None = None,
                      tail_mask=None) -> jnp.ndarray:
    """Engine-facing layout: q (B,K,W1,H,hd); caches (B,S,KV,hd);
    tails (B,K,W1,KV,hd); cur_len (B,).  Returns (B,K,W1,H,hd).

    ``tail_mask``: optional STATIC tail-visibility matrix as a hashable
    tuple-of-tuples of bool (a topology constant, so it is part of the jit
    cache key on purpose — dispatch.py converts from numpy)."""
    if interpret is None:
        interpret = _default_interpret()
    B, K, W1, H, hd = q.shape
    S = k_cache.shape[1]
    KV = k_cache.shape[2]
    qk = q.transpose(0, 3, 1, 2, 4).reshape(B, H, K * W1, hd)
    kc = k_cache.transpose(0, 2, 1, 3)           # (B,KV,S,hd)
    vc = v_cache.transpose(0, 2, 1, 3)
    kt = k_tail.transpose(0, 3, 1, 2, 4).reshape(B, KV, K * W1, hd)
    vt = v_tail.transpose(0, 3, 1, 2, 4).reshape(B, KV, K * W1, hd)
    bs = min(block_s, S)
    kc, S0 = _pad_to(kc, 2, bs)
    vc, _ = _pad_to(vc, 2, bs)
    tm = None if tail_mask is None else np.asarray(tail_mask, bool)
    # padded cache slots have slot >= S0 >= cur_len -> masked by cur_len test
    # (serving avoids the per-call repad by sizing its buffers through
    # dispatch.align_cache_len; arbitrary lengths stay correct here)
    out = spec_attention_call(qk, kc, vc, kt, vt, cur_len.astype(jnp.int32),
                              w1=W1, block_s=bs, interpret=interpret,
                              tail_mask=tm)
    return out.reshape(B, H, K, W1, hd).transpose(0, 2, 3, 1, 4)


@functools.partial(jax.jit, static_argnames=("w1", "interpret", "tail_mask"))
def paged_spec_attention_op(q, k_pool, v_pool, page_table, k_tail, v_tail,
                            cur_len, *, w1: int,
                            interpret: bool | None = None,
                            tail_mask=None) -> jnp.ndarray:
    """Engine-facing paged layout: q (B,K,W1,H,hd);
    pools (num_pages, page_size, KV, hd); page_table (B, pages_per_slot);
    tails (B,K,W1,KV,hd); cur_len (B,); tail_mask as in spec_attention_op.
    Returns (B,K,W1,H,hd).

    No cache padding path exists here on purpose: the pool is whole pages by
    construction (page_size == the kernel's block_s), which is exactly why
    the paged layout is free for this kernel (DESIGN.md §8).
    """
    if interpret is None:
        interpret = _default_interpret()
    B, K, W1, H, hd = q.shape
    KV = k_pool.shape[2]
    qk = q.transpose(0, 3, 1, 2, 4).reshape(B, H, K * W1, hd)
    kp = k_pool.transpose(0, 2, 1, 3)            # (NP, KV, ps, hd)
    vp = v_pool.transpose(0, 2, 1, 3)
    kt = k_tail.transpose(0, 3, 1, 2, 4).reshape(B, KV, K * W1, hd)
    vt = v_tail.transpose(0, 3, 1, 2, 4).reshape(B, KV, K * W1, hd)
    tm = None if tail_mask is None else np.asarray(tail_mask, bool)
    out = paged_spec_attention_call(qk, kp, vp,
                                    page_table.astype(jnp.int32), kt, vt,
                                    cur_len.astype(jnp.int32), w1=W1,
                                    interpret=interpret, tail_mask=tm)
    return out.reshape(B, H, K, W1, hd).transpose(0, 2, 3, 1, 4)


def spec_attention_ref_op(q, k_cache, v_cache, k_tail, v_tail, cur_len, *,
                          w1: int, tail_mask=None) -> jnp.ndarray:
    """Oracle with the same engine-facing layout."""
    B, K, W1, H, hd = q.shape
    KV = k_cache.shape[2]
    qk = q.transpose(0, 3, 1, 2, 4).reshape(B, H, K * W1, hd)
    kc = k_cache.transpose(0, 2, 1, 3)
    vc = v_cache.transpose(0, 2, 1, 3)
    kt = k_tail.transpose(0, 3, 1, 2, 4).reshape(B, KV, K * W1, hd)
    vt = v_tail.transpose(0, 3, 1, 2, 4).reshape(B, KV, K * W1, hd)
    tm = None if tail_mask is None else np.asarray(tail_mask, bool)
    out = ref.spec_attention_ref(qk, kc, vc, kt, vt,
                                 cur_len.astype(jnp.int32), w1=W1,
                                 tail_mask=tm)
    return out.reshape(B, H, K, W1, hd).transpose(0, 2, 3, 1, 4)


@functools.partial(jax.jit, static_argnames=("w", "interpret"))
def ngram_match_op(buf, query, cur_len, *, w: int,
                   interpret: bool | None = None):
    """buf: (B, L) int32; query: (B, q); cur_len: (B,).

    Returns (match (B, L) int32, hash (B, L) uint32).  The kernel walks L
    in tiles of ``ngram_match.TILE`` positions."""
    if interpret is None:
        interpret = _default_interpret()
    B, L = buf.shape
    Lp = -(-L // NGRAM_TILE) * NGRAM_TILE
    # whole tiles plus the last tile's halo; padded positions fail the
    # kernel's cur_len guard, so they never match
    pad = jnp.full((B, Lp + NGRAM_TILE - L), -1, jnp.int32)
    bufp = jnp.concatenate([buf.astype(jnp.int32), pad], axis=1)
    m, h = ngram_match_call(bufp.reshape(B, -1, NGRAM_LANE),
                            query.astype(jnp.int32),
                            cur_len.astype(jnp.int32), w=w,
                            interpret=interpret)
    return m.reshape(B, Lp)[:, :L], h.reshape(B, Lp)[:, :L]


@functools.partial(jax.jit,
                   static_argnames=("chunk", "block_d", "interpret"))
def mamba_scan_op(u, dt, A, B, C, D, h0, *, chunk: int = 128,
                  block_d: int = 512, interpret: bool | None = None):
    """Padded/clamped wrapper for the chunked selective-scan kernel."""
    from .mamba_scan import mamba_scan_call
    if interpret is None:
        interpret = _default_interpret()
    Bt, T, di = u.shape
    c = min(chunk, T) if T % min(chunk, T) == 0 else T
    bd = min(block_d, di) if di % min(block_d, di) == 0 else di
    return mamba_scan_call(u, dt, A, B, C, D, h0, chunk=c, block_d=bd,
                           interpret=interpret)
