"""Pallas TPU kernel: bifurcated speculative-verification attention.

This is the compute hot-spot of the paper's method: every decode step calls
the model on a (k, w+1) block whose attention reads an ell-long KV cache.
The paper's PyTorch layout replicates the cache k times (`torch.expand`);
on TPU we instead stream the SHARED cache once per (batch, head) from HBM
through VMEM (flash-decoding style online softmax over cache blocks) and
handle the per-row speculative tail with an in-register causal mask — the
k× HBM traffic disappears (DESIGN.md §3).

Layout/tiling:
  grid = (B, H, S/BS) — the last axis iterates sequentially on TPU, so the
  online-softmax accumulators live in VMEM scratch across cache blocks.
  q is laid out (B, H, K*W1, hd): K*W1 query rows per (batch, head); the MXU
  sees (K*W1, hd) x (hd, BS) matmuls — hd and BS should be multiples of 128
  (the ops.py wrapper pads).  cur_len is a scalar-prefetch operand so block
  masking is known before the DMA of each block.

The speculative tail (K*W1 keys) is processed in the LAST grid step with a
row-block-diagonal causal mask: query row i = (draft r_i, offset t_i) may
attend tail key j = (r_j, t_j) iff r_i == r_j and t_j <= t_i — drafts never
see each other, exactly the paper's batched independence.

Tree variant (DESIGN.md §11): tree-structured speculation verifies one
(N+1)-node token TREE per slot instead of k independent rows.  The only
kernel-visible difference is the tail mask: ancestor-only visibility
(``tail_mask[i, j]`` = input j is an ancestor-or-self of input i) replaces
the row-block-diagonal causal mask.  The mask is a static topology
constant; Pallas forbids capturing array constants in the kernel body, so
it rides as a tiny lane-padded int32 operand whose index map is constant —
the pipeline fetches the same (KW1, KW1) block once, not per cache block —
and the cache-streaming half is untouched: every tree node attends the
whole committed context exactly like a linear row.

Paged variant (DESIGN.md §8): the cache streaming is already block-shaped,
so the page-pool layout costs the kernel nothing — ``paged_spec_attention_call``
keeps the SAME kernel body and only swaps the cache index map: the pool is
(num_pages, KV, page_size, hd) with page_size == block_s, the per-slot page
table rides in as a second scalar-prefetch operand, and grid step s of batch
b DMAs physical page ``page_table[b, s]`` instead of linear block s.
Unallocated pages (-1) clamp to page 0; every position they cover is
>= cur_len, so the existing block mask hides them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_S = 512
NEG_INF = -1e30
LANE = 128          # TPU lane width: the mask operand is lane-padded


def _pad_mask(tail_mask, kw1: int) -> np.ndarray:
    """(KW1, KW1) bool -> lane-padded (KW1, KW1p) int32 kernel operand."""
    tm = np.asarray(tail_mask, bool)
    assert tm.shape == (kw1, kw1), (tm.shape, kw1)
    kp = -(-kw1 // LANE) * LANE
    out = np.zeros((kw1, kp), np.int32)
    out[:, :kw1] = tm
    return out


def _kernel(cur_len_ref, q_ref, k_ref, v_ref, kt_ref, vt_ref, *rest,
            w1: int, scale: float, block_s: int, tree: bool = False):
    if tree:          # trailing operand: lane-padded int32 tail mask
        tm_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        tm_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    s = pl.program_id(2)
    n_s = pl.num_programs(2)

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                  # (KW1, hd)
    kb = k_ref[0, 0].astype(jnp.float32)                 # (BS, hd)
    logits = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
    slot = s * block_s + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    valid = slot < cur_len_ref[b]
    logits = jnp.where(valid, logits, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_cur = jnp.max(logits, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(logits - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1)
    vb = v_ref[0, 0].astype(jnp.float32)                 # (BS, hd)
    acc = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, vb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc

    @pl.when(s == n_s - 1)
    def _tail_and_write():
        kt = kt_ref[0, 0].astype(jnp.float32)            # (KW1, hd)
        vt = vt_ref[0, 0].astype(jnp.float32)
        lt = jax.lax.dot_general(q, kt, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        kw1 = lt.shape[0]
        if tm_ref is None:
            qi = jax.lax.broadcasted_iota(jnp.int32, (kw1, kw1), 0)
            kj = jax.lax.broadcasted_iota(jnp.int32, (kw1, kw1), 1)
            same_row = (qi // w1) == (kj // w1)
            causal = (kj % w1) <= (qi % w1)
            mask = same_row & causal
        else:
            # tree ancestor mask (DESIGN.md §11): constant-index-map block,
            # statically sliced back down from its lane padding
            mask = tm_ref[...][:, :kw1] != 0
        lt = jnp.where(mask, lt, NEG_INF)

        m_p, l_p, a_p = m_scr[...], l_scr[...], acc_scr[...]
        m_c = jnp.max(lt, axis=-1)
        m_f = jnp.maximum(m_p, m_c)
        p_t = jnp.exp(lt - m_f[:, None])
        alpha_f = jnp.exp(m_p - m_f)
        l_f = l_p * alpha_f + p_t.sum(axis=-1)
        a_f = a_p * alpha_f[:, None] + jax.lax.dot_general(
            p_t, vt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0, 0] = (a_f / l_f[:, None]).astype(o_ref.dtype)


def spec_attention_call(q, k_cache, v_cache, k_tail, v_tail, cur_len, *,
                        w1: int, block_s: int = DEFAULT_BLOCK_S,
                        interpret: bool = False,
                        tail_mask=None) -> jnp.ndarray:
    """q: (B, H, KW1, hd) — KW1 = k*(w+1) rows, k-major.
    k_cache/v_cache: (B, KV, S, hd) (linear cache, slot == position).
    k_tail/v_tail:   (B, KV, KW1, hd) per-row speculative KV.
    cur_len: (B,) int32.  Returns (B, H, KW1, hd), dtype of q.

    ``tail_mask``: optional STATIC (KW1, KW1) bool replacing the
    row-block-diagonal causal tail mask — tree speculation passes the
    topology's ancestor mask here (DESIGN.md §11).

    S must be a multiple of block_s (ops.py pads).
    """
    B, H, KW1, hd = q.shape
    KV, S = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    assert S % block_s == 0, (S, block_s)
    assert KW1 % w1 == 0
    grid = (B, H, S // block_s)
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_kernel, w1=w1, scale=scale, block_s=block_s,
                               tree=tail_mask is not None)
    in_specs = [
        pl.BlockSpec((1, 1, KW1, hd), lambda b, h, s, c: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, block_s, hd),
                     lambda b, h, s, c: (b, h // G, s, 0)),
        pl.BlockSpec((1, 1, block_s, hd),
                     lambda b, h, s, c: (b, h // G, s, 0)),
        pl.BlockSpec((1, 1, KW1, hd),
                     lambda b, h, s, c: (b, h // G, 0, 0)),
        pl.BlockSpec((1, 1, KW1, hd),
                     lambda b, h, s, c: (b, h // G, 0, 0)),
    ]
    operands = [cur_len, q, k_cache, v_cache, k_tail, v_tail]
    if tail_mask is not None:
        tm = _pad_mask(tail_mask, KW1)
        in_specs.append(pl.BlockSpec(tm.shape, lambda b, h, s, c: (0, 0)))
        operands.append(tm)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, KW1, hd),
                                   lambda b, h, s, c: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((KW1,), jnp.float32),
                pltpu.VMEM((KW1,), jnp.float32),
                pltpu.VMEM((KW1, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, KW1, hd), q.dtype),
        interpret=interpret,
        name="spec_attention",
    )(*operands)


def _paged_kernel(cur_len_ref, pt_ref, *rest, **kw):
    # the page table steers DMA from the index maps only; the body is the
    # linear kernel unchanged (page s holds positions [s*ps, (s+1)*ps) of
    # its slot, exactly what the block mask assumes)
    return _kernel(cur_len_ref, *rest, **kw)


def paged_spec_attention_call(q, k_pool, v_pool, page_table, k_tail, v_tail,
                              cur_len, *, w1: int,
                              interpret: bool = False,
                              tail_mask=None) -> jnp.ndarray:
    """q: (B, H, KW1, hd); k_pool/v_pool: (num_pages, KV, page_size, hd);
    page_table: (B, pages_per_slot) int32, -1 = unallocated; tails/cur_len/
    tail_mask as in spec_attention_call.  block_s == page_size by
    construction, so the grid's cache axis walks the slot's page table:
    pages_per_slot steps per (batch, head), each DMA-ing one whole physical
    page.
    """
    B, H, KW1, hd = q.shape
    NP, KV, ps = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    PPS = page_table.shape[1]
    G = H // KV
    assert KW1 % w1 == 0
    grid = (B, H, PPS)
    scale = 1.0 / (hd ** 0.5)

    def page_ix(b, h, s, cl, pt):
        return (jnp.maximum(pt[b, s], 0), h // G, 0, 0)

    kernel = functools.partial(_paged_kernel, w1=w1, scale=scale, block_s=ps,
                               tree=tail_mask is not None)
    in_specs = [
        pl.BlockSpec((1, 1, KW1, hd),
                     lambda b, h, s, cl, pt: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, ps, hd), page_ix),
        pl.BlockSpec((1, 1, ps, hd), page_ix),
        pl.BlockSpec((1, 1, KW1, hd),
                     lambda b, h, s, cl, pt: (b, h // G, 0, 0)),
        pl.BlockSpec((1, 1, KW1, hd),
                     lambda b, h, s, cl, pt: (b, h // G, 0, 0)),
    ]
    operands = [cur_len, page_table.astype(jnp.int32), q, k_pool, v_pool,
                k_tail, v_tail]
    if tail_mask is not None:
        tm = _pad_mask(tail_mask, KW1)
        in_specs.append(pl.BlockSpec(tm.shape,
                                     lambda b, h, s, cl, pt: (0, 0)))
        operands.append(tm)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, KW1, hd),
                                   lambda b, h, s, cl, pt: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((KW1,), jnp.float32),
                pltpu.VMEM((KW1,), jnp.float32),
                pltpu.VMEM((KW1, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, KW1, hd), q.dtype),
        interpret=interpret,
        name="paged_spec_attention",
    )(*operands)
