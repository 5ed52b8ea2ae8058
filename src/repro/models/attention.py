"""Attention: MHA / GQA / MQA with RoPE variants, sliding windows, caches.

Covers every assigned attention flavour:
  - full-causal (StableLM, GLM4, Nemotron, Jamba attn layers, ...)
  - bidirectional (HuBERT encoder)
  - sliding-window causal (Mixtral; long-context dense variant)
  - partial-rotary RoPE (StableLM 25%, Nemotron 50%)
  - M-RoPE (Qwen2-VL, 3D t/h/w positions)
  - MQA (Gemma kv=1) and GQA groups

The decode/verify path attends to a cache buffer + the in-flight block, which
is exactly the shape the paper's batched (k, w+1) verification needs.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import MROPE, ROPE, ModelConfig
from .layers import dense_init

Params = Dict[str, jnp.ndarray]


def init_attention(rng, cfg: ModelConfig) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(rng, 4)
    return {
        "wq": dense_init(ks[0], (d, cfg.num_heads * hd), cfg.param_dtype),
        "wk": dense_init(ks[1], (d, cfg.num_kv_heads * hd), cfg.param_dtype),
        "wv": dense_init(ks[2], (d, cfg.num_kv_heads * hd), cfg.param_dtype),
        "wo": dense_init(ks[3], (cfg.num_heads * hd, d), cfg.param_dtype),
    }


# ----------------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------------
def _rope_inv_freq(cfg: ModelConfig) -> jnp.ndarray:
    rd = cfg.rotary_dim
    return 1.0 / (cfg.rope_theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))


def rope_freqs(cfg: ModelConfig, positions: jnp.ndarray) -> jnp.ndarray:
    """positions: (B, T) int32, or (3, B, T) for M-RoPE. Returns (B, T, rd/2)."""
    inv = _rope_inv_freq(cfg)  # (rd/2,)
    if cfg.rope == MROPE:
        assert positions.ndim == 3, "M-RoPE needs (3, B, T) positions"
        sec = jnp.asarray(cfg.mrope_sections)
        # section id for each rotary half-dim
        sec_id = jnp.repeat(jnp.arange(3), sec, total_repeat_length=inv.shape[0])
        # per-dim positions: select the t/h/w position row
        pos = positions.astype(jnp.float32)  # (3, B, T)
        pos_per_dim = pos[sec_id]            # (rd/2, B, T)
        return jnp.moveaxis(pos_per_dim, 0, -1) * inv  # (B, T, rd/2)
    pos = positions.astype(jnp.float32)
    return pos[..., None] * inv  # (B, T, rd/2)


def apply_rope(x: jnp.ndarray, freqs: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """x: (B, T, N, hd); freqs: (B, T, rd/2). NeoX half-split convention."""
    rd = cfg.rotary_dim
    if rd == 0:
        return x
    x_rot, x_pass = x[..., :rd], x[..., rd:]
    half = rd // 2
    x1, x2 = x_rot[..., :half], x_rot[..., half:]
    cos = jnp.cos(freqs)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(freqs)[:, :, None, :].astype(x.dtype)
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    return jnp.concatenate([r1, r2, x_pass], axis=-1)


# ----------------------------------------------------------------------------
# core attention math (pure-jnp reference path; Pallas kernel is the TPU path)
# ----------------------------------------------------------------------------
# Above this many keys, full self-attention switches to the blockwise
# (flash-style, online-softmax) path: the (B,H,T,S) logits tensor of a 32k
# prefill is ~50 GiB/device even sharded — measured in EXPERIMENTS.md §Perf
# it-3 — while blockwise keeps only one (B,H,T,BS) slab live at a time.
BLOCKWISE_THRESHOLD = 8192
BLOCKWISE_BLOCK = 1024


def _blockwise_attention(q, k, v, q_pos, k_pos, cfg, causal: bool,
                         block: int = BLOCKWISE_BLOCK,
                         scale: Optional[float] = None) -> jnp.ndarray:
    """Flash-style attention: scan over key blocks with online softmax.

    Same contract as ``masked_attention``; numerically identical softmax.
    """
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    nb = S // block
    assert S % block == 0
    qf = q.reshape(B, T, KV, G, hd).astype(jnp.float32)
    if scale is None:
        scale = 1.0 / (hd ** 0.5)

    def rs(a):  # (B,S,...) -> (nb, B, bs, ...)
        return jnp.moveaxis(a.reshape(B, nb, block, *a.shape[2:]), 1, 0)

    kb, vb, kpb = rs(k.astype(jnp.float32)), rs(v.astype(jnp.float32)), \
        rs(k_pos)

    def body(carry, xs):
        m, l, acc = carry
        k_c, v_c, kp_c = xs
        logits = jnp.einsum("btkgh,bskh->bkgts", qf, k_c) * scale
        if cfg.attn_logit_softcap:
            c = cfg.attn_logit_softcap
            logits = c * jnp.tanh(logits / c)
        valid = (kp_c >= 0)[:, None, None, None, :]
        if causal:
            valid = valid & (kp_c[:, None, :] <=
                             q_pos[:, :, None])[:, None, None]
        if cfg.sliding_window is not None:
            win = cfg.sliding_window
            valid = valid & (kp_c[:, None, :] >
                             q_pos[:, :, None] - win)[:, None, None]
        logits = jnp.where(valid, logits, -1e30)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgts,bskh->bkgth", p, v_c)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, T), -1e30, jnp.float32)
    l0 = jnp.zeros((B, KV, G, T), jnp.float32)
    a0 = jnp.zeros((B, KV, G, T, hd), jnp.float32)
    from .runtime_flags import UNROLL_FOR_ANALYSIS
    if UNROLL_FOR_ANALYSIS:
        carry = (m0, l0, a0)
        for i in range(nb):
            carry, _ = body(carry, (kb[i], vb[i], kpb[i]))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, kpb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, -2, 1).reshape(B, T, H, hd).astype(q.dtype)


def masked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                     cfg: ModelConfig, causal: bool,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """q: (B,T,H,hd) k/v: (B,S,KV,hd); *_pos: (B,T)/(B,S) (-1 = invalid key).

    Returns (B, T, H, hd).  GQA via reshape to (KV, G) groups.  ``scale``
    multiplies the logits (default hd ** -0.5).
    Dispatches to the blockwise path for large key counts.
    """
    S = k.shape[1]
    if S >= BLOCKWISE_THRESHOLD and S % BLOCKWISE_BLOCK == 0:
        return _blockwise_attention(q, k, v, q_pos, k_pos, cfg, causal,
                                    scale=scale)
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    qf = q.reshape(B, T, KV, G, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    logits = jnp.einsum("btkgh,bskh->bkgts", qf, kf)
    logits = logits / (hd ** 0.5) if scale is None else logits * scale
    if cfg.attn_logit_softcap:
        c = cfg.attn_logit_softcap
        logits = c * jnp.tanh(logits / c)
    valid = (k_pos >= 0)[:, None, None, None, :]
    if causal:
        valid = valid & (k_pos[:, None, :] <= q_pos[:, :, None])[:, None, None]
    if cfg.sliding_window is not None:
        win = cfg.sliding_window
        valid = valid & (k_pos[:, None, :] > q_pos[:, :, None] - win)[:, None, None]
    logits = jnp.where(valid, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", w, v.astype(jnp.float32))
    return out.reshape(B, T, H, hd).astype(q.dtype)


# ----------------------------------------------------------------------------
# layer application
# ----------------------------------------------------------------------------
def qkv_project(params: Params, x: jnp.ndarray, cfg: ModelConfig,
                freqs: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    B, T, _ = x.shape
    hd = cfg.resolved_head_dim
    cd = cfg.compute_dtype
    x = x.astype(cd)
    q = (x @ params["wq"].astype(cd)).reshape(B, T, cfg.num_heads, hd)
    k = (x @ params["wk"].astype(cd)).reshape(B, T, cfg.num_kv_heads, hd)
    v = (x @ params["wv"].astype(cd)).reshape(B, T, cfg.num_kv_heads, hd)
    if cfg.rope != "none":
        q = apply_rope(q, freqs, cfg)
        k = apply_rope(k, freqs, cfg)
    return q, k, v


def attn_full(params: Params, x: jnp.ndarray, cfg: ModelConfig,
              positions: jnp.ndarray,
              seq_mask: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray,
                                                               Tuple[jnp.ndarray,
                                                                     jnp.ndarray]]:
    """Self-attention over a full block (train / prefill).

    positions: (B, T) (or (3,B,T) for mrope). seq_mask: (B, T) bool for padding.
    Returns output and the (k, v) tensors for cache insertion.
    """
    freqs = rope_freqs(cfg, positions) if cfg.rope != "none" else None
    q, k, v = qkv_project(params, x, cfg, freqs)
    pos2d = positions[0] if positions.ndim == 3 else positions
    k_pos = pos2d if seq_mask is None else jnp.where(seq_mask, pos2d, -1)
    out = masked_attention(q, k, v, pos2d, k_pos, cfg, causal=cfg.causal)
    B, T, _, _ = out.shape
    y = out.reshape(B, T, -1) @ params["wo"].astype(cfg.compute_dtype)
    return y, (k, v)


def _verify_attention_xla(q, k_cache, v_cache, k_tail, v_tail, cache_pos,
                          pos2d, cfg: ModelConfig,
                          tail_mask=None,
                          scale: Optional[float] = None) -> jnp.ndarray:
    """XLA backend of the bifurcated verify attention.

    q: (B,K,W1,H,hd); caches (B,S,KV,hd); tails (B,K,W1,KV,hd);
    cache_pos: (B,S) absolute position per slot (-1 = empty, ring-aware);
    pos2d: (B,W1) query positions.  ``tail_mask``: optional STATIC
    (W1, W1) bool tail visibility replacing the causal triangle — tree
    verification's ancestor mask (DESIGN.md §11; K == 1 there).
    ``scale`` multiplies the logits (default hd ** -0.5).
    Returns (B,K,W1,H,hd) f32.

    This is the fully-general path (softcap, sliding-window ring caches,
    sharded context logits); the Pallas backend covers the linear-cache
    subset via kernels/dispatch.verify_attention.
    """
    B, K, W1, H, hd = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, K, W1, KV, G, hd).astype(jnp.float32)
    kn = k_tail.astype(jnp.float32)
    vn = v_tail.astype(jnp.float32)
    kc = k_cache.astype(jnp.float32)
    vc = v_cache.astype(jnp.float32)
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    # context logits: shared cache read once per sequence
    lc = jnp.einsum("bkwnGh,bsnh->bknGws", qg, kc) * scale
    from ..distributed import act_sharding
    lc = act_sharding.constrain(lc, "ctx_logits")   # (B,K,n,G,w1,S)
    if cfg.attn_logit_softcap:
        lc = cfg.attn_logit_softcap * jnp.tanh(lc / cfg.attn_logit_softcap)
    valid_c = (cache_pos >= 0)[:, None, None, None, None, :]
    if cfg.sliding_window is not None:
        win = cfg.sliding_window
        in_win = (cache_pos[:, None, :] > pos2d[:, :, None] - win)
        valid_c = valid_c & in_win[:, None, None, None]
    lc = jnp.where(valid_c, lc, -1e30)
    # local (per-row) logits: causal within the speculative tail
    ll = jnp.einsum("bkwnGh,bkvnh->bknGwv", qg, kn) * scale
    if cfg.attn_logit_softcap:
        ll = cfg.attn_logit_softcap * jnp.tanh(ll / cfg.attn_logit_softcap)
    if tail_mask is None:
        local = jnp.tril(jnp.ones((W1, W1), bool))
    else:
        # tree ancestor mask; applied within each of the K rows (tree mode
        # flattens the whole tree into the single row K == 1)
        local = jnp.asarray(tail_mask, bool)
    ll = jnp.where(local[None, None, None, None], ll, -1e30)
    # merged softmax WITHOUT concatenating [lc | ll]: a concat would force
    # the sharded context logits to be gathered; here only per-row max/sum
    # scalars cross the cache's sharding (flash-decode style, §Perf it-7).
    m = jnp.maximum(lc.max(axis=-1), ll.max(axis=-1))     # (b,k,n,G,w)
    e_c = jnp.exp(lc - m[..., None])
    e_l = jnp.exp(ll - m[..., None])
    denom = e_c.sum(axis=-1) + e_l.sum(axis=-1)
    out = (jnp.einsum("bknGws,bsnh->bkwnGh", e_c, vc)
           + jnp.einsum("bknGwv,bkvnh->bkwnGh", e_l, vn))
    out = act_sharding.constrain(out, "ctx_out")
    out = out / jnp.moveaxis(denom, -1, 2)[..., None]
    return out.reshape(B, K, W1, H, hd)


def _use_verify_kernel(cfg: ModelConfig, cur_len) -> bool:
    """Route to the Pallas kernel iff the backend resolves to pallas, the
    config is inside the kernel's contract (linear cache, no softcap) and
    the caller supplied the scalar-prefetch cur_len.  The mesh-sharded XLA
    path keeps its own flash-decode partitioning, so an installed
    activation-sharder also pins the XLA backend."""
    from ..distributed import act_sharding
    from ..kernels import dispatch
    return (cur_len is not None
            and dispatch.use_pallas(cfg.backend)
            and dispatch.pallas_verify_supported(cfg)
            and not act_sharding.installed())


def attn_verify(params: Params, x: jnp.ndarray, cfg: ModelConfig,
                positions: jnp.ndarray,
                k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                cache_pos: jnp.ndarray,
                cur_len: Optional[jnp.ndarray] = None,
                page_table: Optional[jnp.ndarray] = None,
                tail_mask=None
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Bifurcated batched-speculation attention (the paper's verification).

    x: (B, k, w1, d) — k speculative rows per sequence.  Each row attends to
    the SHARED context cache (read once, not k times — beyond-paper
    optimisation, see DESIGN.md §3) plus its own (w1)-token tail, causally,
    with no cross-row attention.

    positions: (B, w1) or (3, B, w1) — identical for all k rows.
    tail_mask: optional STATIC (w1, w1) bool numpy array replacing the causal
    tail triangle — tree verification's ancestor-only visibility
    (DESIGN.md §11; the tree rides as the single row k == 1, so the
    (k*w1, k*w1) kernel mask and this per-row mask coincide).
    cur_len: (B,) committed cache length (linear caches); enables the Pallas
    backend (kernels/dispatch.py) when ``cfg.backend`` resolves to pallas.
    page_table: (B, pages_per_slot) when the cache is PAGED (DESIGN.md §8) —
    k_cache/v_cache are then the shared (num_pages, page_size, KV, hd) pool:
    the Pallas backend walks the table directly (one grid step per page);
    the XLA backend gathers the per-slot linear view first and reuses
    ``_verify_attention_xla`` unchanged, which is what the bit-parity tests
    pin against the linear layout.
    Returns (y (B,k,w1,d), k_new, v_new (B,k,w1,KV,hd)).
    """
    B, K, W1, d = x.shape
    hd = cfg.resolved_head_dim
    cd = cfg.compute_dtype
    freqs = rope_freqs(cfg, positions) if cfg.rope != "none" else None
    xf = x.reshape(B * K, W1, d).astype(cd)
    fr = None
    if freqs is not None:
        fr = jnp.repeat(freqs, K, axis=0)  # (B*K, w1, rd/2)
    q = (xf @ params["wq"].astype(cd)).reshape(B * K, W1, cfg.num_heads, hd)
    k_new = (xf @ params["wk"].astype(cd)).reshape(B * K, W1,
                                                   cfg.num_kv_heads, hd)
    v_new = (xf @ params["wv"].astype(cd)).reshape(B * K, W1,
                                                   cfg.num_kv_heads, hd)
    if cfg.rope != "none":
        q = apply_rope(q, fr, cfg)
        k_new = apply_rope(k_new, fr, cfg)
    KV = cfg.num_kv_heads
    qk = q.reshape(B, K, W1, cfg.num_heads, hd)
    kn = k_new.reshape(B, K, W1, KV, hd)
    vn = v_new.reshape(B, K, W1, KV, hd)
    pos2d = positions[0] if positions.ndim == 3 else positions  # (B, w1)
    if page_table is not None:
        if _use_verify_kernel(cfg, cur_len):
            from ..kernels import dispatch
            out = dispatch.verify_attention_paged(qk, k_cache, v_cache,
                                                  page_table, kn, vn,
                                                  cur_len, w1=W1,
                                                  tail_mask=tail_mask)
        else:
            from .cache import gather_pages
            k_lin, v_lin = gather_pages(k_cache, v_cache, page_table)
            out = _verify_attention_xla(qk, k_lin, v_lin, kn, vn, cache_pos,
                                        pos2d, cfg, tail_mask=tail_mask)
    elif _use_verify_kernel(cfg, cur_len):
        from ..kernels import dispatch
        out = dispatch.verify_attention(qk, k_cache, v_cache, kn, vn,
                                        cur_len, w1=W1,
                                        block_s=cfg.kernel_block_s,
                                        tail_mask=tail_mask)
    else:
        out = _verify_attention_xla(qk, k_cache, v_cache, kn, vn, cache_pos,
                                    pos2d, cfg, tail_mask=tail_mask)
    out = out.reshape(B, K, W1, cfg.num_heads * hd).astype(cd)
    y = out @ params["wo"].astype(cd)
    return y, kn, vn


