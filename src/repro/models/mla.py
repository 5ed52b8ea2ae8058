"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1), without
a query latent (``q_lora_rank`` null, as in DeepSeek-V2-Lite).

Per token x:

    q              = x W_q                     H heads of qk_nope + qk_rope
    [c_kv, k_pe]   = x W_kva                   kv_lora_rank + qk_rope
    c_kv           = RMSNorm(c_kv) * kv_norm   (``kv_a_layernorm``)
    [k_nope, v]_h  = c_kv W_kvb                per head: qk_nope + v_head
    score_h        = ([q_nope, rope(q_pe)]_h . [k_nope_h, rope(k_pe)]) * scale
    y              = (softmax(score) v) W_o

k_pe is one rotary key shared by every head.  The cache holds the latent
``[c_kv, rope(k_pe)]`` (``cfg.latent_dim`` values a position, shared by all
heads) and never per-head keys or values.

Every mode attends in the absorbed form: W_kvb's key half is folded into
the query (q_lat_h = q_nope_h W_UK_h, kv_lora_rank wide) and its value half
into the output (o_h = (softmax . c_kv) W_UV_h).  Scores are then one
shared latent "head" of ``latent_dim``: [q_lat_h, rope(q_pe)_h] .
[c_kv, rope(k_pe)], exactly the per-head scores above.  So verify reads the
latent cache once per slot and never expands it per head.  Prefill pays
latent_dim / (qk_nope + qk_rope) times the expanded form's score work for
it; at long prompts the expanded form is the cheaper one (a later change).

RoPE is YaRN-scaled where ``cfg.yarn`` is set (``inv_freq``,
``softmax_scale``), in the half-split layout: the published checkpoint
stores q_pe and k_pe interleaved, which is one fixed permutation of the
rotary columns of W_q and W_kva, so on weights drawn at random the
half-split layout is the same model.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .attention import _verify_attention_xla, masked_attention
from .cache import kv_write, prefill_write
from .config import ModelConfig
from .layers import dense_init

Params = Dict[str, jnp.ndarray]


def init_mla(rng, cfg: ModelConfig) -> Params:
    d, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = cfg.param_dtype
    ks = jax.random.split(rng, 4)
    return {"wq": dense_init(ks[0], (d, H * (dn + dr)), dt),
            "wkv_a": dense_init(ks[1], (d, r + dr), dt),
            "kv_norm": jnp.ones((r,), dt),
            "wkv_b": dense_init(ks[2], (r, H * (dn + dv)), dt),
            "wo": dense_init(ks[3], (H * dv, d), dt)}


def _yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_range(cfg: ModelConfig) -> Tuple[int, int]:
    """YaRN's correction range over the rotary frequency indices: the dims
    that make ``beta_fast`` and ``beta_slow`` rotations over the original
    context, floored and ceiled into [0, dim - 1]."""
    y, dim = cfg.yarn, cfg.qk_rope_head_dim

    def corr(rot):
        return (dim * math.log(y.original_max_position / (rot * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    return (max(math.floor(corr(y.beta_fast)), 0),
            min(math.ceil(corr(y.beta_slow)), dim - 1))


def inv_freq(cfg: ModelConfig) -> np.ndarray:
    """(qk_rope_head_dim / 2,) float32 inverse frequencies: plain RoPE, or
    YaRN's blend of the plain and the ``factor``-interpolated ones."""
    dim = cfg.qk_rope_head_dim
    f = 1.0 / cfg.rope_theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.yarn is not None and cfg.yarn.factor > 1:
        lo, hi = yarn_range(cfg)
        ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
        m = 1.0 - ramp                   # 1: keep the frequency, 0: interpolate
        f = f / cfg.yarn.factor * (1 - m) + f * m
    return f.astype(np.float32)


def softmax_scale(cfg: ModelConfig) -> float:
    """(qk_nope + qk_rope) ** -0.5, times YaRN's ``mscale_all_dim`` factor
    squared.  cos and sin carry mscale(mscale) / mscale(mscale_all_dim),
    which is 1 where the two are equal (DeepSeek-V2); other settings are
    refused rather than approximated."""
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    y = cfg.yarn
    if y is None or y.factor <= 1:
        return s
    if _yarn_mscale(y.factor, y.mscale) != _yarn_mscale(y.factor,
                                                        y.mscale_all_dim):
        raise ValueError("YaRN with mscale != mscale_all_dim scales cos and "
                         "sin; not supported")
    if y.mscale_all_dim:
        s *= _yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return s


def _rope(x: jnp.ndarray, ang: jnp.ndarray) -> jnp.ndarray:
    """Rotate x (..., dim) by angles ang (..., dim / 2), half-split, in
    float32; returns x's dtype."""
    h = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :h], xf[..., h:]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def project(p: Params, x: jnp.ndarray, cfg: ModelConfig,
            positions: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (N, T, d), positions (N, T) -> (absorbed queries (N, T, H,
    latent_dim), latents (N, T, latent_dim)), both in the compute type."""
    cd = cfg.compute_dtype
    N, T, _ = x.shape
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    x = x.astype(cd)
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv_freq(cfg))                                       # (N, T, dr/2)
    with jax.named_scope("mla.project"):
        q = (x @ p["wq"].astype(cd)).reshape(N, T, H, dn + dr)
        kva = x @ p["wkv_a"].astype(cd)
        c = kva[..., :r].astype(jnp.float32)
        c = c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True)
                              + cfg.norm_eps)
        c = (c * p["kv_norm"].astype(jnp.float32)).astype(cd)
        k_pe = _rope(kva[..., r:], ang)
        q_pe = _rope(q[..., dn:], ang[:, :, None])
        w_uk = p["wkv_b"].astype(cd).reshape(r, H, -1)[..., :dn]
        q_lat = jnp.einsum("nthk,rhk->nthr", q[..., :dn], w_uk)
        return (jnp.concatenate([q_lat, q_pe], -1),
                jnp.concatenate([c, k_pe], -1))


def output(p: Params, o_lat: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Attention over latents o_lat (..., H, >= kv_lora_rank) -> (..., d):
    each head's value half of W_kvb, then W_o."""
    cd = cfg.compute_dtype
    H, r, dn = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    w_uv = p["wkv_b"].astype(cd).reshape(r, H, -1)[..., dn:]
    o = jnp.einsum("...hr,rhv->...hv", o_lat[..., :r].astype(cd), w_uv)
    return o.reshape(o.shape[:-2] + (-1,)) @ p["wo"].astype(cd)


def mla_full(p: Params, x: jnp.ndarray, cfg: ModelConfig,
             positions: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Causal self-attention over a whole block (train / prefill).
    x (B, T, d) -> (y (B, T, d), latents (B, T, latent_dim)) to cache."""
    qa, lat = project(p, x, cfg, positions)
    with jax.named_scope("mla.attend"):
        kv = lat[:, :, None]
        o = masked_attention(qa, kv, kv, positions, positions, cfg,
                             causal=cfg.causal, scale=softmax_scale(cfg))
    return output(p, o, cfg), lat


def mla_verify(p: Params, x: jnp.ndarray, cfg: ModelConfig,
               positions: jnp.ndarray, cache: jnp.ndarray,
               cache_pos: jnp.ndarray, tail_mask=None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Bifurcated verify attention on the latent cache: x (B, K, W1, d),
    positions (B, W1), cache (B, S, latent_dim), cache_pos (B, S) (-1 =
    empty); each of the K rows attends the shared cache and, causally (or
    by ``tail_mask``, tree mode), its own tail.  Returns (y (B, K, W1, d),
    the rows' latents (B, K, W1, latent_dim))."""
    B, K, W1, d = x.shape
    pos = jnp.repeat(positions, K, axis=0)                   # (B*K, W1)
    qa, lat = project(p, x.reshape(B * K, W1, d), cfg, pos)
    L = lat.shape[-1]
    qa = qa.reshape(B, K, W1, cfg.num_heads, L)
    lat = lat.reshape(B, K, W1, L)
    with jax.named_scope("mla.attend"):
        c = cache[:, :, None]                               # one latent head
        t = lat[..., None, :]
        o = _verify_attention_xla(qa, c, c, t, t, cache_pos, positions,
                                  cfg, tail_mask=tail_mask,
                                  scale=softmax_scale(cfg))
    return output(p, o, cfg), lat


def attn_block(p: Params, h: jnp.ndarray, cfg: ModelConfig, mode: str,
               gst: Optional[Dict], ctx: Dict
               ) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """One MLA sublayer in a transformer mode (models/transformer.py):
    returns (y, the group's new decode state or verify tails)."""
    if ctx.get("paged"):
        raise ValueError(f"{cfg.name}: the latent cache is linear only")
    if mode in ("full", "prefill"):
        y, lat = mla_full(p, h, cfg, ctx["positions"])
        if mode == "full":
            return y, None
        (c,) = prefill_write(cfg, (gst["latent"],), (lat,))
        return y, {"latent": c}
    if mode in ("decode", "replay"):
        y, lat = mla_verify(p, h[:, None], cfg, ctx["positions"],
                            gst["latent"], ctx["cache_pos"])
        (c,) = kv_write((gst["latent"],), (lat[:, 0],), ctx["slots"],
                        gate=ctx.get("gate"))
        return y[:, 0], {"latent": c}
    if mode == "verify":
        K = ctx["k_rows"]
        B = h.shape[0] // K
        y, lat = mla_verify(p, h.reshape(B, K, h.shape[-2], h.shape[-1]),
                            cfg, ctx["positions"], gst["latent"],
                            ctx["cache_pos"], tail_mask=ctx.get("tail_mask"))
        return y.reshape(h.shape), {"latent_tail": lat}
    raise ValueError(mode)
