"""Mixture-of-Experts layer: top-k router, shared experts, three dispatch
impls.

Covers Mixtral (8e top-2), DeepSeek-MoE and DeepSeek-V2 (2 shared + 64
routed top-6, fine-grained expert width) and Jamba (16e top-2).

Dispatch implementations:
  - ``dense``:   every expert computes every token, combined with router
                 weights.  O(E) FLOPs — used only as the correctness oracle
                 in tests and for tiny models.
  - ``scatter``: sort-based dispatch with capacity (the MaxText approach):
                 token-slots are sorted by expert id, packed into an
                 (E, C, d) buffer, batched expert matmuls, then combined
                 back.  Rows past a capacity of ``capacity_factor`` times
                 the mean are dropped, so a row's output can depend on the
                 other rows of its batch.
  - ``gmm``:     dropless: every (token, held expert) pair is computed.
                 The pairs are sorted by expert into groups padded to whole
                 row tiles, and a grouped matmul over the held experts
                 (``kernels/dispatch.expert_matmul``: the Pallas
                 ``expert_gmm`` kernel on TPU, ``lax.ragged_dot`` elsewhere)
                 computes each tile with its group's weights.  A row's
                 output depends on its own routing alone, so a verified row
                 equals the same row decoded alone.

The layer holds ``cfg.held_experts`` of the router's ``num_experts``
(``experts_offset`` on): it routes over all of them and computes the part
of the output its own experts give (one chip's share under expert
parallelism; the absent experts' part is left out, not stood in for).

Router aux loss (load balancing, Switch-style) is returned for training.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import dense_init

Params = Dict[str, jnp.ndarray]


def init_moe(rng, cfg: ModelConfig) -> Params:
    d, e_ff = cfg.d_model, cfg.expert_d_ff
    E = cfg.held_experts
    dt = cfg.param_dtype
    ks = jax.random.split(rng, 5)
    p = {
        "router": dense_init(ks[0], (d, cfg.num_experts), jnp.float32),
        "w_gate": dense_init(ks[1], (E, d, e_ff), dt),
        "w_up": dense_init(ks[2], (E, d, e_ff), dt),
        "w_down": dense_init(ks[3], (E, e_ff, d), dt),
    }
    if cfg.num_shared_experts:
        s_ff = e_ff * cfg.num_shared_experts
        ks2 = jax.random.split(ks[4], 3)
        p["shared_gate"] = dense_init(ks2[0], (d, s_ff), dt)
        p["shared_up"] = dense_init(ks2[1], (d, s_ff), dt)
        p["shared_down"] = dense_init(ks2[2], (s_ff, d), dt)
    return p


def _expert_ffn(wg, wu, wd, x, cd):
    """x: (E, C, d) -> (E, C, d) batched SwiGLU over experts."""
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, wg.astype(cd)))
    u = jnp.einsum("ecd,edf->ecf", x, wu.astype(cd))
    return jnp.einsum("ecf,efd->ecd", g * u, wd.astype(cd))


def _router(params: Params, x2d: jnp.ndarray, cfg: ModelConfig):
    """Returns (topk_idx (N,K), topk_w (N,K), aux_loss scalar).  Logits in
    float32.  The dropless layer takes them at full precision (near-tied
    experts are where a coarser product would route a row elsewhere); the
    capacity and dense paths keep the backend's default precision they
    have always routed with."""
    hi = jax.lax.Precision.HIGHEST if cfg.moe_impl == "gmm" else None
    logits = jnp.dot(x2d.astype(jnp.float32),
                     params["router"].astype(jnp.float32),
                     precision=hi)                                 # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        topk_w = topk_w / jnp.maximum(topk_w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load balance loss
    E = cfg.num_experts
    me = probs.mean(axis=0)                                        # (E,)
    ce = jnp.zeros((E,)).at[topk_idx.reshape(-1)].add(1.0)
    ce = ce / jnp.maximum(ce.sum(), 1.0)
    aux = E * jnp.sum(me * ce)
    return topk_idx, topk_w, aux


def moe_dense(params: Params, x: jnp.ndarray, cfg: ModelConfig):
    """Oracle: all experts on all tokens. x: (B,T,d).  Returns (y, aux,
    routed (B*T, experts) rows routed to each expert)."""
    cd = cfg.compute_dtype
    B, T, d = x.shape
    x2d = x.reshape(-1, d).astype(cd)
    idx, w, aux = _router(params, x2d, cfg)
    E = cfg.num_experts
    outs = _expert_ffn(params["w_gate"], params["w_up"], params["w_down"],
                       jnp.broadcast_to(x2d, (E,) + x2d.shape), cd)  # (E,N,d)
    onehot = jax.nn.one_hot(idx, E, dtype=cd) * w.astype(cd)[..., None]
    comb = jnp.einsum("nke,end->nd", onehot, outs)
    return comb.reshape(B, T, d), aux, _routed(*_held(idx, cfg), E)


def moe_scatter(params: Params, x: jnp.ndarray, cfg: ModelConfig):
    """Sort-based capacity dispatch. x: (B,T,d).  Returns (y, aux,
    routed (B*T, experts) rows routed to each expert, dropped or not)."""
    cd = cfg.compute_dtype
    B, T, d = x.shape
    N = B * T
    K = cfg.num_experts_per_tok
    E = cfg.num_experts
    C = max(int(N * K / E * cfg.capacity_factor), K)
    x2d = x.reshape(N, d).astype(cd)
    idx, w, aux = _router(params, x2d, cfg)                        # (N,K)
    flat_e = idx.reshape(-1)                                       # (N*K,)
    flat_t = jnp.repeat(jnp.arange(N), K)
    flat_w = w.reshape(-1)
    # position of each slot within its expert (stable over token order)
    order = jnp.argsort(flat_e, stable=True)
    ranks = jnp.zeros((N * K,), jnp.int32)
    seg = jax.nn.one_hot(flat_e[order], E, dtype=jnp.int32)
    pos_sorted = jnp.cumsum(seg, axis=0)[jnp.arange(N * K), flat_e[order]] - 1
    ranks = ranks.at[order].set(pos_sorted)
    keep = ranks < C
    # scatter tokens into (E, C, d)
    buf = jnp.zeros((E, C, d), cd)
    e_idx = jnp.where(keep, flat_e, 0)
    c_idx = jnp.where(keep, ranks, 0)
    vals = jnp.where(keep[:, None], x2d[flat_t], 0)
    buf = buf.at[e_idx, c_idx].add(vals)
    out_buf = _expert_ffn(params["w_gate"], params["w_up"], params["w_down"],
                          buf, cd)                                  # (E,C,d)
    gathered = out_buf[e_idx, c_idx]                                # (N*K, d)
    gathered = jnp.where(keep[:, None], gathered, 0) * flat_w[:, None].astype(cd)
    comb = jnp.zeros((N, d), cd).at[flat_t].add(gathered)
    return comb.reshape(B, T, d), aux, _routed(*_held(idx, cfg), E)


def _held(idx: jnp.ndarray, cfg: ModelConfig):
    """Routed expert ids (N, K) -> (index among the held experts, held?)."""
    local = idx - cfg.experts_offset
    return local, (local >= 0) & (local < cfg.held_experts)


def expert_layout(local: jnp.ndarray, held: jnp.ndarray, E: int, tm: int):
    """Where each (token, held expert) pair goes in the grouped matmul.

    Pairs are sorted by expert, each expert's group padded to whole tiles
    of ``tm`` rows, groups one after another.  Returns
      dest (N, K): the pair's row, M (out of range) where not held;
      src (M,): the token of each row (0 on padding rows, never read back);
      tile_group (M // tm,): the expert each tile multiplies by;
      live (): tiles holding a group (at least 1);
    with M, static, the most rows any routing can need: N * min(K, E)
    pairs, and at most tm - 1 padding rows per expert."""
    N, K = local.shape
    tiles = (N * min(K, E) + E * (tm - 1)) // tm
    M = tiles * tm
    flat = jnp.where(held, local, E).reshape(-1)                  # (N*K,)
    hot = jax.nn.one_hot(flat, E, dtype=jnp.int32)                # 0 if not held
    rank = jnp.take_along_axis(jnp.cumsum(hot, 0) - hot,
                               jnp.minimum(flat, E - 1)[:, None], 1)[:, 0]
    padded = -(-hot.sum(0) // tm) * tm                            # (E,)
    ends = jnp.cumsum(padded)
    starts = ends - padded
    dest = jnp.where(flat < E, starts[jnp.minimum(flat, E - 1)] + rank, M)
    src = jnp.zeros((M,), jnp.int32).at[dest].set(
        jnp.repeat(jnp.arange(N, dtype=jnp.int32), K), mode="drop")
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(tiles) * tm, side="right"),
        E - 1).astype(jnp.int32)
    live = jnp.maximum(ends[-1] // tm, 1).astype(jnp.int32)
    return dest.reshape(N, K), src, tile_group, live


EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def moe_gmm(params: Params, x: jnp.ndarray, cfg: ModelConfig,
            name: str = "expert_gmm", layer=None):
    """Dropless grouped-matmul dispatch over the held experts. x: (B,T,d).
    Returns (y, aux, routed (B*T, held) rows routed to each held expert).
    ``name`` names the grouped matmul's kernel in a device trace.  With
    ``layer``, the expert weights are those of every layer, (R, E, ...),
    and the matmuls read layer ``layer`` of them in place."""
    from ..kernels import dispatch
    cd = cfg.compute_dtype
    B, T, d = x.shape
    E = cfg.held_experts
    if layer is None:
        params = {**params, **{k: params[k][None] for k in EXPERT_WEIGHTS}}
        layer = 0
    x2d = x.reshape(B * T, d).astype(cd)
    with jax.named_scope("moe.route"):
        idx, w, aux = _router(params, x2d, cfg)                    # (N, K)
        local, held = _held(idx, cfg)
        dest, src, tile_group, live = expert_layout(local, held, E,
                                                    dispatch.EXPERT_TILE)
    with jax.named_scope("moe.experts"):
        mm = lambda a, k: dispatch.expert_matmul(
            a, params[k], layer, tile_group, live, backend=cfg.backend,
            name=name)
        xs = x2d[src]
        h = jax.nn.silu(mm(xs, "w_gate")) * mm(xs, "w_up")
        out = mm(h.astype(cd), "w_down")                           # (M, d)
        # a pair held elsewhere has no row (dest M): it reads 0, never a
        # row past the live tiles, which hold whatever the buffer held
        # (a NaN there would survive a zero weight)
        pairs = jnp.take(out, dest, axis=0, mode="fill", fill_value=0)
        y = jnp.einsum("nk,nkd->nd", w.astype(jnp.float32),
                       pairs.astype(jnp.float32))                  # (N, d)
    return y.astype(cd).reshape(B, T, d), aux, _routed(local, held, E)


def _routed(local, held, E):
    return jnp.where(held[..., None],
                     jax.nn.one_hot(local, E, dtype=jnp.int32), 0).sum(1)


def apply_moe(params: Params, x: jnp.ndarray, cfg: ModelConfig,
              mode: str = "full", layer=None):
    """Returns (y, aux_loss, routed (B*T, held experts) int32).  Adds
    shared experts (DeepSeek) when present.  ``mode`` (the transformer's)
    names the grouped matmul: ``expert_gmm`` in verify, where the step's
    per-layer metric reads it, ``expert_gmm_<mode>`` elsewhere.
    ``layer``: see ``moe_gmm``."""
    if cfg.moe_impl == "gmm":
        y, aux, routed = moe_gmm(
            params, x, cfg,
            "expert_gmm" if mode == "verify" else "expert_gmm_" + mode,
            layer)
    else:
        impl = moe_dense if cfg.moe_impl == "dense" else moe_scatter
        y, aux, routed = impl(params, x, cfg)
    if cfg.num_shared_experts:
        cd = cfg.compute_dtype
        with jax.named_scope("moe.shared"):
            xs = x.astype(cd)
            g = jax.nn.silu(xs @ params["shared_gate"].astype(cd))
            u = xs @ params["shared_up"].astype(cd)
            y = y + (g * u) @ params["shared_down"].astype(cd)
    return y, aux, routed
