"""Transformer assembly: heterogeneous layer stacks as a compact scan.

Layers are grouped by position inside the repeating ``block_pattern`` and
their parameters stacked over the ``R = num_layers / period`` repetitions, so
the whole stack lowers to ONE ``lax.scan`` whose body contains one period
(Jamba: 8 sublayers, dense archs: 1).  This keeps the HLO small enough to
compile 96-layer/398B configs in the multi-pod dry-run.

Execution modes (static):
  "full"    — train / scoring: full self-attention, zero-init recurrent state.
  "prefill" — "full" + populate the decode state (KV buffers, final states).
  "decode"  — T new tokens from cached state, commit everything.
  "replay"  — decode with per-row gating ``n_commit``: only the first
              n_commit positions update caches/states (speculative commit of
              the winning row, see core/spec_engine.py).
  "verify"  — the paper's batched speculation: (B, k, w+1) rows attend to the
              shared cache bifurcated-ly; states are read-only; returns
              per-row logits (+ KV tails for attention-only fast commit).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import mla as mla_lib
from . import moe as moe_lib
from .attention import attn_full, attn_verify, init_attention
from .cache import (cache_buffer_len, group_ids, key_positions, kv_write,
                    paged_kv_write, prefill_write, select_step_state,
                    write_slots)
from .config import (ATTN, MAMBA, MLSTM, MOE, NO_MLP, SLSTM, BlockSpec,
                     ModelConfig)
from .layers import (apply_mlp, apply_norm, init_embed, init_mlp, init_norm)
from .mamba import init_mamba, mamba_mix, mamba_mix_steps
from .xlstm import (init_mlstm, init_slstm, mlstm_mix, slstm_mix)

Params = Dict[str, Any]


# ----------------------------------------------------------------------------
# init
# ----------------------------------------------------------------------------
def init_block(rng, cfg: ModelConfig, spec: BlockSpec) -> Params:
    ks = jax.random.split(rng, 2)
    p: Params = {"norm1": init_norm(cfg)}
    if spec.mixer == ATTN and cfg.mla:
        p["mixer"] = mla_lib.init_mla(ks[0], cfg)
    elif spec.mixer == ATTN:
        p["mixer"] = init_attention(ks[0], cfg)
    elif spec.mixer == MAMBA:
        p["mixer"] = init_mamba(ks[0], cfg)
    elif spec.mixer == MLSTM:
        p["mixer"] = init_mlstm(ks[0], cfg)
    elif spec.mixer == SLSTM:
        p["mixer"] = init_slstm(ks[0], cfg)
    else:
        raise ValueError(spec.mixer)
    if spec.mlp != NO_MLP:
        p["norm2"] = init_norm(cfg)
        if spec.mlp == MOE:
            p["mlp"] = moe_lib.init_moe(ks[1], cfg)
        else:
            p["mlp"] = init_mlp(ks[1], cfg, spec.mlp)
    return p


def init_params(rng, cfg: ModelConfig) -> Params:
    cfg.validate()
    groups = group_ids(cfg)
    ks = jax.random.split(rng, 2 + len(groups))
    params: Params = {"embed": init_embed(ks[0], cfg),
                      "final_norm": init_norm(cfg)}
    for (gid, spec, R), k in zip(groups, ks[2:]):
        keys = jax.random.split(k, R)
        params[gid] = jax.vmap(lambda kk: init_block(kk, cfg, spec))(keys)
    return params


# ----------------------------------------------------------------------------
# one sublayer in one mode
# ----------------------------------------------------------------------------
def _apply_block(bp: Params, x: jnp.ndarray, cfg: ModelConfig,
                 spec: BlockSpec, mode: str, gst: Optional[Dict],
                 ctx: Dict, experts: Optional[Tuple[Dict, Any]] = None
                 ) -> Tuple[jnp.ndarray, Optional[Dict], jnp.ndarray]:
    """Returns (x_out, new_group_state (or None), moe_aux).  In verify mode
    an expert block adds ``expert_rows`` (B, held experts) to its group's
    entry: the rows of each slot's verify call routed to each held
    expert.  ``experts``: (the group's expert weights stacked over its
    layers, this layer's index), for a grouped-matmul expert block whose
    ``bp`` leaves them out (``_whole_experts``)."""
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(bp["norm1"], x, cfg)
    new_gst: Optional[Dict] = None
    K = ctx.get("k_rows")  # static int for verify mode

    if spec.mixer == ATTN and cfg.mla:
        y, new_gst = mla_lib.attn_block(bp["mixer"], h, cfg, mode, gst, ctx)

    elif spec.mixer == ATTN:
        if mode in ("full", "prefill"):
            y, (k_new, v_new) = attn_full(bp["mixer"], h, cfg,
                                          ctx["positions"])
            if mode == "prefill":
                if ctx.get("paged"):
                    # shared pool: positions routed through each slot's
                    # page table (model.prefill precomputed the physical
                    # slots; no ring semantics in the paged layout)
                    kc, vc = paged_kv_write(gst["k"], gst["v"], k_new,
                                            v_new, ctx["slots"])
                else:
                    kc, vc = prefill_write(cfg, (gst["k"], gst["v"]),
                                           (k_new, v_new))
                new_gst = {"k": kc, "v": vc}
        elif mode in ("decode", "replay"):
            # Bifurcated decode (= verify with k=1): the query block attends
            # the shared cache and its own causal tail SEPARATELY, then the
            # new KV is scatter-written.  The previous concat([cache, new])
            # copied the full cache per layer AND forced an all-gather when
            # the operands' shardings disagreed — 2.1 TB/dev/token for
            # qwen2-72b decode_32k (EXPERIMENTS §Perf it-4).
            B, T = h.shape[:2]
            y, k_t, v_t = attn_verify(bp["mixer"], h[:, None], cfg,
                                      ctx["positions"], gst["k"], gst["v"],
                                      ctx["cache_pos"],
                                      cur_len=ctx.get("cur_len"),
                                      page_table=ctx.get("page_table"))
            y = y[:, 0]
            if ctx.get("paged"):
                kc, vc = paged_kv_write(gst["k"], gst["v"], k_t[:, 0],
                                        v_t[:, 0], ctx["slots"],
                                        gate=ctx.get("gate"))
            else:
                kc, vc = kv_write((gst["k"], gst["v"]),
                                  (k_t[:, 0], v_t[:, 0]),
                                  ctx["slots"], gate=ctx.get("gate"))
            new_gst = {"k": kc, "v": vc}
        elif mode == "verify":
            B = h.shape[0] // K         # pool states carry no batch dim
            hv = h.reshape(B, K, h.shape[-2], h.shape[-1])
            y, k_t, v_t = attn_verify(bp["mixer"], hv, cfg, ctx["positions"],
                                      gst["k"], gst["v"], ctx["cache_pos"],
                                      cur_len=ctx.get("cur_len"),
                                      page_table=ctx.get("page_table"),
                                      tail_mask=ctx.get("tail_mask"))
            y = y.reshape(x.shape)
            new_gst = {"k_tail": k_t, "v_tail": v_t}
        else:
            raise ValueError(mode)

    elif spec.mixer == MAMBA:
        if mode in ("full", "prefill"):
            B = h.shape[0]
            conv0 = jnp.zeros((B, cfg.mamba_d_conv - 1, cfg.mamba_d_inner),
                              cfg.compute_dtype)
            ssm0 = jnp.zeros((B, cfg.mamba_d_inner, cfg.mamba_d_state),
                             jnp.float32)
            y, conv, ssm = mamba_mix(bp["mixer"], h, cfg, conv0, ssm0)
            if mode == "prefill":
                new_gst = {"conv": conv, "ssm": ssm}
        elif mode == "decode":
            y, conv, ssm = mamba_mix(bp["mixer"], h, cfg, gst["conv"],
                                     gst["ssm"])
            new_gst = {"conv": conv, "ssm": ssm}
        elif mode == "replay":
            y, conv_ext, ssm_steps = mamba_mix_steps(bp["mixer"], h, cfg,
                                                     gst["conv"], gst["ssm"])
            n = ctx["n_commit"]
            dc = cfg.mamba_d_conv
            # conv state after n steps = conv_ext[:, n : n+dc-1]
            conv = jax.vmap(
                lambda e, nn: jax.lax.dynamic_slice_in_dim(e, nn, dc - 1, 0)
            )(conv_ext, n)
            ssm = select_step_state(ssm_steps, gst["ssm"], n)
            new_gst = {"conv": conv.astype(gst["conv"].dtype), "ssm": ssm}
        elif mode == "verify":
            rep = lambda a: jnp.repeat(a, K, axis=0)
            y, _, _ = mamba_mix(bp["mixer"], h, cfg, rep(gst["conv"]),
                                rep(gst["ssm"]))
            new_gst = None

    elif spec.mixer == MLSTM:
        di = int(cfg.d_model * cfg.xlstm_mlstm_proj_factor)
        if mode in ("full", "prefill"):
            B = h.shape[0]
            nh = cfg.num_heads
            dh = di // nh
            st0 = (jnp.zeros((B, nh, dh, dh), jnp.float32),
                   jnp.zeros((B, nh, dh), jnp.float32),
                   jnp.full((B, nh), -1e9, jnp.float32))
            conv0 = jnp.zeros((B, cfg.xlstm_conv_kernel - 1, di),
                              cfg.compute_dtype)
            y, st, conv = mlstm_mix(bp["mixer"], h, cfg, st0, conv0,
                                    chunkwise=ctx.get("chunkwise", False))
            if mode == "prefill":
                new_gst = {"C": st[0], "n": st[1], "m": st[2], "conv": conv}
        elif mode == "decode":
            st = (gst["C"], gst["n"], gst["m"])
            y, st, conv = mlstm_mix(bp["mixer"], h, cfg, st, gst["conv"])
            new_gst = {"C": st[0], "n": st[1], "m": st[2], "conv": conv}
        elif mode == "replay":
            st = (gst["C"], gst["n"], gst["m"])
            y, st_steps, conv_ext = mlstm_mix(bp["mixer"], h, cfg, st,
                                              gst["conv"], per_step=True)
            n = ctx["n_commit"]
            dc = cfg.xlstm_conv_kernel
            conv = jax.vmap(
                lambda e, nn: jax.lax.dynamic_slice_in_dim(e, nn, dc - 1, 0)
            )(conv_ext, n)
            C, nv, m = select_step_state(
                st_steps, (gst["C"], gst["n"], gst["m"]), n)
            new_gst = {"C": C, "n": nv, "m": m,
                       "conv": conv.astype(gst["conv"].dtype)}
        elif mode == "verify":
            rep = lambda a: jnp.repeat(a, K, axis=0)
            st = (rep(gst["C"]), rep(gst["n"]), rep(gst["m"]))
            y, _, _ = mlstm_mix(bp["mixer"], h, cfg, st, rep(gst["conv"]))
            new_gst = None

    elif spec.mixer == SLSTM:
        if mode in ("full", "prefill"):
            B = h.shape[0]
            nh = cfg.num_heads
            dh = cfg.d_model // nh
            z = jnp.zeros((B, nh, dh), jnp.float32)
            st0 = (z, z, z, jnp.full((B, nh, dh), -1e9, jnp.float32))
            y, st = slstm_mix(bp["mixer"], h, cfg, st0)
            if mode == "prefill":
                new_gst = {"c": st[0], "n": st[1], "h": st[2], "m": st[3]}
        elif mode == "decode":
            st = (gst["c"], gst["n"], gst["h"], gst["m"])
            y, st = slstm_mix(bp["mixer"], h, cfg, st)
            new_gst = {"c": st[0], "n": st[1], "h": st[2], "m": st[3]}
        elif mode == "replay":
            st = (gst["c"], gst["n"], gst["h"], gst["m"])
            y, st_steps = slstm_mix(bp["mixer"], h, cfg, st, per_step=True)
            c, nv, hh, m = select_step_state(st_steps, st, ctx["n_commit"])
            new_gst = {"c": c, "n": nv, "h": hh, "m": m}
        elif mode == "verify":
            rep = lambda a: jnp.repeat(a, K, axis=0)
            st = (rep(gst["c"]), rep(gst["n"]), rep(gst["h"]), rep(gst["m"]))
            y, _ = slstm_mix(bp["mixer"], h, cfg, st)
            new_gst = None
    else:
        raise ValueError(spec.mixer)

    x = x + y.astype(x.dtype)

    if spec.mlp != NO_MLP:
        h2 = apply_norm(bp["norm2"], x, cfg)
        if spec.mlp == MOE:
            mlp, layer = bp["mlp"], None
            if experts is not None:
                mlp, layer = {**mlp, **experts[0]}, experts[1]
            y2, aux, routed = moe_lib.apply_moe(mlp, h2, cfg, mode, layer)
            if mode == "verify":
                # (B*K*W1 tokens, held) -> per slot
                rows = routed.reshape(x.shape[0] // K, -1, routed.shape[-1])
                new_gst = {**(new_gst or {}),
                           "expert_rows": rows.sum(1)}
        else:
            y2 = apply_mlp(bp["mlp"], h2, cfg, spec.mlp)
        x = x + y2.astype(x.dtype)
    return x, new_gst, aux


# ----------------------------------------------------------------------------
# full stack
# ----------------------------------------------------------------------------
def _whole_experts(cfg: ModelConfig, spec: BlockSpec, gp: Params):
    """A grouped-matmul expert block's params without its expert weights,
    and those weights whole, stacked over the group's layers (else None).
    The grouped matmul reads a layer's experts in place: sliced per layer,
    as the scan slices everything else, they would be copied before every
    call (the whole layer's experts, since the kernel's operand must be an
    array)."""
    if spec.mlp != MOE or cfg.moe_impl != "gmm":
        return gp, None
    mlp = dict(gp["mlp"])
    whole = {k: mlp.pop(k) for k in moe_lib.EXPERT_WEIGHTS}
    return {**gp, "mlp": mlp}, whole


def run_stack(params: Params, cfg: ModelConfig, x: jnp.ndarray, mode: str,
              state: Optional[Dict], ctx: Dict,
              remat: bool = False) -> Tuple[jnp.ndarray, Dict, jnp.ndarray]:
    """Apply every layer. Returns (x, new_group_states, moe_aux_mean)."""
    aux0 = jnp.zeros((), jnp.float32)
    new_groups: Dict[str, Any] = {}

    # prefix layers (unrolled)
    for i, spec in enumerate(cfg.prefix_blocks):
        gid = f"pre{i}"
        gp, whole = _whole_experts(cfg, spec, params[gid])
        bp = jax.tree_util.tree_map(lambda a: a[0], gp)
        gst = (jax.tree_util.tree_map(lambda a: a[0], state["groups"][gid])
               if state is not None and gid in state["groups"] else None)
        x, ngst, aux = _apply_block(bp, x, cfg, spec, mode, gst, ctx,
                                    None if whole is None else (whole, 0))
        aux0 = aux0 + aux
        if ngst is not None:
            new_groups[gid] = jax.tree_util.tree_map(lambda a: a[None], ngst)

    # periodic body: one scan over R periods
    P = cfg.pattern_period
    gids = [f"p{j}" for j in range(P)]
    split = [_whole_experts(cfg, cfg.block_pattern[j], params[g])
             for j, g in enumerate(gids)]
    xs_params = tuple(gp for gp, _ in split)
    wholes = tuple(w for _, w in split)
    xs_state = None
    if state is not None:
        xs_state = tuple(state["groups"].get(g) for g in gids)
    # the layer index rides along only where a block needs it
    xs = (xs_params, xs_state)
    if any(w is not None for w in wholes):
        xs += (jnp.arange(cfg.num_periods, dtype=jnp.int32),)

    from ..distributed import act_sharding

    def body(carry, xs):
        xc, aux = carry
        ps, sts = xs[:2]
        r = xs[2] if len(xs) > 2 else None
        new_sts = []
        for j in range(P):
            gst = sts[j] if sts is not None else None
            xc, ngst, a = _apply_block(
                ps[j], xc, cfg, cfg.block_pattern[j], mode, gst, ctx,
                None if wholes[j] is None else (wholes[j], r))
            xc = act_sharding.constrain(xc, "residual")
            new_sts.append(ngst)
            aux = aux + a
        return (xc, aux), tuple(new_sts)

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)

    from .runtime_flags import UNROLL_FOR_ANALYSIS
    if UNROLL_FOR_ANALYSIS:
        # python loop so HloCostAnalysis sees every layer (roofline calib)
        R = cfg.num_periods
        carry = (x, aux0)
        ys_list = []
        for r in range(R):
            xs_r = jax.tree_util.tree_map(lambda a: a[r], xs)
            carry, y_r = body(carry, xs_r)
            ys_list.append(y_r)
        x, aux_total = carry
        has_ys = len(jax.tree_util.tree_leaves(ys_list[0])) > 0
        ys = (jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys_list)
              if has_ys else ys_list[0])
    else:
        (x, aux_total), ys = jax.lax.scan(body, (x, aux0), xs)
    for gid, ngst in zip(gids, ys):
        if ngst is not None:
            new_groups[gid] = ngst
    n_moe = max(sum(1 for b in (tuple(cfg.prefix_blocks)
                                + tuple(cfg.block_pattern) * cfg.num_periods)
                    if b.mlp == MOE), 1)
    return x, new_groups, aux_total / n_moe
