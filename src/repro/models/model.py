"""Top-level language-model API: init / forward / prefill / decode / verify.

These are the pure functions the training loop, the serving engine and the
speculative-decoding core compose.  Everything is jit-friendly: shapes are
static, sequence advance is tracked by ``state["cur_len"]``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .cache import (buffer_len, cache_names, group_ids, init_state,
                    is_paged, key_positions, kv_commit_in_place, kv_write,
                    paged_dims, paged_kv_write, phys_slots, write_slots)
from .config import ATTN, MOE, MROPE, ModelConfig, layer_blocks
from .layers import apply_norm, embed_tokens, lm_logits
from .transformer import init_params, run_stack

Params = Dict[str, Any]
State = Dict[str, Any]

__all__ = ["init_params", "init_state", "forward", "prefill", "decode",
           "verify", "commit_kv_tails", "has_recurrent", "has_experts",
           "split_expert_rows", "make_positions"]


def has_recurrent(cfg: ModelConfig) -> bool:
    return any(b.mixer != ATTN for b in layer_blocks(cfg))


def has_experts(cfg: ModelConfig) -> bool:
    return any(b.mlp == MOE for b in layer_blocks(cfg))


def split_expert_rows(tails: Dict) -> Tuple[Dict, Optional[jnp.ndarray]]:
    """Separate ``verify``'s per-group ``expert_rows`` ((R, B, held) each)
    from the cache tails.  Returns (tails without them, (layers, B, held)
    rows routed to each held expert by each slot, or None)."""
    rows = [g["expert_rows"] for g in tails.values() if "expert_rows" in g]
    rest = {gid: {k: v for k, v in g.items() if k != "expert_rows"}
            for gid, g in tails.items()}
    rest = {gid: g for gid, g in rest.items() if g}
    return rest, (jnp.concatenate(rows, 0) if rows else None)


def make_positions(cfg: ModelConfig, B: int, T: int,
                   offset: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    if offset is not None:
        pos = pos + offset[:, None]
    if cfg.rope == MROPE:
        # text tokens: t/h/w positions coincide (Qwen2-VL §3.1)
        pos = jnp.broadcast_to(pos[None], (3, B, T))
    return pos


def _embed(params: Params, cfg: ModelConfig, tokens, embeds):
    if embeds is not None:
        return embeds.astype(cfg.compute_dtype)
    return embed_tokens(params["embed"], tokens, cfg)


def forward_hidden(params: Params, cfg: ModelConfig, tokens=None,
                   embeds=None, positions=None, remat: bool = False
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full forward up to the final norm. Returns (hidden (B,T,d), moe_aux).

    Splitting the LM head out lets the training loss compute logits in
    vocab/time chunks (train_loop.chunked_lm_loss) — materialising the full
    (B, T, 256k) f32 logits of Nemotron/Gemma-class vocabs would not fit
    v5e HBM.
    """
    x = _embed(params, cfg, tokens, embeds)
    B, T = x.shape[:2]
    if positions is None:
        positions = make_positions(cfg, B, T)
    ctx = {"positions": positions}
    x, _, aux = run_stack(params, cfg, x, "full", None, ctx, remat=remat)
    return apply_norm(params["final_norm"], x, cfg), aux


def forward(params: Params, cfg: ModelConfig, tokens=None, embeds=None,
            positions=None, remat: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full forward (train / scoring). Returns (logits f32, moe_aux)."""
    x, aux = forward_hidden(params, cfg, tokens, embeds, positions, remat)
    return lm_logits(params["embed"], x, cfg), aux


def prefill(params: Params, cfg: ModelConfig, state: State, tokens=None,
            embeds=None, positions=None,
            last_only: bool = False) -> Tuple[jnp.ndarray, State]:
    """Process the prompt, populating ``state``. All rows same length T.

    ``state`` must be freshly allocated (cur_len == 0).  ``last_only``
    computes logits for the final position only (serving never needs the
    rest; a 32k x 152k-vocab logit tensor would dwarf the KV cache).
    """
    x = _embed(params, cfg, tokens, embeds)
    B, T = x.shape[:2]
    if positions is None:
        positions = make_positions(cfg, B, T)
    ctx = {"positions": positions}
    if is_paged(state):
        # prefill writes positions 0..T-1 of every row through its page
        # table (pages must already be allocated — see spec_engine)
        NP, ps, _ = paged_dims(state)
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        ctx["paged"] = True
        ctx["slots"] = phys_slots(state["page_table"], pos, ps, NP)
    x, new_groups, _ = run_stack(params, cfg, x, "prefill", state, ctx)
    x = apply_norm(params["final_norm"], x, cfg)
    if last_only:
        x = x[:, -1:]
    logits = lm_logits(params["embed"], x, cfg)
    new_state = {**state, "cur_len": state["cur_len"] + T,
                 "groups": {**state["groups"], **new_groups}}
    return logits, new_state


def decode(params: Params, cfg: ModelConfig, state: State,
           tokens: jnp.ndarray,
           n_commit: Optional[jnp.ndarray] = None
           ) -> Tuple[jnp.ndarray, State]:
    """Decode T new tokens from cached state.

    With ``n_commit`` (B,), runs in *replay* mode: only the first n_commit
    positions of each row update the caches/recurrent state — this is the
    speculative commit of the winning draft (paper App. D's "overwrite all
    rows with the accepted speculation", adapted to recurrent state).
    """
    B, T = tokens.shape[:2]
    cur = state["cur_len"]
    positions = make_positions(cfg, B, T, offset=cur)
    gid0 = next(gid for gid, s, _ in group_ids(cfg) if s.mixer == ATTN
                ) if not _pure_recurrent(cfg) else None
    adv = n_commit if n_commit is not None else T
    ctx: Dict[str, Any] = {"positions": positions}
    if gid0 is not None:
        if is_paged(state):
            NP, ps, pps = paged_dims(state)
            S = pps * ps                    # logical capacity per slot
            ctx["paged"] = True
            ctx["page_table"] = state["page_table"]
            ctx["slots"] = phys_slots(state["page_table"],
                                      write_slots(cfg, S, cur, T), ps, NP)
        else:
            S = buffer_len(state["groups"][gid0])
            ctx["slots"] = write_slots(cfg, S, cur, T)
        ctx["cache_pos"] = key_positions(cfg, S, cur)   # pre-write owners
        ctx["cur_len"] = cur        # scalar-prefetch operand (Pallas backend)
    mode = "decode"
    if n_commit is not None:
        mode = "replay"
        ctx["n_commit"] = n_commit
        if gid0 is not None:
            ctx["gate"] = jnp.arange(T)[None, :] < n_commit[:, None]
    x = _embed(params, cfg, tokens, None)
    x, new_groups, _ = run_stack(params, cfg, x, mode, state, ctx)
    x = apply_norm(params["final_norm"], x, cfg)
    logits = lm_logits(params["embed"], x, cfg)
    adv = n_commit if n_commit is not None else T
    new_state = {**state, "cur_len": cur + adv,
                 "groups": {**state["groups"], **new_groups}}
    return logits, new_state


def verify(params: Params, cfg: ModelConfig, state: State,
           tokens: jnp.ndarray, pos_off=None,
           tail_mask=None) -> Tuple[jnp.ndarray, Dict]:
    """The paper's batched verification call.

    tokens: (B, k, w+1) — row i is [last_token, draft_i(0..w-1)].
    Returns (logits (B, k, w+1, V) f32, kv_tails for attention groups).
    State is NOT advanced (pure read).

    Groups with an expert layer also carry ``expert_rows`` (R, B, held
    experts): rows of each slot's call routed to each held expert
    (``split_expert_rows``).

    Tree mode (DESIGN.md §11) passes the whole token tree as the single row
    k == 1 with two STATIC topology constants:
      pos_off:   (w+1,) int numpy array — per-node position offset (tree
                 LEVEL, 0 for the committed last token) replacing the linear
                 arange; node i gets absolute position cur + pos_off[i].
      tail_mask: (w+1, w+1) bool numpy array — ancestor-or-self visibility
                 between tree nodes, threaded to the attention tail mask.
    Recurrent mixers run verify rows as causal SEQUENCES, which has no valid
    tree layout — callers gate tree mode on ``not has_recurrent(cfg)``
    (core/spec_engine.py raises at config validation).
    """
    B, K, W1 = tokens.shape
    cur = state["cur_len"]
    if pos_off is None:
        positions = make_positions(cfg, B, W1, offset=cur)
    else:
        pos = (jnp.asarray(pos_off, jnp.int32)[None, :]
               + cur[:, None])                            # (B, W1)
        if cfg.rope == MROPE:
            pos = jnp.broadcast_to(pos[None], (3, B, W1))
        positions = pos
    gid0 = next((gid for gid, s, _ in group_ids(cfg) if s.mixer == ATTN), None)
    ctx: Dict[str, Any] = {"positions": positions, "k_rows": K,
                           "tail_mask": tail_mask}
    if gid0 is not None:
        if is_paged(state):
            _, ps, pps = paged_dims(state)
            S = pps * ps
            ctx["paged"] = True
            ctx["page_table"] = state["page_table"]
        else:
            S = buffer_len(state["groups"][gid0])
        ctx["cache_pos"] = key_positions(cfg, S, cur)
        ctx["cur_len"] = cur        # scalar-prefetch operand (Pallas backend)
    x = _embed(params, cfg, tokens.reshape(B * K, W1), None)
    x, kv_tails, _ = run_stack(params, cfg, x, "verify", state, ctx)
    x = apply_norm(params["final_norm"], x, cfg)
    logits = lm_logits(params["embed"], x, cfg)
    return logits.reshape(B, K, W1, -1), kv_tails


def commit_kv_tails(cfg: ModelConfig, state: State, kv_tails: Dict,
                    winner: jnp.ndarray, n_commit: jnp.ndarray) -> State:
    """Fast commit for attention-only archs: write the winning row's accepted
    KV tail (or latent tail, MLA) into the shared cache (no replay forward
    needed).  ``kv_tails``: {gid: {"<cache>_tail": (R, B, K, W1, ...)}}
    for each cache leaf of the group; other entries are ignored.

    Linear caches write it in place (``kv_commit_in_place``).  The gated
    scatter stays where that cannot work: ring caches (a tail may wrap past
    the end of the ring) and caches whose slot or sequence dim the active
    mesh splits (``act_sharding.splits_cache_rows``: a per-row window there
    makes GSPMD all-gather the cache).  Paged states route the scatter
    through each slot's page table."""
    from ..distributed import act_sharding
    cur = state["cur_len"]
    groups = dict(state["groups"])
    paged = is_paged(state)
    if paged:
        NP, ps, pps = paged_dims(state)
        S = pps * ps
    else:
        gid0 = next(gid for gid, s, _ in group_ids(cfg) if s.mixer == ATTN)
        S = buffer_len(state["groups"][gid0])
    ring = cfg.sliding_window is not None and cfg.sliding_window <= S
    for gid, tails in kv_tails.items():
        names = cache_names(state["groups"][gid])
        tl = [tails[n + "_tail"] for n in names]      # (R, B, K, W1, ...)
        R, B, K, W1 = tl[0].shape[:4]
        wsel = winner.reshape((1, B, 1, 1) + (1,) * (tl[0].ndim - 4))
        news = [jnp.take_along_axis(t, wsel, axis=2)[:, :, 0] for t in tl]
        caches = tuple(state["groups"][gid][n] for n in names)
        if not (paged or ring or W1 > S
                or act_sharding.splits_cache_rows(caches[0].shape)):
            caches = kv_commit_in_place(caches, news, cur, n_commit)
        elif paged:
            slots = write_slots(cfg, S, cur, W1)
            gate = jnp.arange(W1)[None, :] < n_commit[:, None]
            phys = phys_slots(state["page_table"], slots, ps, NP)
            caches = jax.vmap(
                lambda kp, vp, kn, vn: paged_kv_write(kp, vp, kn, vn, phys,
                                                      gate=gate)
            )(*caches, *news)
        else:
            slots = write_slots(cfg, S, cur, W1)
            gate = jnp.arange(W1)[None, :] < n_commit[:, None]
            caches = jax.vmap(
                lambda cs, ns: kv_write(cs, ns, slots, gate=gate)
            )(caches, tuple(news))
        groups[gid] = dict(zip(names, caches))
    return {**state, "cur_len": cur + n_commit, "groups": groups}


def _pure_recurrent(cfg: ModelConfig) -> bool:
    return all(b.mixer != ATTN for b in layer_blocks(cfg))
