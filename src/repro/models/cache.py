"""Decode-state management: KV caches (linear, sliding-window ring, paged),
SSM and xLSTM recurrent states, and the speculative *commit* semantics.

Paper mapping (Appendix D): the paper keeps a batched (k-row) static KV cache,
initialised from a k=1 cache by broadcasting, and after each verification
overwrites all rows with the winning row's accepted entries.  Our TPU-native
default is the *bifurcated* variant instead: ONE shared cache of the context,
per-row KV only for the in-flight (w+1)-token speculative tail; commit writes
the winner's accepted tail into the shared cache.  This removes the k× HBM
traffic (and k× memory) of the paper's layout — see DESIGN.md §3 and
EXPERIMENTS.md §Perf where both layouts are measured.

State layout (everything stacked over the R periods of the layer pattern so
the transformer can ``lax.scan`` over it):

  state = {
    "cur_len": (B,) int32   — #positions committed per sequence,
    "groups": {gid: {...}}  — gid = "pre{i}" or "p{j}"; every leaf has
                               leading dim R (R=1 for prefix groups).
  }

Multi-head latent attention (``cfg.mla``, models/mla.py) keeps a third
kind of decode state in its attention groups: one latent buffer
``{"latent": (R, B, S, kv_lora_rank + qk_rope_head_dim)}`` per layer,
shared by every head, in place of the K/V pair.  It is linear only: the
paged layout refuses it (``paged_supported``), as it refuses windows.  The
writes below take a tuple of caches, so the pair and the latent share them.

Paged layout (DESIGN.md §8): instead of a per-slot linear buffer
(R, B, S, KV, hd), attention groups hold ONE shared page pool
(R, num_pages, page_size, KV, hd) and the state grows four extra leaves:

    "page_table": (B, pages_per_slot) int32  — physical page per logical
                                               page, -1 = unallocated,
    "n_pages":    (B,) int32                 — allocated pages per slot,
    "free_list":  (num_pages,) int32         — free-page stack,
    "free_top":   () int32                   — #free pages (stack pointer).

The page table is shared by every layer (physical page p of every group's
pool belongs to the same slot), page_size matches the Pallas verify
kernel's ``block_s`` cache-streaming grid, and alloc/free/grow are pure
jnp scatter/gather so they run inside the jitted admit/release/spec-step
path.  Recurrent leaves stay per-slot (they are O(1) in sequence length).
Presence of "page_table" is what flags a state as paged (`is_paged`).

The speculative commit (``model.commit_kv_tails``) writes the winner's
accepted tail by layout:

  - linear cache: in place, one window of positions per slot
    (``kv_commit_in_place``), in the cache's own layout, with no gather
    or scatter (those make XLA relayout the whole cache);
  - ring cache (sliding window <= buffer): the gated scatter of
    ``kv_write``, since a tail may wrap past the end of the ring;
  - paged pool: the gated scatter of ``paged_kv_write`` through the slot's
    page table.

A linear cache whose slot or sequence dim the active mesh splits keeps
the scatter too (``act_sharding.splits_cache_rows``: a per-row window
there all-gathers the cache), in one-shot and in continuous serving.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ATTN, MAMBA, MLSTM, SLSTM, BlockSpec, ModelConfig


def cache_buffer_len(cfg: ModelConfig, max_len: int) -> int:
    """Physical KV buffer length: window-sized ring when sliding-window."""
    if cfg.sliding_window is not None and cfg.sliding_window < max_len:
        return cfg.sliding_window
    return max_len


def group_ids(cfg: ModelConfig):
    """Yield (gid, BlockSpec, R) for prefix and body pattern positions."""
    out = []
    for i, b in enumerate(cfg.prefix_blocks):
        out.append((f"pre{i}", b, 1))
    for j, b in enumerate(cfg.block_pattern):
        out.append((f"p{j}", b, cfg.num_periods))
    return out


def cache_names(group: Dict) -> Tuple[str, ...]:
    """The cache leaves of an attention group: the K/V pair or the
    latent."""
    return ("latent",) if "latent" in group else ("k", "v")


def buffer_len(group: Dict) -> int:
    """Positions per slot of an attention group's linear cache."""
    return group[cache_names(group)[0]].shape[2]


def _init_group(cfg: ModelConfig, spec: BlockSpec, R: int, batch: int,
                S: int) -> Dict:
    """Empty decode-state group for one layer position (linear ATTN layout)."""
    hd = cfg.resolved_head_dim
    if spec.mixer == ATTN and cfg.mla:
        return {"latent": jnp.zeros((R, batch, S, cfg.latent_dim),
                                    cfg.compute_dtype)}
    if spec.mixer == ATTN:
        shape = (R, batch, S, cfg.num_kv_heads, hd)
        return {"k": jnp.zeros(shape, cfg.compute_dtype),
                "v": jnp.zeros(shape, cfg.compute_dtype)}
    elif spec.mixer == MAMBA:
        return {
            "conv": jnp.zeros((R, batch, cfg.mamba_d_conv - 1,
                               cfg.mamba_d_inner), cfg.compute_dtype),
            "ssm": jnp.zeros((R, batch, cfg.mamba_d_inner,
                              cfg.mamba_d_state), jnp.float32)}
    elif spec.mixer == MLSTM:
        di = int(cfg.d_model * cfg.xlstm_mlstm_proj_factor)
        nh = cfg.num_heads
        dh = di // nh
        return {
            "C": jnp.zeros((R, batch, nh, dh, dh), jnp.float32),
            "n": jnp.zeros((R, batch, nh, dh), jnp.float32),
            "m": jnp.full((R, batch, nh), -1e9, jnp.float32),
            "conv": jnp.zeros((R, batch, cfg.xlstm_conv_kernel - 1, di),
                              cfg.compute_dtype)}
    elif spec.mixer == SLSTM:
        nh = cfg.num_heads
        dh = cfg.d_model // nh
        # distinct buffers per leaf: sharing one zeros array here makes
        # donation of the enclosing state illegal ("same buffer donated
        # twice" in the jitted admit/spec-step path)
        z = lambda: jnp.zeros((R, batch, nh, dh), jnp.float32)
        return {"c": z(), "n": z(), "h": z(),
                "m": jnp.full((R, batch, nh, dh), -1e9, jnp.float32)}
    raise ValueError(spec.mixer)


def init_state(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    """Allocate an empty decode state for ``batch`` sequences."""
    S = cache_buffer_len(cfg, max_len)
    groups = {gid: _init_group(cfg, spec, R, batch, S)
              for gid, spec, R in group_ids(cfg)}
    return {"cur_len": jnp.zeros((batch,), jnp.int32), "groups": groups}


# ----------------------------------------------------------------------------
# slot management (continuous batching)
# ----------------------------------------------------------------------------
def insert_slot(state: Dict, row_state: Dict, slot) -> Dict:
    """Overwrite batch slot ``slot`` of ``state`` with a batch-1 state.

    ``row_state`` comes from prefilling one request in isolation (batch 1,
    same ``max_len``); writing it over the slot replaces *every* leaf of the
    previous occupant — KV rows, recurrent states and cur_len — so request
    N+1 in a reused slot cannot observe request N's cache.  ``slot`` may be
    a traced scalar (jit-compatible admission).
    """
    def ins(leaf, row):
        if leaf.shape[2:] != row.shape[2:] or row.shape[1] != 1:
            raise ValueError(f"slot insert shape mismatch: {leaf.shape} "
                             f"vs {row.shape}")
        return leaf.at[:, slot].set(row[:, 0])

    groups = {gid: jax.tree_util.tree_map(ins, g, row_state["groups"][gid])
              for gid, g in state["groups"].items()}
    return {"cur_len": state["cur_len"].at[slot].set(row_state["cur_len"][0]),
            "groups": groups}


def zero_slot_stats(stats: Dict, slot) -> Dict:
    """Zero batch slot ``slot``'s row in every per-slot stats array.

    Works for any trailing shape — scalar counters (B,), histograms (B, n)
    and the adaptive controller's per-arm state (B, A) alike — so slot
    admission/release resets the bandit with the same sweep that resets the
    call/token counters: a reused slot can never inherit the previous
    request's arm rewards (DESIGN.md §9 donation/reset rules).  ``slot`` may
    be traced (used inside the jitted admit/release paths).
    """
    return {k: v.at[slot].set(jnp.zeros((), v.dtype))
            for k, v in stats.items()}


def reset_slot(cfg: ModelConfig, state: Dict, slot) -> Dict:
    """Reset batch slot ``slot`` to the freshly-initialised empty state.

    Passing the existing physical buffer length S back through init_state is
    shape-stable: cache_buffer_len(cfg, S) == S whether S came from a linear
    cache or a window-sized ring, and recurrent leaves ignore max_len.
    Paged states free the slot's pages instead of zeroing KV (a freed page
    is never read: phys_slots maps unallocated positions out of bounds).
    """
    if is_paged(state):
        state = free_slot_pages(state, slot)
        empty = init_state(cfg, 1, 1)
        groups = dict(state["groups"])
        for gid, g in state["groups"].items():
            if "k" in g:
                continue                      # pool pages already reclaimed
            groups[gid] = jax.tree_util.tree_map(
                lambda leaf, row: leaf.at[:, slot].set(row[:, 0]),
                g, empty["groups"][gid])
        return {**state, "groups": groups,
                "cur_len": state["cur_len"].at[slot].set(0)}
    S = 1
    for gid, spec, _ in group_ids(cfg):
        if spec.mixer == ATTN:
            S = buffer_len(state["groups"][gid])
            break
    return insert_slot(state, init_state(cfg, 1, S), slot)


# ----------------------------------------------------------------------------
# paged KV cache (DESIGN.md §8)
# ----------------------------------------------------------------------------
def default_page_size(cfg: ModelConfig) -> int:
    """Pages match the Pallas verify kernel's cache-streaming block: one page
    == one ``block_s`` VMEM block, so the paged kernel's grid steps map 1:1
    onto pages and the pool layout needs no per-call repacking."""
    if cfg.kernel_block_s:
        return cfg.kernel_block_s
    from ..kernels.spec_attention import DEFAULT_BLOCK_S
    return DEFAULT_BLOCK_S


def paged_supported(cfg: ModelConfig) -> bool:
    """Paged layout implements linear-cache semantics only: sliding-window
    ring caches keep the per-slot ring buffer, and at least one attention
    group must exist for paging to mean anything.  The pool holds K/V
    pairs: a latent (MLA) cache stays linear."""
    return (cfg.sliding_window is None and not cfg.mla
            and any(spec.mixer == ATTN for _, spec, _ in group_ids(cfg)))


def is_paged(state: Dict) -> bool:
    return "page_table" in state


def paged_dims(state: Dict) -> Tuple[int, int, int]:
    """(num_pages, page_size, pages_per_slot) of a paged state."""
    pool = next(g["k"] for g in state["groups"].values() if "k" in g)
    return pool.shape[1], pool.shape[2], state["page_table"].shape[1]


def init_paged_state(cfg: ModelConfig, batch: int, num_pages: int,
                     page_size: int, pages_per_slot: int) -> Dict:
    """Allocate an empty PAGED decode state: attention groups hold a shared
    (R, num_pages, page_size, KV, hd) pool, all pages start on the free
    stack, and every slot's page table is empty."""
    assert paged_supported(cfg), (
        f"{cfg.name}: paged KV requires a linear-cache attention arch "
        f"(sliding_window=None, no latent cache, >=1 attn layer)")
    hd = cfg.resolved_head_dim
    groups = {}
    for gid, spec, R in group_ids(cfg):
        if spec.mixer == ATTN:
            shape = (R, num_pages, page_size, cfg.num_kv_heads, hd)
            groups[gid] = {"k": jnp.zeros(shape, cfg.compute_dtype),
                           "v": jnp.zeros(shape, cfg.compute_dtype)}
        else:
            groups[gid] = _init_group(cfg, spec, R, batch, 0)
    return {"cur_len": jnp.zeros((batch,), jnp.int32),
            "groups": groups,
            "page_table": jnp.full((batch, pages_per_slot), -1, jnp.int32),
            "n_pages": jnp.zeros((batch,), jnp.int32),
            "free_list": jnp.arange(num_pages, dtype=jnp.int32),
            "free_top": jnp.asarray(num_pages, jnp.int32)}


def pages_for_len(length, page_size: int):
    """Pages needed to hold ``length`` positions (works traced or concrete)."""
    return (length + page_size - 1) // page_size


def phys_slots(page_table: jnp.ndarray, pos: jnp.ndarray, page_size: int,
               num_pages: int) -> jnp.ndarray:
    """Physical pool slot for each logical position. pos: (B, T) int32.

    Positions without an allocated page map to the out-of-bounds sentinel
    ``num_pages * page_size`` so scatter writes with ``mode='drop'`` discard
    them (never clamp: a clamped index would silently write into another
    slot's page).
    """
    B, PPS = page_table.shape
    pg = pos // page_size
    pid = jnp.take_along_axis(page_table, jnp.clip(pg, 0, PPS - 1), axis=1)
    ok = (pos >= 0) & (pg < PPS) & (pid >= 0)
    return jnp.where(ok, pid * page_size + pos % page_size,
                     num_pages * page_size).astype(jnp.int32)


def paged_kv_write(k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                   k_new: jnp.ndarray, v_new: jnp.ndarray,
                   phys: jnp.ndarray,
                   gate: Optional[jnp.ndarray] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter new KV into the shared pool.  pools: (N, ps, KV, hd);
    k_new/v_new: (B, T, KV, hd); phys: (B, T) physical slots (flattened pool
    indexing, out-of-bounds = skip); gate: (B, T) bool — write where True.

    Distinct slots own distinct pages, so flattened scatter indices never
    collide across batch rows; gated-off / unallocated writes fall on the
    out-of-bounds sentinel and are dropped.
    """
    N, ps = k_pool.shape[:2]
    tail = k_pool.shape[2:]
    if gate is not None:
        phys = jnp.where(gate, phys, N * ps)
    idx = phys.reshape(-1)
    kf = k_pool.reshape((N * ps,) + tail)
    vf = v_pool.reshape((N * ps,) + tail)
    kf = kf.at[idx].set(k_new.reshape((-1,) + tail).astype(kf.dtype),
                        mode="drop")
    vf = vf.at[idx].set(v_new.reshape((-1,) + tail).astype(vf.dtype),
                        mode="drop")
    return kf.reshape(k_pool.shape), vf.reshape(v_pool.shape)


def gather_pages(k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                 page_table: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Materialise the per-slot linear view (B, pages_per_slot*ps, KV, hd)
    of the pool — the XLA fallback's read path.  Unallocated pages clamp to
    physical page 0; every position they cover is >= cur_len, so the
    verify-attention mask already hides the garbage.
    """
    N = k_pool.shape[0]
    B, PPS = page_table.shape
    pid = jnp.clip(page_table, 0, N - 1)               # (B, PPS)
    ps = k_pool.shape[1]
    tail = k_pool.shape[2:]
    k_lin = k_pool[pid].reshape((B, PPS * ps) + tail)
    v_lin = v_pool[pid].reshape((B, PPS * ps) + tail)
    return k_lin, v_lin


def alloc_slot_pages(state: Dict, slot, n_new) -> Dict:
    """Pop ``n_new`` pages off the free stack into ``slot``'s page table
    (appended after its currently-allocated pages).  jit-compatible: ``slot``
    and ``n_new`` may be traced.  The caller guarantees n_new <= free_top
    (the serving engine's page-reservation admission does; see engine.py).
    """
    pt, npg = state["page_table"], state["n_pages"]
    fl, ft = state["free_list"], state["free_top"]
    PPS, N = pt.shape[1], fl.shape[0]
    cur = npg[slot]
    idx = jnp.arange(PPS)
    j = idx - cur                                   # j-th newly-added page
    take = (j >= 0) & (j < n_new)
    src = ft - 1 - j
    grant = take & (src >= 0) & (src < N)
    row = jnp.where(grant, fl[jnp.clip(src, 0, N - 1)], pt[slot])
    return {**state,
            "page_table": pt.at[slot].set(row),
            "n_pages": npg.at[slot].set(cur + grant.sum().astype(jnp.int32)),
            "free_top": jnp.maximum(ft - n_new, 0).astype(jnp.int32)}


def free_slot_pages(state: Dict, slot) -> Dict:
    """Push every page of ``slot`` back onto the free stack and clear its
    table.  Idempotent: a slot with n_pages == 0 is a no-op, so release
    followed by a defensive free at admission cannot double-free."""
    pt, npg = state["page_table"], state["n_pages"]
    fl, ft = state["free_list"], state["free_top"]
    PPS, N = pt.shape[1], fl.shape[0]
    n = npg[slot]
    idx = jnp.arange(PPS)
    dst = jnp.where(idx < n, ft + idx, N)           # OOB sentinel -> dropped
    fl = fl.at[dst].set(pt[slot], mode="drop")
    return {**state,
            "free_list": fl,
            "free_top": (ft + n).astype(jnp.int32),
            "page_table": pt.at[slot].set(jnp.full((PPS,), -1, jnp.int32)),
            "n_pages": npg.at[slot].set(0)}


def grow_pages(state: Dict, required_len: jnp.ndarray,
               active: jnp.ndarray) -> Dict:
    """Batched on-the-fly growth: ensure every ``active`` slot has pages
    covering ``required_len`` positions (spec_step calls this each iteration
    with cur_len + w + 1, so commits never outrun the table).

    Pops sum(need) pages in one vectorised step; on exhaustion a slot's
    missing pages stay -1 (its writes drop, reads mask — row-local
    corruption at worst, never another slot's pages).  The engine's
    reservation admission keeps exhaustion unreachable in serving.
    """
    pt, npg = state["page_table"], state["n_pages"]
    fl, ft = state["free_list"], state["free_top"]
    B, PPS = pt.shape
    N = fl.shape[0]
    ps = paged_dims(state)[1]
    need = jnp.maximum(pages_for_len(required_len, ps) - npg, 0)
    need = jnp.where(active, need, 0).astype(jnp.int32)
    offs = jnp.cumsum(need) - need                  # exclusive prefix (B,)
    idx = jnp.arange(PPS)[None, :]
    j = idx - npg[:, None]                          # j-th new page per row
    take = (j >= 0) & (j < need[:, None])
    src = ft - 1 - (offs[:, None] + j)
    grant = take & (src >= 0)
    new_pt = jnp.where(grant, fl[jnp.clip(src, 0, N - 1)], pt)
    return {**state,
            "page_table": new_pt,
            "n_pages": npg + grant.sum(axis=1).astype(jnp.int32),
            "free_top": jnp.maximum(ft - need.sum(), 0).astype(jnp.int32)}


def insert_slot_paged(state: Dict, row_state: Dict, slot,
                      row_len: int) -> Dict:
    """Paged counterpart of insert_slot: scatter a prefilled batch-1 LINEAR
    row state (buffer length ``row_len``, cur_len == row_len) into the pool
    pages already allocated to ``slot``; recurrent leaves copy as usual.

    The caller allocates ceil(row_len / page_size) pages first
    (alloc_slot_pages) — spec_engine.admit_slot does both inside one jit.
    """
    N, ps, _ = paged_dims(state)
    pos = jnp.arange(row_len, dtype=jnp.int32)[None, :]          # (1, row_len)
    phys = phys_slots(state["page_table"][slot][None], pos, ps, N)
    groups = dict(state["groups"])
    for gid, g in state["groups"].items():
        row_g = row_state["groups"][gid]
        if "k" in g:                                 # attention group -> pool
            # row KV is (R, 1, row_len, KV, hd); vmap over R hands
            # paged_kv_write the (1, row_len, KV, hd) batch it expects
            kc, vc = jax.vmap(
                lambda kp, vp, kr, vr: paged_kv_write(kp, vp, kr, vr, phys)
            )(g["k"], g["v"], row_g["k"], row_g["v"])
            groups[gid] = {"k": kc, "v": vc}
        else:
            groups[gid] = jax.tree_util.tree_map(
                lambda leaf, row: leaf.at[:, slot].set(row[:, 0]), g, row_g)
    return {**state, "groups": groups,
            "cur_len": state["cur_len"].at[slot].set(row_state["cur_len"][0])}


def check_page_invariants(state: Dict) -> Dict:
    """Host-side free-list/page-table audit (tests + debugging).

    Asserts: allocated pages are unique, disjoint from the free stack, and
    together with it cover exactly {0..num_pages-1}; every page table row is
    n_pages valid entries followed by -1s.  Returns summary counts.
    """
    import numpy as np
    pt = np.asarray(state["page_table"])
    npg = np.asarray(state["n_pages"])
    fl = np.asarray(state["free_list"])
    ft = int(np.asarray(state["free_top"]))
    N = fl.shape[0]
    allocated = []
    for b in range(pt.shape[0]):
        row = pt[b]
        n = int(npg[b])
        assert (row[:n] >= 0).all(), (b, row, n)
        assert (row[n:] == -1).all(), (b, row, n)
        allocated.extend(row[:n].tolist())
    free = fl[:ft].tolist()
    assert len(set(allocated)) == len(allocated), "page double-mapped"
    assert not (set(allocated) & set(free)), "allocated page on free stack"
    assert set(allocated) | set(free) == set(range(N)), (
        f"page leak: {sorted(set(range(N)) - set(allocated) - set(free))}")
    return {"num_pages": N, "free": ft, "allocated": len(allocated)}


# ----------------------------------------------------------------------------
# position bookkeeping
# ----------------------------------------------------------------------------
def key_positions(cfg: ModelConfig, S: int, cur_len: jnp.ndarray) -> jnp.ndarray:
    """Absolute position stored in each cache slot; -1 where empty.

    cur_len: (B,). Linear cache: slot s holds position s if s < cur_len.
    Ring cache (window W=S): slot s holds the largest p < cur_len with
    p % W == s, valid if p >= 0 and p >= cur_len - W.
    """
    B = cur_len.shape[0]
    slots = jnp.arange(S)[None, :]                      # (1, S)
    cl = cur_len[:, None]                               # (B, 1)
    if cfg.sliding_window is not None and cfg.sliding_window <= S:
        # ring semantics
        p = cl - 1 - jnp.mod(cl - 1 - slots, S)
        valid = (p >= 0) & (p >= cl - S) & (cl > 0)
        return jnp.where(valid, p, -1).astype(jnp.int32)
    pos = jnp.broadcast_to(slots, (B, S))
    return jnp.where(pos < cl, pos, -1).astype(jnp.int32)


def write_slots(cfg: ModelConfig, S: int, cur_len: jnp.ndarray,
                T_new: int) -> jnp.ndarray:
    """Cache slots for the next T_new positions. (B, T_new) int32."""
    pos = cur_len[:, None] + jnp.arange(T_new)[None, :]
    if cfg.sliding_window is not None and cfg.sliding_window <= S:
        return jnp.mod(pos, S).astype(jnp.int32)
    return pos.astype(jnp.int32)


def kv_write(caches: Tuple[jnp.ndarray, ...], news: Tuple[jnp.ndarray, ...],
             slots: jnp.ndarray,
             gate: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, ...]:
    """Write new positions into slots of each cache.  caches: (B, S, ...)
    (the K/V pair, or the one latent); news: (B, T, ...) alike; slots:
    (B, T).  ``gate``: (B, T) bool -- write only where True (spec commit).

    T == 1 (the production serve step) uses a one-hot masked select instead
    of a scatter: elementwise ops partition cleanly when the cache sequence
    dim is sharded over the `model` axis, whereas a scatter with dynamic
    per-row indices makes GSPMD all-gather the whole cache every layer
    (EXPERIMENTS §Perf it-6).  Multi-token writes scatter: prefill, the
    recurrent archs' replay, and speculative commits into ring caches
    (linear caches commit through ``kv_commit_in_place``).
    """
    B, T = slots.shape
    S = caches[0].shape[1]
    trail = lambda a, c: a.reshape(a.shape + (1,) * (c.ndim - a.ndim))
    if T == 1:
        hit = (jnp.arange(S)[None, :] == slots)            # (B, S)
        if gate is not None:
            hit = hit & gate
        return tuple(jnp.where(trail(hit, c), n.astype(c.dtype), c)
                     for c, n in zip(caches, news))
    b_idx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, T))
    out = []
    for c, n in zip(caches, news):
        n = n.astype(c.dtype)
        if gate is not None:
            n = jnp.where(trail(gate, c), n, c[b_idx, slots])
        out.append(c.at[b_idx, slots].set(n))
    return tuple(out)


COMMIT_TILE = 128    # positions: the TPU's lane tile


def kv_commit_in_place(caches: Tuple[jnp.ndarray, ...],
                       news: Tuple[jnp.ndarray, ...],
                       cur_len: jnp.ndarray, n_commit: jnp.ndarray
                       ) -> Tuple[jnp.ndarray, ...]:
    """Speculative commit into LINEAR caches, in the caches' own layout.
    caches: (R, B, S, ...) stacked over layers (the K/V pair (R, B, S, KV,
    hd), or the latent (R, B, S, L)); news: (R, B, W1, ...) alike, with
    W1 <= S; cur_len, n_commit: (B,).  Writes positions cur_len[b] ..
    cur_len[b] + n_commit[b] - 1 of row b, drops those >= S, and leaves
    every other position bit-identical: the gated scatter's semantics.

    A gather and a scatter with per-row indices each want a layout of their
    own, so XLA relayouts the whole cache around them (six whole-cache
    copies a step at StableLM-2-1.6B's shapes on TPU v5e).  Here each row
    selects, over a window of the cache, between the old values and its
    shifted tail, and writes the window back with ``dynamic_update_slice``,
    which updates a donated cache in place.  The rows run in a
    ``fori_loop``: unrolled, XLA may fuse the chain into one loop fusion
    that relayouts the cache again.

    The window is whole tiles of ``COMMIT_TILE`` positions (or of the
    largest power of two dividing S, if that is smaller), capped at S: a
    window at an aligned start that XLA can see is aligned (hence the
    non-negative indices) writes whole tiles of the TPU layout, with a head
    of 64 (the sequence on the lanes) as with one of 128.  On a v5e the
    commit alone took 0.81-0.96 ms at StableLM-2-1.6B's decode shapes with
    256-position windows against 3.04 ms with 11-position ones; at
    Mistral-7B's cache widths (head 128) both took 0.72-0.97 ms.  The
    start is clamped into the buffer, and the tail and gate shift by where
    the row's positions fall in the window.
    """
    R, B, S = caches[0].shape[:3]
    rest = caches[0].shape[3:]          # (KV, hd), or (L,) for the latent
    W1 = news[0].shape[2]
    align = math.gcd(S, COMMIT_TILE)
    Wn = min(S, -(-(W1 + align - 1) // align) * align)
    win = (R, 1, Wn) + rest
    zero = (0,) * len(rest)
    # the tail sits at window position `shift`: slicing it out of a
    # zero-padded copy (a gather would pull the cache into its layout)
    pad = ((0, 0), (0, 0), (Wn, Wn - W1)) + ((0, 0),) * len(rest)

    def write_row(b, caches):
        start = jnp.clip(cur_len[b], 0, S - Wn) & -align
        shift = jnp.minimum(cur_len[b] - start, Wn)
        src = jnp.arange(Wn) - shift
        g = ((src >= 0) & (src < n_commit[b]))[
            (None, None, slice(None)) + (None,) * len(rest)]
        at = (0, b, start) + zero
        out = []
        for c, new in zip(caches, news):
            t = jnp.pad(jax.lax.dynamic_slice_in_dim(new, b, 1, axis=1),
                        pad).astype(c.dtype)
            t = jax.lax.dynamic_slice(t, (0, 0, Wn - shift) + zero, win)
            old = jax.lax.dynamic_slice(c, at, win,
                                        allow_negative_indices=False)
            out.append(jax.lax.dynamic_update_slice(
                c, jnp.where(g, t, old), at, allow_negative_indices=False))
        return tuple(out)

    return jax.lax.fori_loop(0, B, write_row, tuple(caches))


def prefill_write(cfg: ModelConfig, caches: Tuple[jnp.ndarray, ...],
                  news: Tuple[jnp.ndarray, ...],
                  seq_mask: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, ...]:
    """Write a full prefill block (positions 0..T-1) into empty caches
    (the K/V pair, or the latent).

    With a ring cache only the last S positions land (earlier ones are
    overwritten by the mod-S scatter, in order, which is exactly ring
    semantics).
    """
    B, T = news[0].shape[:2]
    S = caches[0].shape[1]
    if T > S:
        # ring cache shorter than the prompt: only the last S positions land
        # (slice explicitly — a mod-S scatter with duplicate slots would have
        # undefined winner order).
        news = tuple(n[:, -S:] for n in news)
        if seq_mask is not None:
            seq_mask = seq_mask[:, -S:]
        off = jnp.full((B,), T - S, jnp.int32)
        slots = write_slots(cfg, S, off, S)
        return kv_write(caches, news, slots, gate=seq_mask)
    cur0 = jnp.zeros((B,), jnp.int32)
    slots = write_slots(cfg, S, cur0, T)
    return kv_write(caches, news, slots, gate=seq_mask)


# ----------------------------------------------------------------------------
# recurrent-state select helpers (used by gated replay commit)
# ----------------------------------------------------------------------------
def select_step_state(states_per_step, old_state, n_commit: jnp.ndarray):
    """states_per_step: pytree with leading (B, T, ...) per-step states;
    old_state: matching (B, ...). Returns state after n_commit steps
    (old state where n_commit == 0)."""
    def sel(per_step, old):
        B, T = per_step.shape[:2]
        idx = jnp.clip(n_commit - 1, 0, T - 1)
        picked = jnp.take_along_axis(
            per_step, idx.reshape((B,) + (1,) * (per_step.ndim - 1)), axis=1
        )[:, 0]
        return jnp.where(
            (n_commit > 0).reshape((B,) + (1,) * (old.ndim - 1)), picked, old)
    return jax.tree_util.tree_map(sel, states_per_step, old_state)
