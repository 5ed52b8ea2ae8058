"""The speculative generation engine: draft -> verify -> accept -> commit.

The unit of work is ONE jitted iteration, ``spec_step``: it drafts, runs the
batched verification call, and commits the winning tokens for every *active*
slot of a persistent ``DecodeState`` pytree.  Everything is fixed-shape (a
requirement for TPU serving): the token buffer is static-length, per-sequence
progress is tracked by ``buf_len``, and inactive/finished rows simply commit
0 tokens.

``generate`` (the one-shot path) is a thin ``lax.while_loop`` over the same
step body, so batch-at-once generation and step-driven serving are literally
the same computation — the bit-exact-vs-greedy guarantee (property-tested)
transfers to both.  Step-driven serving additionally gets ``admit_slot`` /
``release_slot`` so a continuous-batching engine can retire finished rows and
prefill a queued prompt into the freed slot *between* verify calls
(serving/engine.py builds on exactly this).

Invariants:
  - output is bit-identical to greedy decoding (property-tested);
  - per row: model.cur_len == #cached positions == buf_len - 1 (the last
    committed token's KV is materialised by the *next* call, exactly as in
    the paper's Appendix D cache).

Commit paths:
  - attention-only archs: write the winner's verified KV tail (no extra
    model call) — ``commit_kv_tails``;
  - archs with recurrent mixers (Jamba, xLSTM): gated replay of the winner
    row (one (B, w+1) forward; ~1/k of the verify cost) — see DESIGN.md §4.

Statistics mirror the paper's ablations (Fig. 4): acceptance-length
histogram, winning-rank histogram, context/bigram allocation and
per-strategy accepted tokens.  Stats are per-slot; ``admit_slot`` zeroes a
slot's row so a continuous engine reads them per-request at retirement.

In-flight adaptive (k, w) (DESIGN.md §9): ``SpecConfig.arms`` turns (k, w)
into compile-time maxima and every step each slot picks one arm by
per-slot UCB (core/controller.py) and is MASKED down to it — bit-identical
to a dedicated static step of that arm, with zero recompiles across arm
switches.  The bandit's (B, A) state rides in ``DecodeState.stats`` and is
zeroed with the rest of the slot's stats on admission/release.

Lossless speculative sampling (DESIGN.md §12): ``SpecConfig.sampling``
compiles the sampled verification walk into the same step — per-slot
``temperature``/``top_p``/``rng_key`` DecodeState leaves steer each row at
runtime, 0-temperature rows stay bit-exact greedy, and temperature > 0 rows
emit exactly the plain-sampling output distribution (the point-mass
rejection rule realised by trajectory coupling — core/verify.py).  One
compiled step therefore serves mixed greedy/sampled continuous batches.

Tree mode (DESIGN.md §11): ``SpecConfig.tree`` swaps the k independent
linear rows for ONE token tree per slot (core/tree.py): the first
``min(tree_branch, w)`` depths branch over the drafter's top-k candidates,
deeper levels chain on argmax, and the whole tree is verified in a single
(B, 1, N+1) call whose attention uses the topology's static ancestor mask.
Acceptance runs over the tree's root-to-leaf PATHS (each bit-identical to a
linear row of the same tokens), the winning path's KV tail is gathered and
committed through the unchanged ``commit_kv_tails``.  Under ``arms`` the
(k, w) pairs read as (tree_width, depth) arms, masked by path eligibility
(all branch indices < width_b) — the same zero-recompile contract as §9.
Attention-only archs only: recurrent mixers verify rows as causal
sequences, which has no valid tree layout (validate_tree raises).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import dispatch
from ..models import cache as C
from ..models import model as M
from ..models.config import ModelConfig
from . import tree as T
from .controller import (arm_slowdowns, choose_arms, init_arm_stats,
                         tree_arm_slowdowns, update_arm_stats)
from .drafters import (bigram_draft, context_ngram_draft, mixed_draft,
                       multi_depth_draft, unigram_draft)
from .ngram_tables import NGramTables
from .verify import accept, per_row_keys, sample_predictions, sample_token


@dataclasses.dataclass(frozen=True)
class PagedConfig:
    """Sizing of a paged DecodeState (models/cache.py, DESIGN.md §8).

    ``num_pages`` is the page-pool size shared by every slot; 0 sizes it to
    the per-slot worst case (num_slots * pages_per_slot — the linear
    footprint, useful for parity testing).  ``page_size`` is positions per
    page; 0 follows the verify kernel's cache block (cfg.kernel_block_s or
    the kernel default), which keeps the paged Pallas grid page-aligned.
    """
    num_pages: int = 0
    page_size: int = 0

    def resolve_page_size(self, cfg: ModelConfig) -> int:
        return self.page_size or C.default_page_size(cfg)


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    k: int = 10                 # number of batched drafts
    w: int = 10                 # speculation depth
    q: int = 1                  # context-match query length
    strategy: str = "mixed"     # mixed | bigram | unigram | context | greedy
    max_new_tokens: int = 64
    eos_id: int = -1            # -1: never stop on eos
    # drafter backend (kernels/dispatch.py): "xla" | "pallas" | "auto" —
    # routes the context match/hash sweep to the Pallas kernel or XLA.
    # (The verify call's backend is ModelConfig.backend: it lives in the
    # model, not the drafter.)
    backend: str = "auto"
    # In-flight adaptive (k, w) (DESIGN.md §9): a static table of
    # (k_arm, w_arm) arms, each within [1, k] x [0, w].  When set, (k, w)
    # become the COMPILE-TIME maxima of the step's shapes; every step each
    # slot picks one arm by per-slot UCB (core/controller.py) and is masked
    # down to it — bit-identical to a dedicated (k_arm, w_arm) step, with
    # zero recompiles across arm switches.  (k_arm, w_arm) == (1, 0) is
    # plain greedy decoding.  The per-slot bandit state lives in
    # DecodeState.stats and is zeroed on slot admission/release.
    arms: Optional[Tuple[Tuple[int, int], ...]] = None
    adapt_explore: float = 0.3  # UCB exploration coefficient
    adapt_ema: float = 0.9      # per-arm tokens-per-call EMA decay
    adapt_ell: int = 512        # context length of the roofline prior
    # Tree mode (DESIGN.md §11): verify one top-k draft TREE per slot
    # instead of k independent rows.  (k, w) read as (tree width, depth);
    # ``tree_branch`` is how many of the first depths fan out over the
    # drafter's top-k candidates (deeper levels argmax-chain).  Under
    # ``arms`` the arm table reads as (width, depth) pairs in the same
    # [1, k] x [0, w] box.  Attention-only archs, tables required.
    tree: bool = False
    tree_branch: int = 2
    # Lossless speculative sampling (DESIGN.md §12): compile the sampled
    # verification walk (core/verify.py::sample_predictions) into the step.
    # Per-slot temperature/top_p/rng_key leaves in DecodeState then steer
    # each row at RUNTIME — temperature == 0 rows stay bit-exact greedy, so
    # one compiled step serves mixed greedy/sampled batches.  Off by
    # default: the gumbel draw + top-p sort are real per-step work that
    # pure-greedy serving should not pay, and the flag is static so the
    # greedy-only executable is byte-identical to the pre-sampling engine.
    sampling: bool = False

    def validate_tree(self) -> "SpecConfig":
        """Raise unless the tree knobs are a buildable topology."""
        if not self.tree:
            return self
        if self.strategy == "greedy":
            raise ValueError("tree mode needs a drafting strategy "
                             "(strategy='greedy' verifies nothing)")
        if self.w < 1:
            raise ValueError(f"tree mode needs w >= 1, got w={self.w}")
        if self.tree_branch < 1:
            raise ValueError(
                f"tree_branch must be >= 1, got {self.tree_branch}")
        return self

    def validate_arms(self) -> "SpecConfig":
        """Raise unless the arm table fits the compile-time (k, w) box."""
        if self.arms is None:
            return self
        if self.strategy == "greedy":
            raise ValueError(
                "arms require a drafting strategy (the greedy arm (1, 0) "
                "is expressed inside the masked step, not via "
                "strategy='greedy')")
        if not self.arms:
            raise ValueError("arms must be a non-empty tuple")
        for a in self.arms:
            ka, wa = a
            if not (1 <= ka <= self.k and 0 <= wa <= self.w):
                raise ValueError(
                    f"arm {a} outside the compile-time box "
                    f"[1, {self.k}] x [0, {self.w}]")
        return self


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["buf", "buf_len", "prompt_len", "budget", "eos_id", "done",
                 "active", "model", "stats", "rng_key", "temperature",
                 "top_p"],
    meta_fields=[])
@dataclasses.dataclass
class DecodeState:
    """Persistent decoding state: one row ("slot") per in-flight sequence.

    A slot is *occupied* while ``active``; ``done`` marks rows that must not
    commit further tokens (finished, or empty slot).  ``eos_id == -1`` means
    the row never stops on eos.  All leaves are fixed-shape so the state can
    thread through ``lax.while_loop`` and a jit-compiled ``spec_step``
    without recompilation as requests come and go.

    Sampling leaves (DESIGN.md §12): ``rng_key`` is the slot's CARRY key —
    a sampling-enabled step splits it once (vmapped over slots, inside the
    jit, donated with the rest of the state), uses one half for this step's
    gumbel draws and stores the other, so replaying the same admitted key
    replays the same output.  ``temperature``/``top_p`` are per-slot runtime
    data: 0-temperature rows take the bit-exact argmax path inside the SAME
    compiled step.  All three reset on admit_slot/release_slot exactly like
    the bandit stats.
    """
    buf: jnp.ndarray         # (B, L) int32 token buffer (prompt + output)
    buf_len: jnp.ndarray     # (B,) int32 committed length per row
    prompt_len: jnp.ndarray  # (B,) int32
    budget: jnp.ndarray      # (B,) int32 per-row max_new_tokens
    eos_id: jnp.ndarray      # (B,) int32 per-row eos (-1: never)
    done: jnp.ndarray        # (B,) bool
    active: jnp.ndarray      # (B,) bool — slot currently occupied
    model: Dict[str, Any]    # models/cache.py state {"cur_len", "groups"}
    stats: Dict[str, jnp.ndarray]
    rng_key: jnp.ndarray = None      # (B, 2) uint32 per-slot carry key
    temperature: jnp.ndarray = None  # (B,) f32, <= 0 -> greedy row
    top_p: jnp.ndarray = None        # (B,) f32 nucleus mass, 1 -> off

    @property
    def num_slots(self) -> int:
        return self.buf.shape[0]

    @property
    def buf_size(self) -> int:
        return self.buf.shape[1]


def _draft(spec: SpecConfig, tables: NGramTables, buf, buf_len, last):
    if spec.strategy == "mixed":
        return mixed_draft(tables, buf, buf_len, last, spec.q, spec.k,
                           spec.w, backend=spec.backend)
    if spec.strategy == "bigram":
        d, v = bigram_draft(tables, last, spec.k, spec.w)
    elif spec.strategy == "unigram":
        d, v = unigram_draft(tables, buf.shape[0], spec.k, spec.w)
    elif spec.strategy == "context":
        d, v = context_ngram_draft(buf, buf_len, spec.q, spec.k, spec.w,
                                   backend=spec.backend)
        d = jnp.where(v[..., None], d, 0)
    else:
        raise ValueError(spec.strategy)
    n_ctx = (v.sum(axis=1) if spec.strategy == "context"
             else jnp.zeros((buf.shape[0],), jnp.int32))
    return d, v, n_ctx.astype(jnp.int32)


def _init_stats(spec: SpecConfig, B: int) -> Dict[str, jnp.ndarray]:
    # tree mode ranks over root-to-leaf PATHS, not drafter rows
    ranks = (T.num_paths(spec.k, spec.w, spec.tree_branch) if spec.tree
             else max(spec.k, 1))
    st = {
        "calls": jnp.zeros((B,), jnp.int32),
        "tokens": jnp.zeros((B,), jnp.int32),
        # accept_hist bins n_commit per verify call into 0..w+1 (w+2 bins).
        # INVARIANT: bin 0 is structurally zero and hist.sum() == calls —
        # every step path commits >= 1 token per call (accept() returns
        # n_commit = n_win + 1, the greedy body books its single token into
        # bin 1, and eos/budget clamps only shrink n_commit of an ACTIVE
        # row to >= 1).  Bin 0 is kept so the index IS the n_commit value
        # (aggregators like benchmarks' _add_hist sum bins positionally),
        # and as a canary: a nonzero bin 0 means a zero-commit call
        # slipped through.  Rejection sampling makes n_commit == 1 (bonus
        # only) the common case — bin 1, never bin 0.
        "accept_hist": jnp.zeros((B, spec.w + 2), jnp.int32),
        "rank_hist": jnp.zeros((B, max(ranks, 1)), jnp.int32),
        "alloc_ctx": jnp.zeros((B, spec.k + 1), jnp.int32),     # n_ctx per call
        "accepted_ctx": jnp.zeros((B,), jnp.int32),             # drafted tokens
        "accepted_bigram": jnp.zeros((B,), jnp.int32),          # accepted per src
    }
    if spec.arms is not None:
        # per-slot bandit state rides in the stats dict: donated with the
        # DecodeState and zeroed by the same slot-reset sweep as the
        # call/token counters (admission AND release)
        st.update(init_arm_stats(B, len(spec.arms)))
    return st


def _draft_adaptive(spec: SpecConfig, tables: Optional[NGramTables],
                    buf, buf_len, last, arm):
    """Arm-masked drafting: (k_max, w_max) candidates for every slot.

    One genuine draft per distinct positive arm depth (the context sweep's
    hash is a function of w — see drafters.multi_depth_draft), selected per
    slot by its chosen arm.  An all-greedy arm table drafts nothing.
    """
    B = buf.shape[0]
    sw = dispatch.unique_sweep_widths(spec.arms)
    if not sw:                              # every arm is (k, 0): greedy
        return (jnp.zeros((B, spec.k, spec.w), jnp.int32),
                jnp.zeros((B, spec.k), bool),
                jnp.zeros((B,), jnp.int32))
    widx = jnp.asarray([sw.index(w) if w > 0 else 0
                        for _, w in spec.arms], jnp.int32)[arm]
    draft_fn = lambda w: _draft(
        dataclasses.replace(spec, w=w, arms=None), tables, buf, buf_len,
        last)
    return multi_depth_draft(draft_fn, sw, spec.w, widx)


# ---------------------------------------------------------------------------
# state construction / slot admission
# ---------------------------------------------------------------------------
def _sampling_leaves(B: int) -> Dict[str, jnp.ndarray]:
    """Greedy-default per-slot sampling leaves (the admit/release reset)."""
    return dict(rng_key=jnp.zeros((B, 2), jnp.uint32),
                temperature=jnp.zeros((B,), jnp.float32),
                top_p=jnp.ones((B,), jnp.float32))


def empty_decode_state(cfg: ModelConfig, spec: SpecConfig, num_slots: int,
                       buf_size: int,
                       paged: Optional[PagedConfig] = None) -> DecodeState:
    """All-slots-free state for a continuous-batching engine.

    With ``paged``, the model cache is a shared page pool + per-slot page
    tables instead of per-slot linear buffers; ``buf_size`` (the token
    buffer / logical KV capacity per slot) is rounded up to whole pages.
    """
    spec.validate_arms()
    spec.validate_tree()
    B = num_slots
    if paged is not None:
        ps = paged.resolve_page_size(cfg)
        buf_size = -(-buf_size // ps) * ps
        pps = buf_size // ps
        model = C.init_paged_state(cfg, B, paged.num_pages or B * pps,
                                   ps, pps)
    else:
        model = M.init_state(cfg, B, buf_size)
    return DecodeState(
        buf=jnp.zeros((B, buf_size), jnp.int32),
        buf_len=jnp.zeros((B,), jnp.int32),
        prompt_len=jnp.zeros((B,), jnp.int32),
        budget=jnp.zeros((B,), jnp.int32),
        eos_id=jnp.full((B,), -1, jnp.int32),
        done=jnp.ones((B,), bool),
        active=jnp.zeros((B,), bool),
        model=model,
        stats=_init_stats(spec, B),
        **_sampling_leaves(B))


def init_decode_state(params, cfg: ModelConfig, spec: SpecConfig,
                      prompt: jnp.ndarray,
                      max_new_tokens: Optional[jnp.ndarray] = None,
                      eos_id: Optional[jnp.ndarray] = None,
                      buf_size: Optional[int] = None,
                      paged: Optional[PagedConfig] = None,
                      temperature: Optional[jnp.ndarray] = None,
                      top_p: Optional[jnp.ndarray] = None,
                      rng: Optional[jnp.ndarray] = None) -> DecodeState:
    """Prefill every row of ``prompt`` (B, P) into a fresh DecodeState.

    The static buffer is sized by spec.max_new_tokens (grown to cover
    concrete per-row ``max_new_tokens``; traced budgets must not exceed
    spec.max_new_tokens) unless ``buf_size`` is given.

    ``paged`` switches the KV layout to the shared page pool: each row gets
    ceil(P / page_size) pages up front and grows on the fly inside
    spec_step.  The default pool covers the worst case, so one-shot
    ``generate`` can never exhaust it — pool pressure is a serving concern
    (ServingEngine's page-reservation admission).

    Sampling (requires ``spec.sampling`` — a silent greedy fallback would be
    a correctness trap): ``temperature``/``top_p`` broadcast to per-row f32
    controls, ``rng`` is either one base key (2,) — expanded per row via
    fold_in(row) — or explicit per-row keys (B, 2).  The prompt's first free
    token is already a sampling event: it draws from the row key's first
    split, and the carry half seeds the step loop.
    """
    spec.validate_arms()
    spec.validate_tree()
    if not spec.sampling and (temperature is not None or top_p is not None
                              or rng is not None):
        raise ValueError(
            "temperature/top_p/rng need SpecConfig(sampling=True): the "
            "sampled verification walk is compiled statically "
            "(DESIGN.md §12); without it these knobs would silently "
            "degrade to greedy")
    B, P = prompt.shape
    budget = (jnp.full((B,), spec.max_new_tokens, jnp.int32)
              if max_new_tokens is None
              else jnp.broadcast_to(jnp.asarray(max_new_tokens, jnp.int32),
                                    (B,)))
    cap = spec.max_new_tokens
    if max_new_tokens is not None:
        try:
            cap = max(cap, int(jnp.max(budget)))
        except (jax.errors.ConcretizationTypeError,
                jax.errors.TracerArrayConversionError):
            pass  # traced budgets: caller promises <= spec.max_new_tokens
    L = buf_size or P + cap + spec.w + 2
    if (buf_size is None and dispatch.use_pallas(cfg.backend)
            and dispatch.pallas_verify_supported(cfg)):
        # size the cache so the verify kernel streams whole blocks and
        # never repads per call (padded slots are masked by cur_len, so
        # the extra length cannot change outputs)
        L = dispatch.align_cache_len(L, cfg.kernel_block_s)
    eos = (jnp.full((B,), spec.eos_id, jnp.int32) if eos_id is None
           else jnp.broadcast_to(jnp.asarray(eos_id, jnp.int32), (B,)))
    if paged is not None:
        ps = paged.resolve_page_size(cfg)
        L = -(-L // ps) * ps
        pps = L // ps
        model = C.init_paged_state(cfg, B, paged.num_pages or B * pps,
                                   ps, pps)
        model = C.grow_pages(model, jnp.full((B,), P, jnp.int32),
                             jnp.ones((B,), bool))
    else:
        model = M.init_state(cfg, B, L)
    buf = jnp.zeros((B, L), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, prompt.astype(jnp.int32), (0, 0))

    logits_p, model = M.prefill(params, cfg, model, tokens=prompt)
    leaves = _sampling_leaves(B)
    if spec.sampling:
        if temperature is not None:
            leaves["temperature"] = jnp.broadcast_to(
                jnp.asarray(temperature, jnp.float32), (B,))
        if top_p is not None:
            leaves["top_p"] = jnp.broadcast_to(
                jnp.asarray(top_p, jnp.float32), (B,))
        if rng is not None:
            keys = per_row_keys(rng, B)
        else:
            keys = leaves["rng_key"]
        nk = jax.vmap(jax.random.split)(keys)               # (B, 2, 2)
        first = sample_token(logits_p[:, -1], nk[:, 0],
                             leaves["temperature"], leaves["top_p"])
        leaves["rng_key"] = nk[:, 1]
    else:
        first = jnp.argmax(logits_p[:, -1], axis=-1).astype(jnp.int32)
    buf = buf.at[:, P].set(first)
    stats = _init_stats(spec, B)
    stats["tokens"] = stats["tokens"] + 1
    return DecodeState(
        buf=buf,
        buf_len=jnp.full((B,), P + 1, jnp.int32),
        prompt_len=jnp.full((B,), P, jnp.int32),
        budget=budget,
        eos_id=eos,
        done=(first == eos) & (eos >= 0),
        active=jnp.ones((B,), bool),
        model=model,
        stats=stats,
        **leaves)


def _admit_body(params, cfg: ModelConfig, state: DecodeState,
                slot: jnp.ndarray, prompt: jnp.ndarray,
                max_new_tokens: jnp.ndarray, eos_id: jnp.ndarray,
                temperature: jnp.ndarray = 0.0, top_p: jnp.ndarray = 1.0,
                rng_key: Optional[jnp.ndarray] = None) -> DecodeState:
    """Un-jitted body of ``admit_slot`` (re-jitted with explicit
    NamedShardings by ``make_sharded_slot_fns`` for mesh serving)."""
    P = prompt.shape[0]
    L = state.buf_size
    paged = C.is_paged(state.model)
    row_model = M.init_state(cfg, 1, P if paged else L)
    logits, row_model = M.prefill(params, cfg, row_model,
                                  tokens=prompt[None].astype(jnp.int32),
                                  last_only=True)
    temp = jnp.asarray(temperature, jnp.float32)
    topp = jnp.asarray(top_p, jnp.float32)
    key = (jnp.zeros((2,), jnp.uint32) if rng_key is None
           else jnp.asarray(rng_key, jnp.uint32))
    # the request's first free token is its first sampling event: draw it
    # from the admitted key's first split, carry the second into the slot
    k_use, k_carry = jax.random.split(key)
    first = sample_token(logits[:1, -1], k_use[None], temp[None],
                         topp[None])[0]
    row = jnp.zeros((L,), jnp.int32)
    row = jax.lax.dynamic_update_slice(row, prompt.astype(jnp.int32), (0,))
    row = row.at[P].set(first)
    # zero every per-slot stats row — including the adaptive bandit's
    # per-arm pulls/rewards, so a reused slot starts exploring afresh
    stats = C.zero_slot_stats(state.stats, slot)
    stats["tokens"] = stats["tokens"].at[slot].set(1)
    if paged:
        ps = C.paged_dims(state.model)[1]
        model = C.free_slot_pages(state.model, slot)
        model = C.alloc_slot_pages(model, slot, C.pages_for_len(P, ps))
        model = C.insert_slot_paged(model, row_model, slot, P)
    else:
        model = C.insert_slot(state.model, row_model, slot)
    return DecodeState(
        buf=state.buf.at[slot].set(row),
        buf_len=state.buf_len.at[slot].set(P + 1),
        prompt_len=state.prompt_len.at[slot].set(P),
        budget=state.budget.at[slot].set(max_new_tokens),
        eos_id=state.eos_id.at[slot].set(eos_id),
        done=state.done.at[slot].set((first == eos_id) & (eos_id >= 0)),
        active=state.active.at[slot].set(True),
        model=model,
        stats=stats,
        rng_key=state.rng_key.at[slot].set(k_carry),
        temperature=state.temperature.at[slot].set(temp),
        top_p=state.top_p.at[slot].set(topp))


@functools.partial(jax.jit, static_argnums=(1,), donate_argnums=(2,))
def admit_slot(params, cfg: ModelConfig, state: DecodeState,
               slot: jnp.ndarray, prompt: jnp.ndarray,
               max_new_tokens: jnp.ndarray, eos_id: jnp.ndarray,
               temperature: jnp.ndarray = 0.0, top_p: jnp.ndarray = 1.0,
               rng_key: Optional[jnp.ndarray] = None) -> DecodeState:
    """Prefill ``prompt`` (P,) into slot ``slot`` of a shared DecodeState.

    The freed slot's model cache is fully overwritten (cache.insert_slot), so
    nothing can leak from the slot's previous occupant.  Compiles once per
    prompt length P — the scheduler's length bucketing keeps that bounded.
    ``slot``/``max_new_tokens``/``eos_id`` (and the per-request sampling
    controls ``temperature``/``top_p``/``rng_key``) are traced, so
    heterogeneous requests reuse the same executable.  The defaults admit a
    greedy request; the prompt's first free token is sampled from the
    admitted key (temperature 0 reduces to the argmax bit-exactly).

    Paged states prefill the row into a P-sized scratch linear cache, then
    allocate ceil(P / page_size) pool pages for the slot and scatter the
    prefix KV through its fresh page table (spec_step grows further pages on
    the fly).  A defensive free first makes admission safe even if release
    was skipped — free_slot_pages is idempotent.
    """
    return _admit_body(params, cfg, state, slot, prompt, max_new_tokens,
                       eos_id, temperature, top_p, rng_key)


def _release_body(state: DecodeState, slot: jnp.ndarray) -> DecodeState:
    """Un-jitted body of ``release_slot`` (see ``make_sharded_slot_fns``)."""
    model = state.model
    if C.is_paged(model):
        model = C.free_slot_pages(model, slot)
    return dataclasses.replace(
        state,
        model=model,
        stats=C.zero_slot_stats(state.stats, slot),
        active=state.active.at[slot].set(False),
        done=state.done.at[slot].set(True),
        rng_key=state.rng_key.at[slot].set(jnp.zeros((2,), jnp.uint32)),
        temperature=state.temperature.at[slot].set(0.0),
        top_p=state.top_p.at[slot].set(1.0))


@functools.partial(jax.jit, donate_argnums=(0,))
def release_slot(state: DecodeState, slot: jnp.ndarray) -> DecodeState:
    """Mark a retired row's slot as free.  Linear caches are overwritten on
    the next admit (see cache.reset_slot for eager scrubbing); paged caches
    return the slot's pages to the free stack NOW — reclaiming pool capacity
    at retirement is the whole point of the paged layout.  The slot's stats
    rows (including the adaptive bandit's per-arm state) are zeroed eagerly:
    callers must read a retiring slot's stats BEFORE releasing it, and a
    freed slot must not keep steering arm choices it can no longer use."""
    return _release_body(state, slot)


def make_sharded_slot_fns(cfg: ModelConfig, spec: SpecConfig, *,
                          params_sh, state_sh, tables_sh, scalar_sh):
    """jitted (spec_step, admit_slot, release_slot) with every input AND
    output pinned to explicit NamedShardings — the mesh-serving versions of
    the module-level jits (DESIGN.md §10).

    Pinning out_shardings == in_shardings per state leaf is what keeps the
    two serving guarantees alive under a mesh: (a) buffer DONATION stays
    legal (XLA only aliases a donated buffer into an output with the same
    sharding), so the sharded KV cache still updates in place; (b) the
    state's placement is a fixed point of every function here, so the
    serving loop's step N+1 sees bit-identical arg shardings to step N and
    the step compiles exactly ONCE per shape — the same single-trace
    contract the unsharded path has.  Scalars (slot ids, prompts, budgets)
    are replicated.
    """
    step = jax.jit(
        lambda params, state, tables: _step_body(params, cfg, spec, tables,
                                                 state),
        in_shardings=(params_sh, state_sh, tables_sh),
        out_shardings=state_sh, donate_argnums=(1,))
    admit = jax.jit(
        lambda params, state, slot, prompt, mnt, eos, temp, topp, key:
        _admit_body(params, cfg, state, slot, prompt, mnt, eos, temp, topp,
                    key),
        in_shardings=(params_sh, state_sh, scalar_sh, scalar_sh, scalar_sh,
                      scalar_sh, scalar_sh, scalar_sh, scalar_sh),
        out_shardings=state_sh, donate_argnums=(1,))
    release = jax.jit(
        lambda state, slot: _release_body(state, slot),
        in_shardings=(state_sh, scalar_sh),
        out_shardings=state_sh, donate_argnums=(0,))
    return step, admit, release


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------
def _spec_body(params, cfg: ModelConfig, spec: SpecConfig,
               tables: Optional[NGramTables], s: DecodeState) -> DecodeState:
    B, L = s.buf.shape
    adaptive = spec.arms is not None
    if adaptive:
        spec.validate_arms()
    topo = None
    if spec.tree:
        spec.validate_tree()
        if M.has_recurrent(cfg):
            raise ValueError(
                "tree speculation needs an attention-only arch: recurrent "
                "mixers verify rows as causal sequences, which has no "
                "valid tree layout (DESIGN.md §11)")
        if tables is None:
            raise ValueError("tree speculation needs NGramTables "
                             "(off-spine branches come from bigram_topk)")
        topo = T.topology(spec.k, spec.w, spec.tree_branch)
    if C.is_paged(s.model):
        # on-the-fly page growth: this step commits at most w+1 tokens per
        # row (positions cur_len .. cur_len+w), so cover cur_len + w + 1
        # before the verify/commit touches the pool (w is the compile-time
        # maximum under adaptive arms: growth is sized for the worst arm)
        act = s.active & (~s.done) & (s.buf_len - s.prompt_len < s.budget)
        s = dataclasses.replace(
            s, model=C.grow_pages(s.model,
                                  s.model["cur_len"] + spec.w + 1, act))
    buf_c, len_c, done_c, state_c = s.buf, s.buf_len, s.done, s.model
    st = s.stats
    if spec.sampling:
        # one split per slot per step, inside the jit: half drives this
        # step's per-level gumbel draws, half is carried (donated in place)
        nk = jax.vmap(jax.random.split)(s.rng_key)          # (B, 2, 2)
        use_keys, carry_keys = nk[:, 0], nk[:, 1]
    else:
        use_keys, carry_keys = None, s.rng_key
    last = jnp.take_along_axis(buf_c, (len_c - 1)[:, None], axis=1)[:, 0]
    if adaptive:
        # per-slot, per-step arm selection INSIDE the jit: UCB over the
        # slot's own (B, A) stats, then mask the fixed (k_max, w_max)
        # shapes down to the chosen arm — no recompile can ever occur
        slow = (tree_arm_slowdowns(cfg, spec.arms, spec.tree_branch,
                                   spec.adapt_ell) if spec.tree
                else arm_slowdowns(cfg, spec.arms, spec.adapt_ell))
        arm = choose_arms(st, slow, spec.adapt_explore)         # (B,)
        k_eff = jnp.asarray([a[0] for a in spec.arms], jnp.int32)[arm]
        w_eff = jnp.asarray([a[1] for a in spec.arms], jnp.int32)[arm]
        drafts, valid, n_ctx = _draft_adaptive(spec, tables, buf_c, len_c,
                                               last, arm)
    else:
        arm = k_eff = w_eff = None
        drafts, valid, n_ctx = _draft(spec, tables, buf_c, len_c, last)
    if spec.tree:
        # ONE (B, 1, N+1) verify call scores the whole token tree; the
        # topology's ancestor mask + per-level positions make every
        # root-to-leaf path bit-identical to a linear row of its tokens
        nodes = T.fill_tree(topo, drafts, tables,
                            buf=buf_c, buf_len=len_c)           # (B, N)
        rows = jnp.concatenate([last[:, None], nodes],
                               axis=1)[:, None, :]              # (B,1,N+1)
        logits, tails = M.verify(params, cfg, state_c, rows,
                                 pos_off=topo.pos_off,
                                 tail_mask=topo.anc_mask)
        if spec.sampling:
            # noise keyed per tree LEVEL (pos_off), so same-level nodes
            # share it: alive nodes share prefixes -> logits -> samples,
            # and the slot's sampled trajectory is well defined across the
            # whole tree (duplicate-token siblings included)
            preds_n = sample_predictions(logits, use_keys, s.temperature,
                                         s.top_p, levels=topo.pos_off)[:, 0]
        else:
            preds_n = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        # path views: (B, P, w) draft tokens / (B, P, w+1) predictions
        drafts_pv = jnp.take(nodes, topo.path_nodes, axis=1)
        greedy_pv = jnp.take(preds_n, topo.path_inputs, axis=1)
        row_mask = None
        if adaptive:
            # a (width_b, depth_b) arm keeps exactly the paths whose branch
            # indices all fall below width_b (NOT a prefix of the path
            # list — eligibility is scattered through lex order)
            row_mask = (jnp.asarray(topo.path_max_branch, jnp.int32)[None]
                        < k_eff[:, None])
        acc = accept(drafts_pv, greedy_pv, w_eff=w_eff, row_mask=row_mask)
    else:
        rows = jnp.concatenate(
            [jnp.broadcast_to(last[:, None, None], (B, spec.k, 1)), drafts],
            axis=-1)                                            # (B,k,w+1)
        logits, tails = M.verify(params, cfg, state_c, rows)
        if spec.sampling:
            # noise keyed per position level and SHARED across the k rows:
            # rows alive at level j have identical prefixes -> identical
            # logits -> identical samples, so acceptance walks one sampled
            # trajectory and the bonus is its first divergent (= residual)
            # token — the point-mass rejection rule, lossless for any k
            greedy = sample_predictions(logits, use_keys, s.temperature,
                                        s.top_p)
        else:
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        acc = accept(drafts, greedy, k_eff=k_eff, w_eff=w_eff)
    active = s.active & (~done_c) & (len_c - s.prompt_len < s.budget)
    budget = jnp.maximum(s.prompt_len + s.budget - len_c, 0)
    n_commit = jnp.where(active, jnp.minimum(acc.n_commit, budget), 0)
    # eos truncation: commit only up to (and including) the first eos
    iseos = (acc.tokens == s.eos_id[:, None]) & (s.eos_id >= 0)[:, None]
    first_eos = jnp.argmax(iseos, axis=1)
    has_eos = iseos.any(axis=1) & (first_eos < n_commit)
    n_commit = jnp.where(has_eos, first_eos + 1, n_commit)
    done_c = done_c | (has_eos & active)
    # commit the model state
    if spec.tree:
        # gather the winning PATH's verify inputs out of the (N+1)-wide
        # tree tails -> a (w+1)-wide linear tail, then the stock commit
        # (winner row 0 of 1) writes it — linear AND paged paths unchanged
        sel = jnp.asarray(topo.path_inputs, jnp.int32)[acc.winner]  # (B,w+1)
        idx = sel[None, :, None, :, None, None]
        tails = {g: {kk: jnp.take_along_axis(tt, idx, axis=3)
                     for kk, tt in d.items()} for g, d in tails.items()}
        state_n = M.commit_kv_tails(cfg, state_c, tails,
                                    jnp.zeros((B,), jnp.int32), n_commit)
    elif not M.has_recurrent(cfg):
        state_n = M.commit_kv_tails(cfg, state_c, tails, acc.winner,
                                    n_commit)
    else:
        row_tok = jnp.take_along_axis(
            rows, acc.winner[:, None, None], axis=1)[:, 0]      # (B,w+1)
        _, state_n = M.decode(params, cfg, state_c, row_tok,
                              n_commit=n_commit)
    # write accepted tokens into the buffer
    pos = jnp.arange(spec.w + 1)[None, :]
    slots = jnp.clip(len_c[:, None] + pos, 0, L - 1)
    gate = pos < n_commit[:, None]
    b_idx = jnp.broadcast_to(jnp.arange(B)[:, None], slots.shape)
    old = buf_c[b_idx, slots]
    buf_n = buf_c.at[b_idx, slots].set(
        jnp.where(gate, acc.tokens, old))
    len_n = len_c + n_commit
    done_n = done_c | (len_n - s.prompt_len >= s.budget)
    # ---- stats ----
    st = dict(st)
    st["calls"] = st["calls"] + active.astype(jnp.int32)
    st["tokens"] = st["tokens"] + n_commit
    st["accept_hist"] = st["accept_hist"].at[
        jnp.arange(B), jnp.clip(n_commit, 0, spec.w + 1)].add(
            active.astype(jnp.int32))
    n_win = jnp.take_along_axis(acc.n_acc, acc.winner[:, None], 1)[:, 0]
    st["rank_hist"] = st["rank_hist"].at[jnp.arange(B), acc.winner].add(
        (active & (n_win > 0)).astype(jnp.int32))
    st["alloc_ctx"] = st["alloc_ctx"].at[
        jnp.arange(B), jnp.clip(n_ctx, 0, spec.k)].add(
            active.astype(jnp.int32))
    # winning path's origin: the drafter row its first branch tracks (tree)
    # or the winning row itself (linear)
    from_ctx = (jnp.asarray(topo.path_first, jnp.int32)[acc.winner] < n_ctx
                if spec.tree else acc.winner < n_ctx)
    acc_drafted = jnp.maximum(n_commit - 1, 0)
    st["accepted_ctx"] = st["accepted_ctx"] + jnp.where(
        active & from_ctx, acc_drafted, 0)
    st["accepted_bigram"] = st["accepted_bigram"] + jnp.where(
        active & ~from_ctx, acc_drafted, 0)
    if adaptive:
        # reward the pulled arm with the tokens its call committed (bonus
        # included — the same tokens-per-call quantity AdaptiveKW tracks)
        st = update_arm_stats(st, arm, n_commit, active, spec.adapt_ema)
    return dataclasses.replace(s, buf=buf_n, buf_len=len_n, done=done_n,
                               model=state_n, stats=st,
                               rng_key=carry_keys)


def _greedy_body(params, cfg: ModelConfig, spec: SpecConfig,
                 tables: Optional[NGramTables], s: DecodeState) -> DecodeState:
    B, L = s.buf.shape
    if C.is_paged(s.model):
        act = s.active & (~s.done) & (s.buf_len - s.prompt_len < s.budget)
        s = dataclasses.replace(
            s, model=C.grow_pages(s.model, s.model["cur_len"] + 1, act))
    buf_c, len_c, done_c, state_c = s.buf, s.buf_len, s.done, s.model
    last = jnp.take_along_axis(buf_c, (len_c - 1)[:, None], axis=1)
    logits, state_n = M.decode(params, cfg, state_c, last)
    active = s.active & (~done_c) & (len_c - s.prompt_len < s.budget)
    # decode advances cur_len by 1 for every row; freeze inactive rows so
    # the cur_len == buf_len - 1 invariant holds for done/free slots too
    # (their discarded cache/state writes are row-local and invisible:
    # key_positions only exposes p < cur_len, and admission overwrites).
    state_n = {**state_n,
               "cur_len": state_c["cur_len"] + active.astype(jnp.int32)}
    if spec.sampling:
        nk = jax.vmap(jax.random.split)(s.rng_key)          # (B, 2, 2)
        nxt = sample_token(logits[:, -1], nk[:, 0], s.temperature, s.top_p)
        carry_keys = nk[:, 1]
    else:
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        carry_keys = s.rng_key
    slots = jnp.clip(len_c, 0, L - 1)
    buf_n = buf_c.at[jnp.arange(B), slots].set(
        jnp.where(active, nxt, buf_c[jnp.arange(B), slots]))
    len_n = len_c + active.astype(jnp.int32)
    done_n = done_c | (len_n - s.prompt_len >= s.budget)
    done_n = done_n | ((nxt == s.eos_id) & (s.eos_id >= 0))
    st = dict(s.stats)
    st["calls"] = st["calls"] + active.astype(jnp.int32)
    st["tokens"] = st["tokens"] + active.astype(jnp.int32)
    # a greedy-body call commits exactly one token, so it lands in bin 1 of
    # the shared n_commit histogram — keeping "hist.sum() == calls" true for
    # every strategy, and bin 0 structurally zero engine-wide (see
    # _init_stats: every step path commits >= 1 token per call)
    st["accept_hist"] = st["accept_hist"].at[:, 1].add(
        active.astype(jnp.int32))
    return dataclasses.replace(s, buf=buf_n, buf_len=len_n, done=done_n,
                               model=state_n, stats=st,
                               rng_key=carry_keys)


def _step_body(params, cfg: ModelConfig, spec: SpecConfig,
               tables: Optional[NGramTables], state: DecodeState
               ) -> DecodeState:
    body = _greedy_body if spec.strategy == "greedy" else _spec_body
    return body(params, cfg, spec, tables, state)


@functools.partial(jax.jit, static_argnums=(1, 2), donate_argnums=(3,))
def spec_step(params, cfg: ModelConfig, spec: SpecConfig, state: DecodeState,
              tables: Optional[NGramTables] = None) -> DecodeState:
    """One jitted draft→verify→commit iteration over every active slot.

    Reusable across calls: shapes are those of ``state``, so a serving loop
    compiles this exactly once per (cfg, spec, state-shape) and then admits /
    retires requests between invocations.  Rows that are inactive or done
    commit nothing and their stats are untouched.

    The incoming ``state`` is DONATED (as in admit_slot/release_slot): the
    serving loop always rebinds, and donation lets XLA update the KV cache
    in place instead of copying every leaf per verify call.  Callers that
    need the previous state must copy it first.
    """
    return _step_body(params, cfg, spec, tables, state)


# ---------------------------------------------------------------------------
# one-shot generation (a while_loop over the same step body)
# ---------------------------------------------------------------------------
def generate(params, cfg: ModelConfig, spec: SpecConfig,
             prompt: jnp.ndarray, tables: Optional[NGramTables] = None,
             eos_id: Optional[jnp.ndarray] = None,
             paged: Optional[PagedConfig] = None,
             temperature: Optional[jnp.ndarray] = None,
             top_p: Optional[jnp.ndarray] = None,
             rng: Optional[jnp.ndarray] = None
             ) -> Tuple[jnp.ndarray, jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Generate up to max_new_tokens for every row of ``prompt`` (B, P).

    ``eos_id``: optional per-row override of spec.eos_id (traced, so
    heterogeneous batches share one compilation).  ``paged`` runs the same
    loop over the paged KV layout (bit-identical outputs — the parity
    tests' contract).  ``temperature``/``top_p``/``rng`` (scalar or
    per-row; requires ``spec.sampling``) run the lossless sampled
    verification walk instead of greedy — see ``init_decode_state``.
    Returns (buf (B, L), buf_len (B,), stats).  jit-compatible end to end.
    """
    state = init_decode_state(params, cfg, spec, prompt, eos_id=eos_id,
                              paged=paged, temperature=temperature,
                              top_p=top_p, rng=rng)

    def cond(s: DecodeState):
        return (~s.done).any() & ((s.buf_len - s.prompt_len) < s.budget).any()

    def body(s: DecodeState):
        return _step_body(params, cfg, spec, tables, s)

    state = jax.lax.while_loop(cond, body, state)
    return state.buf, state.buf_len, state.stats


def greedy_reference(params, cfg: ModelConfig, prompt: jnp.ndarray,
                     max_new_tokens: int) -> jnp.ndarray:
    """Plain greedy decoding via full forward() only — the test oracle.

    Uses a FIXED-shape buffer (causality guarantees the garbage tail can't
    influence the position being read), so the whole loop compiles once.
    """
    B, P = prompt.shape
    L = P + max_new_tokens
    buf = jnp.zeros((B, L), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, prompt.astype(jnp.int32), (0, 0))
    for i in range(max_new_tokens):
        buf = _greedy_reference_step(params, cfg, buf, jnp.asarray(P + i))
    return buf


# params are an ARGUMENT of the reference steps, never a closure: jit
# inlines closed-over arrays into the program as constants, which at
# published widths would put the whole model into the HLO
@functools.partial(jax.jit, static_argnums=(1,))
def _greedy_reference_step(params, cfg: ModelConfig, buf, cur):
    B = buf.shape[0]
    logits, _ = M.forward(params, cfg, tokens=buf)
    nxt = jnp.take_along_axis(
        jnp.argmax(logits, axis=-1).astype(jnp.int32),
        (cur - 1)[None].repeat(B, 0)[:, None], axis=1)[:, 0]
    return buf.at[:, cur].set(nxt)


def sampling_reference(params, cfg: ModelConfig, prompt: jnp.ndarray,
                       max_new_tokens: int, rng: jnp.ndarray,
                       temperature, top_p=1.0) -> jnp.ndarray:
    """Plain temperature/top-p decoding via full forward() only — the
    sampled sibling of ``greedy_reference`` and the distributional-parity
    oracle.

    Per-row key chains mirror the engine's exactly (``per_row_keys`` then
    one split per sampled token, first token included), and every draw goes
    through the SAME primitive the spec path uses
    (core/verify.py::sample_token on shape_logits-shaped distributions) —
    so spec-vs-plain parity isolates the acceptance walk, not sampler
    differences.  No eos/budget logic: fixed max_new_tokens per row.
    """
    B, P = prompt.shape
    L = P + max_new_tokens
    buf = jnp.zeros((B, L), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, prompt.astype(jnp.int32), (0, 0))
    temp = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (B,))
    topp = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (B,))
    keys = per_row_keys(jnp.asarray(rng, jnp.uint32), B)
    for i in range(max_new_tokens):
        buf, keys = _sampling_reference_step(params, cfg, buf, keys,
                                             jnp.asarray(P + i), temp, topp)
    return buf


@functools.partial(jax.jit, static_argnums=(1,))
def _sampling_reference_step(params, cfg: ModelConfig, buf, keys, cur, temp,
                             topp):
    logits, _ = M.forward(params, cfg, tokens=buf)
    row_logits = logits[:, cur - 1]                           # (B, V)
    nk = jax.vmap(jax.random.split)(keys)
    nxt = sample_token(row_logits, nk[:, 0], temp, topp)
    return buf.at[:, cur].set(nxt), nk[:, 1]
