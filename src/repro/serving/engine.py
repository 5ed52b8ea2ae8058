"""Serving engine: ties the scheduler to the jitted speculative generator.

One ``ServingEngine`` owns (params, cfg, tables) and serves batched requests
with either plain greedy decoding or the paper's batched speculation —
switching is one constructor argument, which is the paper's P3
('plug-and-play', no model modification).

Two serving modes share the engine:

  - ``serve_all``     — static batching: the scheduler forms whole batches
    and each runs one monolithic jitted ``generate``; a finished row idles
    its slot until the slowest row of its batch completes.
  - ``serve_continuous`` / ``step`` — continuous batching over the reusable
    jitted ``spec_step``: between verify calls, finished rows are retired
    and queued prompts are prefilled into the freed slots (admit_slot), so
    slots never idle while there is work queued.

``adaptive=True`` works in BOTH modes, with different machinery: serve_all
picks one (k, w) arm per whole batch with the host-side UCB controller
(core/controller.py AdaptiveKW); continuous batching instead bakes the arm
table into the spec_step as shape-stable masking (SpecConfig.arms,
DESIGN.md §9) — every slot picks its own arm every step INSIDE the jit, so
one compilation serves every arm and requests adapt individually while in
flight.

Continuous batching can further run over the PAGED KV layout
(``paged=True``, DESIGN.md §8): slots share a page pool with per-slot page
tables and admission is gated on pages-available (worst-case reservation,
deferral when the pool is exhausted) instead of slot count alone —
bit-identical outputs, but one long-context request no longer forces every
slot to a worst-case linear buffer.

Both modes also serve SHARDED over a real ``jax.sharding.Mesh``
(``mesh=...``, DESIGN.md §10): params/DecodeState get NamedShardings from
``distributed/sharding``, the step/admit/release jits are rebuilt with
those shardings pinned on inputs and outputs (donation + single-trace
preserved), and the activation sharder is scoped to this engine's traces —
never installed globally.  Outputs remain bit-identical to unsharded
serving; ``mesh_report()`` shows what actually sharded.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..core.ngram_tables import NGramTables, build_bigram, build_unigram
from ..core.spec_engine import (DecodeState, PagedConfig, SpecConfig,
                                admit_slot, empty_decode_state, generate,
                                make_sharded_slot_fns, release_slot,
                                spec_step)
from ..data.tokenizer import ByteTokenizer
from ..distributed import act_sharding
from ..distributed import sharding as shd
from ..kernels import dispatch
from ..models import cache as Cache
from ..models import model as M
from ..models.config import ModelConfig
from .scheduler import DEFAULT_BUCKETS, Batch, Request, Scheduler, SlotMap


class ServingEngine:
    def __init__(self, params, cfg: ModelConfig,
                 spec: Optional[SpecConfig] = None,
                 tables: Optional[NGramTables] = None,
                 max_batch: int = 8,
                 adaptive: bool = False,
                 arms: Optional[Tuple[Tuple[int, int], ...]] = None,
                 buckets: Optional[Tuple[int, ...]] = None,
                 max_new_cap: int = 64,
                 bucket_align: Optional[int] = None,
                 paged: bool = False,
                 num_pages: Optional[int] = None,
                 page_size: int = 0,
                 mesh: Optional[Mesh] = None,
                 sampling: Optional[bool] = None,
                 seed: int = 0):
        """``adaptive``: pick (k, w) online with the UCB controller
        (core/controller.py, beyond-paper) instead of a static setting —
        per whole batch under serve_all, per slot per step (shape-stable
        arm masking inside the jitted spec_step) under continuous batching.
        ``arms`` overrides the controller's arm table (DEFAULT_ARMS).
        ``buckets``/``max_new_cap`` bound the continuous-batching DecodeState
        (buffer length = largest bucket + max_new_cap + w + 2).
        ``bucket_align``: bucket-boundary multiple; None = lane-aligned when
        the Pallas backend is active, else 1 (kernels/dispatch.py).

        ``paged``: continuous batching over the paged KV layout (DESIGN.md
        §8): slots share a ``num_pages``-page pool (default: the linear
        worst case — pass less to actually cap memory) and admission is
        page-reservation-based, so one long-context request no longer
        forces every slot to a worst-case linear buffer.  ``page_size`` 0
        follows cfg.kernel_block_s (the Pallas verify kernel's cache
        block).  Bit-identical outputs to the linear layout.

        ``mesh``: serve SHARDED over a ``jax.sharding.Mesh`` (DESIGN.md
        §10): params are placed by ``distributed.sharding.params_shardings``,
        the continuous DecodeState by ``decode_state_shardings``, and the
        jitted step/admit/release are rebuilt with those shardings pinned on
        inputs AND outputs (donation + the single-trace guarantee survive —
        see spec_engine.make_sharded_slot_fns).  The engine OWNS the
        activation sharder: it is active only inside this engine's traces
        (act_sharding.activated), never installed globally, so other
        engines in the process keep their own backend eligibility.
        Outputs are bit-identical to the same engine without a mesh.
        Known seam: a mesh pins ``attn_verify`` to the sharded XLA
        flash-decode path — the Pallas verify kernel is single-device today
        (models/attention.py:_use_verify_kernel), so ``backend="pallas"``
        is ignored (with a warning) under a mesh.

        ``sampling``: compile the lossless sampled verification walk into
        the continuous spec_step (DESIGN.md §12) so temperature > 0
        requests serve speculatively.  None (default) auto-resolves when
        the continuous state is built: sampling is enabled iff a sampled
        request is queued (or spec.sampling was set).  Pass True to
        pre-commit (e.g. when sampled traffic arrives after the first
        step), False to pin the greedy-only executable — sampled requests
        are then rejected at admission instead of silently served greedy.
        ``seed`` is the engine's base rng key; request keys derive as
        fold_in(seed_key, request_id) unless the request pins its own
        ``seed`` — both replayable.  serve_all resolves sampling per batch
        (static batching recompiles per batch shape anyway).  Mesh seam:
        temperature-0 rows stay bit-exact vs unsharded serving, but
        SAMPLED rows are bit-reproducible only per mesh configuration —
        sharded matmul reductions perturb logits at the ~1e-6 level, which
        argmax absorbs but a gumbel-argmax draw near its (dense) decision
        boundary does not.  The output distribution is unchanged to the
        same ~1e-6."""
        self.params = params
        self.cfg = cfg
        self.spec = spec or SpecConfig(strategy="greedy")
        if self.spec.tree:
            self.spec.validate_tree()
            if M.has_recurrent(cfg):
                raise ValueError(
                    f"{cfg.name}: tree speculation needs an attention-only "
                    f"arch — recurrent mixers verify rows as causal "
                    f"sequences, which has no valid tree layout "
                    f"(DESIGN.md §11)")
        self.tok = ByteTokenizer()
        self.max_batch = max_batch
        self.max_new_cap = max_new_cap
        self.mesh = mesh
        # sampling=None resolves lazily in _init_continuous (queued sampled
        # request -> True); spec.sampling=True is an explicit pre-commit
        self.sampling = (True if self.spec.sampling else sampling)
        self.seed = seed
        self._seed_key = jax.random.PRNGKey(seed)
        self._explicit_buckets = buckets is not None
        if mesh is not None:
            if (dispatch.use_pallas(cfg.backend)
                    and dispatch.pallas_verify_supported(cfg)) \
                    or dispatch.use_pallas(self.spec.backend):
                warnings.warn(
                    f"{cfg.name}: mesh serving pins the Pallas kernels to "
                    f"their XLA paths (attn_verify -> sharded flash-decode, "
                    f"drafter sweep -> XLA ref) — the kernels are "
                    f"single-device today (kernel-dispatch seam, "
                    f"DESIGN.md §10)")
            self.params = jax.device_put(
                params, shd.params_shardings(mesh, params))
        # when the verify kernel is live, size every static length (bucket
        # ladder, continuous DecodeState buffer) to kernel-friendly
        # multiples so spec_attention_op never repads the cache per step
        # (moot under a mesh: the XLA path is pinned there)
        self._kernel_aligned = (
            mesh is None
            and dispatch.use_pallas(cfg.backend)
            and dispatch.pallas_verify_supported(cfg))
        if bucket_align is None:
            bucket_align = dispatch.LANE if self._kernel_aligned else 1
        self.scheduler = Scheduler(
            max_batch=max_batch,
            buckets=buckets if buckets is not None else DEFAULT_BUCKETS,
            align=bucket_align)
        self.controller = None
        self._arms: Optional[Tuple[Tuple[int, int], ...]] = None
        if adaptive:
            from ..core.controller import DEFAULT_ARMS, AdaptiveKW
            self._arms = tuple(tuple(a) for a in (arms or DEFAULT_ARMS))
            self.controller = AdaptiveKW(cfg, arms=self._arms)
        elif arms is not None:
            raise ValueError("arms= requires adaptive=True")
        self.paged = paged
        if paged and not Cache.paged_supported(cfg):
            raise ValueError(
                f"{cfg.name}: paged KV needs a linear-cache attention arch "
                f"(sliding_window=None, >=1 attn layer); run linear instead")
        self._paged_cfg = (PagedConfig(num_pages or 0, page_size)
                           if paged else None)
        if (self.spec.strategy != "greedy" or adaptive) and tables is None:
            arm_k = max((a[0] for a in self._arms or ()), default=0)
            arm_w = max((a[1] for a in self._arms or ()), default=0)
            tables = self.build_tables(k_max=max(self.spec.k, 25, arm_k),
                                       w_max=max(self.spec.w, 16, arm_w))
        self.tables = tables
        if mesh is not None and self.tables is not None:
            # draft tables are small integer lookups: replicate them
            self.tables = jax.device_put(
                self.tables, jax.tree_util.tree_map(
                    lambda _: shd.replicated(mesh), self.tables))
        self._gen_cache: Dict = {}
        # continuous-batching state, built lazily on first step();
        # _cont_spec is the spec the continuous path actually runs —
        # adaptive mode rebuilds it around the arm table in _init_continuous
        self._cont_spec: SpecConfig = self.spec
        self._cont_state: Optional[DecodeState] = None
        self._slots: Optional[SlotMap] = None

    # ------------------------------------------------------------------
    def _act(self):
        """Scoped activation sharder: the engine's mesh is active only
        inside its own traces and always uninstalled on exit — the
        mesh-state-hygiene contract (a meshed engine must not pin OTHER
        engines off the Pallas path)."""
        return (act_sharding.activated(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def build_tables(self, k_max: int = 16, w_max: int = 16,
                     batch: int = 256) -> NGramTables:
        """One-off model sweep (paper: <1 min for a 7B on one A100)."""
        # params ride as an argument: a closure would be inlined into the
        # program as a constant (the whole model, at published widths)
        fwd = jax.jit(lambda p, t: M.forward(p, self.cfg, tokens=t)[0][:, -1])
        with self._act():
            topk, chain = build_bigram(lambda t: fwd(self.params, t),
                                       self.cfg.vocab_size, k_max=k_max,
                                       w_max=w_max, batch=batch)
        uni = build_unigram(self.params["embed"]["embedding"],
                            self.params["embed"].get(
                                "lm_head",
                                self.params["embed"]["embedding"].T),
                            k_max=k_max)
        return NGramTables(unigram_topk=uni, bigram_topk=topk,
                           bigram_chain=chain)

    # ------------------------------------------------------------------
    def submit(self, prompt: str, max_new_tokens: int = 64,
               eos_id: int = -1, temperature: float = 0.0,
               top_p: float = 1.0, seed: Optional[int] = None) -> Request:
        """Queue a request.  ``temperature`` 0 decodes greedy (bit-exact
        spec path); > 0 samples losslessly through the same spec_step
        (DESIGN.md §12) with nucleus mass ``top_p``.  ``seed`` pins the
        request's rng key (None: derived from the engine seed and
        request_id — deterministic either way)."""
        if temperature < 0:
            raise ValueError(
                f"temperature must be >= 0, got {temperature} (pass 0 for "
                f"greedy decoding; negative values are always a bug)")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        req = Request(prompt=prompt, max_new_tokens=max_new_tokens,
                      eos_id=eos_id, temperature=temperature, top_p=top_p,
                      seed=seed)
        self.scheduler.submit(req)
        return req

    def _req_key(self, req: Request) -> jnp.ndarray:
        """The request's (2,) uint32 rng key: its own seed when pinned,
        else fold_in(engine seed key, request_id).  Pure function of
        (engine seed, request) — resubmitting the same request with the
        same seed replays the same sampled output, in any batch mix
        (slots are independent, so a request's trajectory never depends
        on its neighbours)."""
        if req.seed is not None:
            return jax.random.PRNGKey(req.seed)
        return jax.random.fold_in(self._seed_key, req.request_id)

    def _gen_fn(self, max_new: int, kw=None, sampled: bool = False):
        key = (max_new, kw, sampled)
        if key not in self._gen_cache:
            spec = dataclasses.replace(self.spec, max_new_tokens=max_new,
                                       sampling=sampled)
            if kw is not None:                      # adaptive controller arm
                k, w = kw
                strategy = ("greedy" if w == 0 else
                            ("mixed" if self.spec.strategy == "greedy"
                             else self.spec.strategy))
                # the w == 0 arm is plain greedy: there is no tree to build
                # (validate_tree rejects tree+greedy), so drop the flag
                spec = dataclasses.replace(spec, k=max(k, 1), w=max(w, 1),
                                           strategy=strategy,
                                           tree=spec.tree and w > 0)
            if sampled:
                # per-row controls become runtime args; greedy rows inside
                # the batch (temperature 0) stay bit-exact in the same trace
                self._gen_cache[key] = jax.jit(
                    lambda p, toks, eos, tbl, t, tp, ky: generate(
                        p, self.cfg, spec, toks, tbl, eos_id=eos,
                        temperature=t, top_p=tp, rng=ky))
            else:
                # greedy-only batches keep the pre-sampling signature (and
                # therefore the exact executable the seed engine compiled)
                self._gen_cache[key] = jax.jit(
                    lambda p, toks, eos, tbl: generate(p, self.cfg, spec,
                                                       toks, tbl,
                                                       eos_id=eos))
        return self._gen_cache[key]

    def _effective_eos(self, req: Request) -> int:
        """Per-request eos wins; fall back to the engine-wide spec.eos_id —
        the same resolution in both serving modes, so a given submission
        stops identically under serve_all and serve_continuous."""
        return req.eos_id if req.eos_id >= 0 else self.spec.eos_id

    def run_batch(self, batch: Batch) -> List[Request]:
        kw = self.controller.choose() if self.controller else None
        # static batching resolves sampling per batch: a batch with any
        # sampled request runs the sampled trace (its greedy rows stay
        # bit-exact), an all-greedy batch keeps the greedy-only executable
        sampled = (self.sampling is True
                   or any(r.temperature > 0 for r in batch.requests))
        fn = self._gen_fn(batch.max_new_tokens, kw, sampled)
        eos = jnp.asarray([self._effective_eos(r) for r in batch.requests],
                          jnp.int32)
        tokens = jnp.asarray(batch.tokens)
        sample_args = ()
        if sampled:
            sample_args = (
                jnp.asarray([r.temperature for r in batch.requests],
                            jnp.float32),
                jnp.asarray([r.top_p for r in batch.requests], jnp.float32),
                jnp.stack([self._req_key(r) for r in batch.requests]))
        if self.mesh is not None:
            tokens = jax.device_put(
                tokens, shd.batch_sharding(self.mesh, tokens.shape))
            eos = jax.device_put(eos, shd.batch_sharding(self.mesh,
                                                         eos.shape))
            sample_args = tuple(
                jax.device_put(a, shd.batch_sharding(self.mesh, a.shape))
                for a in sample_args)
        t0 = time.perf_counter()
        with self._act():
            buf, blen, stats = fn(self.params, tokens, eos, self.tables,
                                  *sample_args)
        buf.block_until_ready()
        dt = time.perf_counter() - t0
        if self.controller:
            self.controller.update(
                kw, tokens=float(np.asarray(stats["tokens"]).sum()),
                calls=float(max(np.asarray(stats["calls"]).sum(), 1)))
        P = batch.tokens.shape[1]
        buf = np.asarray(buf)
        blen = np.asarray(blen)
        for i, req in enumerate(batch.requests):
            req.output_ids = buf[i, P:blen[i]].copy()
            req.output = self.tok.decode(req.output_ids)
            req.stats = {
                "new_tokens": int(blen[i] - P),
                "model_calls": int(np.asarray(stats["calls"])[i]),
                "tokens_per_call": float(np.asarray(stats["tokens"])[i]
                                         / max(1, np.asarray(
                                             stats["calls"])[i])),
                "accept_hist": np.asarray(stats["accept_hist"])[i].tolist()
                if "accept_hist" in stats else [],
                "wall_time_s": dt,
            }
        return batch.requests

    def serve_all(self) -> List[Request]:
        done: List[Request] = []
        while True:
            batch = self.scheduler.next_batch()
            if batch is None:
                return done
            done.extend(self.run_batch(batch))

    # ------------------------------------------------------------------
    # continuous batching (slot-level admission / retirement)
    # ------------------------------------------------------------------
    def _init_continuous(self) -> None:
        # adaptive continuous: bake the controller's arm table into the
        # spec as shape-stable masking (DESIGN.md §9) — the step's shapes
        # are the arm-table maxima, every slot picks its arm per step
        # inside the ONE jitted spec_step, and the per-slot bandit state
        # rides in DecodeState.stats (zeroed on slot admission/release)
        spec = self.spec
        if self.controller is not None:
            k_max = max(a[0] for a in self._arms)
            w_max = max(a[1] for a in self._arms)
            strategy = ("mixed" if spec.strategy == "greedy"
                        else spec.strategy)
            # spec.tree rides through the replace: tree arms read the same
            # (k, w) table as (width, depth) under path masking (§11)
            spec = dataclasses.replace(
                spec, k=k_max, w=max(w_max, 1), strategy=strategy,
                arms=self._arms).validate_arms().validate_tree()
        # resolve the static sampling flag ONCE, at state build time:
        # sampling=None enables the sampled walk iff a sampled request is
        # already queued.  The flag is compile-time (DESIGN.md §12), so a
        # sampled request reaching a greedy-only compiled step is rejected
        # at admission (_admit_queued) rather than recompiling the step or
        # silently serving it greedy.
        if self.sampling is None:
            self.sampling = any(r.temperature > 0
                                for r in self.scheduler.queued_requests())
        if self.sampling and not spec.sampling:
            spec = dataclasses.replace(spec, sampling=True)
        self._cont_spec = spec
        # size the DecodeState to the queued workload, not the 512-token
        # worst case; the scheduler itself is left untouched (a later
        # serve_all on this engine sees the full bucket ladder).  Prompts
        # longer than the sized capacity are REJECTED at admission with a
        # per-request error stat (truncating them would silently corrupt
        # the output).  Pass buckets= explicitly to reserve more up front.
        # Paged mode reserves the FULL bucket ladder instead: per-slot
        # token buffers are cheap (int32), and KV capacity is governed by
        # the page pool, not the per-slot buffer length.
        prompt_cap = self.scheduler.buckets[-1]
        if not self.paged and not self._explicit_buckets:
            prompt_cap = self.scheduler.max_queued_bucket() or prompt_cap
        self._cont_prompt_cap = prompt_cap
        buf_size = prompt_cap + self.max_new_cap + self._cont_spec.w + 2
        if self._kernel_aligned:
            buf_size = dispatch.align_cache_len(buf_size,
                                                self.cfg.kernel_block_s)
        self._cont_state = empty_decode_state(self.cfg, self._cont_spec,
                                              self.max_batch, buf_size,
                                              paged=self._paged_cfg)
        # mesh serving: place the state, then rebuild the three slot jits
        # with every in/out sharding pinned (donation + single-trace under
        # NamedSharding — spec_engine.make_sharded_slot_fns).  mesh=None
        # keeps the module-level jits, shared across engines.
        self._step_jit = self._admit_jit = self._release_jit = None
        self._step_hlo_text: Optional[str] = None
        if self.mesh is not None:
            self._state_shardings = shd.decode_state_shardings(
                self.mesh, self._cont_state)
            self._cont_state = jax.device_put(self._cont_state,
                                              self._state_shardings)
            params_sh = jax.tree_util.tree_map(lambda x: x.sharding,
                                               self.params)
            tables_sh = (jax.tree_util.tree_map(
                lambda _: shd.replicated(self.mesh), self.tables)
                if self.tables is not None else None)
            self._step_jit, self._admit_jit, self._release_jit = \
                make_sharded_slot_fns(self.cfg, self._cont_spec,
                                      params_sh=params_sh,
                                      state_sh=self._state_shardings,
                                      tables_sh=tables_sh,
                                      scalar_sh=shd.replicated(self.mesh))
        self._slots = SlotMap(self.max_batch)
        # host-side aggregate of retired requests' arm pulls (adaptive)
        self._arm_pulls_total = (np.zeros(len(self._arms), np.int64)
                                 if self._arms else None)
        # page accounting (paged mode): admission reserves each request's
        # worst-case page count up front so the in-step on-the-fly growth
        # (spec_engine) can never exhaust the pool mid-flight; physical
        # allocation stays lazy.  All host-side — no device sync to admit.
        if self.paged:
            self._page_size = self._paged_cfg.resolve_page_size(self.cfg)
            pps = self._cont_state.buf_size // self._page_size
            self._pool_pages = (self._paged_cfg.num_pages
                                or self.max_batch * pps)
            self._page_reserved: Dict[int, int] = {}
            self._pool_peak = 0
            self._deferrals = 0
        self._rejected = 0

    def in_flight(self) -> int:
        return len(self._slots) if self._slots is not None else 0

    # the three continuous-path device calls, routed through either the
    # module-level jits (mesh=None) or this engine's sharding-pinned jits
    def _run_step(self, state: DecodeState) -> DecodeState:
        with self._act():
            if self._step_jit is not None:
                return self._step_jit(self.params, state, self.tables)
            return spec_step(self.params, self.cfg, self._cont_spec, state,
                             self.tables)

    def _run_admit(self, state: DecodeState, slot: int, toks,
                   mnt: int, eos: int, req: Request) -> DecodeState:
        temp = jnp.float32(req.temperature)
        topp = jnp.float32(req.top_p)
        key = self._req_key(req)
        with self._act():
            if self._admit_jit is not None:
                return self._admit_jit(self.params, state, jnp.int32(slot),
                                       jnp.asarray(toks), jnp.int32(mnt),
                                       jnp.int32(eos), temp, topp, key)
            return admit_slot(self.params, self.cfg, state, jnp.int32(slot),
                              jnp.asarray(toks), jnp.int32(mnt),
                              jnp.int32(eos), temp, topp, key)

    def _run_release(self, state: DecodeState, slot: int) -> DecodeState:
        with self._act():
            if self._release_jit is not None:
                return self._release_jit(state, jnp.int32(slot))
            return release_slot(state, jnp.int32(slot))

    def _retire_finished(self) -> List[Request]:
        state = self._cont_state
        # The scheduler's one unavoidable per-step sync: slot reuse is a
        # host decision, so the done flags must come back every step.  The
        # ROADMAP's async-serving item replaces this with a lagged readback;
        # until then it is THE baseline entry in BENCH_syncmap.json.
        # repro-lint: allow(host-sync): scheduling branches on done flags host-side; async serving (ROADMAP) is the structural fix
        done = np.asarray(state.done)
        if not done[[s for s, _ in self._slots.occupied()]].any():
            return []
        if self.paged:
            # pool peak: occupancy only falls at release, so sampling here
            # (before this round's frees) sees every high-water mark
            # repro-lint: allow(host-sync): runs only on retire rounds, behind the done.any() gate — off the steady-state step path
            in_use = self._pool_pages - int(np.asarray(state.model["free_top"]))
            self._pool_peak = max(self._pool_peak, in_use)
        # one device->host transfer per array, not per retired slot, and
        # only on rounds that actually retire (behind the done.any() gate)
        blen = np.asarray(state.buf_len)        # repro-lint: allow(host-sync): batched retire-round readback
        plen = np.asarray(state.prompt_len)     # repro-lint: allow(host-sync): batched retire-round readback
        buf = np.asarray(state.buf)             # repro-lint: allow(host-sync): batched retire-round readback
        calls_np = np.asarray(state.stats["calls"])    # repro-lint: allow(host-sync): batched retire-round readback
        tokens_np = np.asarray(state.stats["tokens"])  # repro-lint: allow(host-sync): batched retire-round readback
        accept_hist_np = np.asarray(state.stats["accept_hist"])  # repro-lint: allow(host-sync): batched retire-round readback
        arm_pulls_np = (np.asarray(state.stats["arm_pulls"])  # repro-lint: allow(host-sync): batched retire-round readback
                        if self._arms else None)
        retired: List[Request] = []
        for slot, req in self._slots.occupied():
            if not done[slot]:
                continue
            calls = int(calls_np[slot])
            tokens = int(tokens_np[slot])
            req.output_ids = buf[slot, plen[slot]:blen[slot]].copy()
            req.output = self.tok.decode(req.output_ids)
            req.stats = {
                "new_tokens": int(blen[slot] - plen[slot]),
                "model_calls": calls,
                "tokens_per_call": float(tokens / max(1, calls)),
                # this request's acceptance-length histogram: entry n =
                # verify calls that committed exactly n tokens (0..w+1) —
                # the paper's Fig. 4 ablation, per request (read BEFORE
                # release zeroes the slot's stats rows)
                # repro-lint: allow(host-sync): numpy-side tolist on the already-transferred accept_hist_np, not a device sync
                "accept_hist": accept_hist_np[slot].tolist(),
                # per-request admit->retire latency; deliberately NOT named
                # wall_time_s (which in serve_all is the shared whole-batch
                # generate time — a different quantity)
                "latency_s": time.perf_counter() - req.stats["admit_t"],
            }
            if arm_pulls_np is not None:
                # the slot's bandit history, read BEFORE release zeroes it
                req.stats["arm_pulls"] = {
                    self._arms[a]: int(arm_pulls_np[slot, a])
                    for a in range(len(self._arms))
                    if arm_pulls_np[slot, a]}
                self._arm_pulls_total += arm_pulls_np[slot].astype(np.int64)
            state = self._run_release(state, slot)
            self._slots.release(slot)
            if self.paged:
                self._page_reserved.pop(slot, None)
            retired.append(req)
        self._cont_state = state
        return retired

    def _slot_pages(self, prompt_len: int, mnt: int) -> int:
        """Worst-case pool pages one request can ever occupy: the cache
        holds at most prompt_len + mnt + w positions (cur_len peaks at
        prompt_len + mnt - 1 and spec growth covers cur_len + w + 1; under
        adaptive arms w is the arm-table maximum — in-step growth is sized
        for the worst arm whichever arm the slot picks)."""
        return int(Cache.pages_for_len(prompt_len + mnt + self._cont_spec.w,
                                       self._page_size))

    def _reject(self, req: Request, reason: str) -> Request:
        """Per-request admission failure: the request completes with an
        ``error`` stat instead of silently-corrupted output."""
        req.output = None
        req.output_ids = np.zeros((0,), np.int32)
        req.stats = {"error": reason, "new_tokens": 0}
        self._rejected += 1
        warnings.warn(f"request {req.request_id} rejected: {reason}")
        return req

    def _admit_queued(self) -> List[Request]:
        """Admit queued prompts into free slots; returns requests REJECTED
        this round (prompt beyond capacity).  Paged mode additionally gates
        admission on pages-available (reservation), deferring the queue
        head — in order — until retirements free enough pages."""
        state = self._cont_state
        rejected: List[Request] = []
        free = self._slots.free_slots()
        i = 0
        while i < len(free):
            slot = free[i]
            head = self.scheduler.peek_next()
            if head is None:
                break
            req, toks, raw_len = head
            if toks.shape[0] > self._cont_prompt_cap:
                # the request's BUCKET does not fit the self-sized state:
                # admitting would truncate below its bucket and silently
                # corrupt the output.  (Prompts beyond the largest bucket
                # are left-clamped by the scheduler in both serving modes —
                # that is bucketing policy, not a continuous-mode hazard.)
                self.scheduler.pop_next()      # rejection frees no slot:
                rejected.append(self._reject(  # retry this slot with the
                    req,                       # next queued request
                    f"prompt is {raw_len} tokens ({toks.shape[0]}-bucket) "
                    f"but the continuous DecodeState was sized for "
                    f"{self._cont_prompt_cap} (pass buckets= / use paged "
                    f"mode to admit longer prompts)"))
                continue
            if req.temperature > 0 and not self._cont_spec.sampling:
                # the step was compiled greedy-only (sampling=False was
                # pinned, or the state was built before sampled traffic
                # arrived) — serving this request greedy would silently
                # break its output distribution, so reject loudly
                self.scheduler.pop_next()
                rejected.append(self._reject(
                    req,
                    f"temperature={req.temperature} needs a "
                    f"sampling-enabled step, but the continuous spec_step "
                    f"was compiled greedy-only (construct the engine with "
                    f"sampling=True, or queue sampled requests before the "
                    f"first step)"))
                continue
            mnt = min(req.max_new_tokens, self.max_new_cap)
            if self.paged:
                pages = self._slot_pages(toks.shape[0], mnt)
                if pages > self._pool_pages:
                    # can NEVER fit — deferring would deadlock an idle pool
                    self.scheduler.pop_next()
                    rejected.append(self._reject(
                        req,
                        f"request needs {pages} pages but the pool has "
                        f"only {self._pool_pages} (raise --num-pages)"))
                    continue
                avail = self._pool_pages - sum(self._page_reserved.values())
                if pages > avail:
                    # pool exhausted: defer the head (FIFO order is kept)
                    # until retirements return pages to the free stack
                    self._deferrals += 1
                    break
                self._page_reserved[slot] = pages
            self.scheduler.pop_next()
            if mnt < req.max_new_tokens:
                # static serve_all honours any budget (it sizes buffers per
                # batch); the continuous DecodeState is sized once by
                # max_new_cap, so an oversized request is clamped — loudly.
                warnings.warn(
                    f"request {req.request_id}: max_new_tokens "
                    f"{req.max_new_tokens} exceeds the engine's continuous "
                    f"max_new_cap={self.max_new_cap}; clamping (raise "
                    f"max_new_cap to honour larger budgets)")
            state = self._run_admit(state, slot, toks, mnt,
                                    self._effective_eos(req), req)
            self._slots.assign(slot, req)
            req.stats = {"admit_t": time.perf_counter()}
            i += 1
        self._cont_state = state
        return rejected

    def step(self) -> List[Request]:
        """One continuous-batching iteration: retire finished rows, admit
        queued prompts into the freed slots, then run one jitted spec_step
        over every active slot.  Returns the requests completed this step —
        retired normally, or rejected at admission (``stats["error"]``)."""
        if self._cont_state is None:
            self._init_continuous()
        retired = self._retire_finished()
        retired.extend(self._admit_queued())
        # occupancy is tracked host-side: after retirement every occupied
        # slot is runnable (an admission that hit eos on its first token is
        # retired next step; the one no-op spec_step it gets is rarer than
        # paying a device->host sync on every step to detect it).
        if len(self._slots):
            self._cont_state = self._run_step(self._cont_state)
            # peak-pool telemetry is NOT sampled here: reading free_top
            # back every step was a per-step device->host sync on the
            # decode critical path (repro-lint host-sync found it).  Pool
            # occupancy only ever falls at release, so sampling it at
            # retirement entry (before the frees) and in pool_stats()
            # observes every high-water mark syncs-free on the hot path.
        return retired

    def reset_pool_counters(self) -> None:
        """Zero the cumulative pool/bandit counters (peak pages, deferral
        rounds, rejections, retired arm pulls) without touching the pool or
        the in-flight bandit state — benchmarks call this after their
        warmup phase so the measured window starts clean."""
        if self._cont_state is None:
            return
        if self.paged:
            self._pool_peak = 0
            self._deferrals = 0
        if self._arm_pulls_total is not None:
            self._arm_pulls_total[:] = 0
        self._rejected = 0

    def pool_stats(self) -> Dict:
        """Paged-pool occupancy/admission counters (paged mode only).

        ``deferrals`` counts deferral ROUNDS — one per step() in which the
        queue head could not reserve pages — not distinct requests."""
        if not self.paged or self._cont_state is None:
            return {}
        free = int(np.asarray(self._cont_state.model["free_top"]))
        # fold current occupancy into the peak: step() no longer samples
        # it per step (that was a hot-path sync), so a caller reading
        # stats mid-flight still observes at least the occupancy it sees
        self._pool_peak = max(self._pool_peak, self._pool_pages - free)
        return {"num_pages": self._pool_pages,
                "page_size": self._page_size,
                "free_pages": free,
                "reserved_pages": sum(self._page_reserved.values()),
                "peak_pages": self._pool_peak,
                "deferrals": self._deferrals,
                "rejected": self._rejected}

    def mesh_report(self) -> Dict:
        """Resolved sharding of THIS engine's serving state ({} without a
        mesh): mesh shape, per-leaf DecodeState partition specs, param
        sharding coverage, and every (logical axis, dim) that silently
        degraded to replication — so a bench/operator can assert the mesh
        actually sharded the state instead of serving replicated at full
        per-device memory (distributed.sharding.ShardingFallbackWarning).
        """
        if self.mesh is None:
            return {}
        p_flat = jax.tree_util.tree_flatten_with_path(self.params)[0]
        p_sharded = sum(
            1 for _, leaf in p_flat
            if any(ax is not None for ax in leaf.sharding.spec))
        # re-resolve THIS engine's specs under a scoped recorder: the
        # report must list only fallbacks attributable to this engine's
        # params/state, not the process-global warning history (another
        # engine's mesh may have produced entirely different ones)
        with shd.recording_fallbacks() as fallbacks:
            shd.params_shardings(self.mesh, self.params)
            if self._cont_state is not None:
                shd.decode_state_shardings(self.mesh, self._cont_state)
        rep = {
            "mesh": {str(k): int(v) for k, v in self.mesh.shape.items()},
            "backend": "xla",   # a mesh pins attn_verify off the Pallas
                                # kernel (DESIGN.md §10 seam)
            "params_leaves": len(p_flat),
            "params_sharded": p_sharded,
            "replication_fallbacks": [list(kv) for kv in sorted(fallbacks)],
        }
        if self._cont_state is not None:
            specs = shd.spec_summary(self._state_shardings)
            rep["state_specs"] = specs
            rep["state_sharded"] = sum(
                1 for s in specs.values()
                if any(f"'{ax}'" in s for ax in self.mesh.shape))
        return rep

    def step_hlo(self) -> str:
        """Optimized HLO of the continuous spec_step for the CURRENT state
        shapes — the mesh bench extracts per-step collective bytes from it
        (launch/dryrun.collective_bytes).  Does not execute (donation is
        only consumed at execution), but the AOT lower().compile() is a
        FULL extra compile separate from the jit execution cache — so the
        text is memoized per engine (state shapes are fixed once the
        continuous path is initialised)."""
        if self._cont_state is None:
            self._init_continuous()
        if self._step_hlo_text is None:
            with self._act():
                if self._step_jit is not None:
                    lowered = self._step_jit.lower(
                        self.params, self._cont_state, self.tables)
                else:
                    lowered = spec_step.lower(
                        self.params, self.cfg, self._cont_spec,
                        self._cont_state, self.tables)
            self._step_hlo_text = lowered.compile().as_text()
        return self._step_hlo_text

    def adaptive_stats(self) -> Dict:
        """Continuous-mode bandit telemetry: the arm table, cumulative
        pulls per arm over all RETIRED requests, and each in-flight slot's
        current pull counts (adaptive continuous mode only)."""
        if self._arms is None or self._cont_state is None:
            return {}
        in_flight = np.asarray(self._cont_state.stats["arm_pulls"])
        return {"arms": [list(a) for a in self._arms],
                "pulls_retired": self._arm_pulls_total.tolist(),
                "pulls_in_flight": in_flight.sum(axis=0).tolist()}

    def serve_continuous(self) -> List[Request]:
        """Drain the queue with continuous batching; blocks until idle."""
        done: List[Request] = []
        while True:
            done.extend(self.step())
            if self.scheduler.pending() == 0 and self.in_flight() == 0:
                return done
