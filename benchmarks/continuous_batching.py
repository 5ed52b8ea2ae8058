"""Continuous vs static batching under Poisson arrivals.

Requests arrive as a Poisson process (seeded, so runs are comparable) with
heterogeneous max_new_tokens; both serving modes run the same mixed-strategy
speculation.  Static batching admits work only when the scheduler forms a
batch and every row then rides until the slowest row finishes; continuous
batching admits/retires between jitted spec_steps.  Reported per mode:

  - throughput_tok_s : committed new tokens / busy wall time
  - p50/p99 latency  : submit -> completion per request (seconds)

Writes ``BENCH_continuous.json`` (repo root) so future PRs can track serving
throughput, and prints one CSV row per mode.

``--paged`` runs the PAGED long-context arrival mix instead (DESIGN.md §8):
mostly short prompts with periodic long-context ones, served by (a) linear
continuous batching, where every slot pays the long bucket's worst-case
buffer, and (b) paged continuous batching over a pool deliberately SMALLER
than that worst case (admission defers when exhausted).  Reported per mode:
throughput + latency as above, plus resident KV in token-positions per
layer (linear: max_batch * buf_size, always; paged: peak pages * page
size), deferral count and the leak check.  Writes ``BENCH_paged.json``.

``--adaptive`` benchmarks the in-flight adaptive (k, w) controller
(DESIGN.md §9) against every static arm of its table: the same Poisson
workload is served continuously once per static arm and once with
per-slot UCB arm masking, and the report gives each arm's throughput and
tokens/call plus the adaptive run's REGRET vs the best static arm (how
much throughput exploration cost) and its pull distribution.  Writes
``BENCH_adaptive.json``.

``--mesh DxM`` serves the SAME Poisson workload sharded over a debug mesh
(DESIGN.md §10) and against the 1-device engine: asserts bit-identical
outputs, reports tokens/s for both, and extracts the sharded spec_step's
per-step collective bytes from its optimized HLO (the dry-run's
``collective_bytes`` scraper — live serving now has the same collective
profile visibility as the 512-device dry-run).  Writes
``BENCH_sharded.json``.  On CPU the sharded run is a parity/plumbing
signal, not a speedup: all placeholder devices share one physical CPU.

Run:  PYTHONPATH=src python -m benchmarks.continuous_batching [--n 24]
      PYTHONPATH=src python -m benchmarks.continuous_batching --paged
      PYTHONPATH=src python -m benchmarks.continuous_batching --adaptive
      PYTHONPATH=src python -m benchmarks.continuous_batching --mesh 2x2
"""
from __future__ import annotations

if __name__ == "__main__":
    # --mesh needs placeholder devices BEFORE any jax import locks the
    # count (appended to XLA_FLAGS; a caller-provided count is respected)
    from repro.launch import hostdev
    hostdev.ensure_for_mesh_argv()

import argparse
import json
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core.spec_engine import SpecConfig
from repro.data.datasets import make_prompts
from repro.launch import hostdev
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import ServingEngine

from .common import get_tables, get_trained, ensure_dirs

BUCKETS = (64,)
MAX_NEW_CHOICES = (16, 32, 48)


def make_workload(n: int, rate_hz: float, seed: int = 0
                  ) -> List[Tuple[str, int, float]]:
    """(prompt, max_new_tokens, arrival_time_s) sorted by arrival."""
    rng = np.random.default_rng(seed)
    texts = [p for p, _ in make_prompts("code", (n + 1) // 2, seed=1)]
    texts += [p for p, _ in make_prompts("math", n - len(texts), seed=2)]
    gaps = rng.exponential(1.0 / rate_hz, n)
    arrivals = np.cumsum(gaps)
    return [(texts[i], int(rng.choice(MAX_NEW_CHOICES)), float(arrivals[i]))
            for i in range(n)]


def _summary(lat: Dict[int, float], toks: int, busy_s: float,
             calls: int = 0, hist: Optional[List[int]] = None) -> Dict:
    ls = np.asarray(sorted(lat.values()))
    out = {"requests": len(ls),
           "new_tokens": toks,
           "busy_wall_s": round(busy_s, 3),
           "throughput_tok_s": round(toks / max(busy_s, 1e-9), 2),
           "p50_latency_s": round(float(np.percentile(ls, 50)), 4),
           "p99_latency_s": round(float(np.percentile(ls, 99)), 4)}
    if calls:
        out["tokens_per_call"] = round(toks / calls, 3)
    if hist is not None:
        out["accept_hist"] = hist
    return out


def _add_hist(agg: List[int], h: List[int]) -> List[int]:
    """Element-wise sum of acceptance-length histograms (index = tokens
    committed by one verify call, 0..w+1); ragged lengths zero-extend so
    mixed (k, w) runs — adaptive arms, warm restarts — still aggregate."""
    if len(h) > len(agg):
        agg = agg + [0] * (len(h) - len(agg))
    return [a + (h[i] if i < len(h) else 0) for i, a in enumerate(agg)]


def run_static(eng, workload) -> Dict:
    """Replay arrivals against static batching: whenever requests are queued,
    form the next batch and run it to completion (the queue keeps growing
    while the monolithic generate blocks)."""
    pending = list(workload)
    arrival: Dict[int, float] = {}
    latency: Dict[int, float] = {}
    toks = 0
    calls = 0
    hist: List[int] = []
    busy = 0.0
    t0 = time.perf_counter()
    while pending or eng.scheduler.pending():
        now = time.perf_counter() - t0
        while pending and pending[0][2] <= now:
            text, mnt, at = pending.pop(0)
            arrival[eng.submit(text, max_new_tokens=mnt).request_id] = at
        batch = eng.scheduler.next_batch()
        if batch is None:
            # nothing runnable yet: jump to the next arrival
            time.sleep(min(0.001, max(pending[0][2] - now, 0.0)))
            continue
        tb = time.perf_counter()
        reqs = eng.run_batch(batch)
        busy += time.perf_counter() - tb
        done_t = time.perf_counter() - t0
        for r in reqs:
            latency[r.request_id] = done_t - arrival[r.request_id]
            toks += r.stats["new_tokens"]
            calls += r.stats.get("model_calls", 0)
            hist = _add_hist(hist, r.stats.get("accept_hist", []))
    return _summary(latency, toks, busy, calls, hist)


def run_continuous(eng, workload,
                   out_ids: Optional[Dict[int, list]] = None) -> Dict:
    pending = list(workload)
    arrival: Dict[int, float] = {}
    order: Dict[int, int] = {}          # request_id -> submission ordinal
    latency: Dict[int, float] = {}
    toks = 0
    calls = 0
    hist: List[int] = []
    busy = 0.0
    t0 = time.perf_counter()
    while pending or eng.scheduler.pending() or eng.in_flight():
        now = time.perf_counter() - t0
        while pending and pending[0][2] <= now:
            text, mnt, at = pending.pop(0)
            rid = eng.submit(text, max_new_tokens=mnt).request_id
            arrival[rid] = at
            order[rid] = len(order)
        if not (eng.scheduler.pending() or eng.in_flight()):
            time.sleep(min(0.001, max(pending[0][2] - now, 0.0)))
            continue
        tb = time.perf_counter()
        retired = eng.step()
        busy += time.perf_counter() - tb
        done_t = time.perf_counter() - t0
        for r in retired:
            latency[r.request_id] = done_t - arrival[r.request_id]
            toks += r.stats["new_tokens"]
            calls += r.stats.get("model_calls", 0)
            hist = _add_hist(hist, r.stats.get("accept_hist", []))
            if out_ids is not None:
                # keyed by SUBMISSION ordinal (request_ids are process-
                # global), so runs of the same workload compare directly
                # (the sharded-vs-baseline parity check)
                out_ids[order[r.request_id]] = \
                    np.asarray(r.output_ids).tolist()
    return _summary(latency, toks, busy, calls, hist)


# ---------------------------------------------------------------------------
# paged long-context mix (--paged): BENCH_paged.json
# ---------------------------------------------------------------------------
PAGED_BUCKETS = (64, 256)        # short bucket + the long-context bucket
PAGED_PAGE_SIZE = 32
LONG_EVERY = 5                   # every 5th arrival is long-context


def make_longctx_workload(n: int, rate_hz: float, seed: int = 0
                          ) -> List[Tuple[str, int, float]]:
    """Arrival mix where every LONG_EVERY-th request needs the long bucket
    (the rest fit the short one) — the admission pattern paged serving is
    for: shorts must keep flowing around the page-hungry requests."""
    rng = np.random.default_rng(seed)
    texts = [p for p, _ in make_prompts("code", n, seed=1)]
    gaps = rng.exponential(1.0 / rate_hz, n)
    arrivals = np.cumsum(gaps)
    out = []
    for i in range(n):
        text = texts[i % len(texts)]
        if i % LONG_EVERY == 2:                              # long-context
            text = ((text + " ") * 40)[:PAGED_BUCKETS[-1] - 1]
        else:
            text = text[:PAGED_BUCKETS[0] - 1]
        # -1: ByteTokenizer prepends BOS, and the engine rejects raw token
        # counts beyond the largest bucket (that rejection path has its own
        # test; here every request must actually run)
        out.append((text, int(rng.choice(MAX_NEW_CHOICES)),
                    float(arrivals[i])))
    return out


def run_paged(n: int = 24, rate_hz: float = 4.0, max_batch: int = 4,
              seed: int = 0) -> Dict:
    ensure_dirs()
    cfg, params = get_trained()
    tables = get_tables(cfg, params, k_max=16, w_max=10)
    cap = max(MAX_NEW_CHOICES)
    spec = SpecConfig(k=8, w=8, strategy="mixed", max_new_tokens=cap)
    ps = PAGED_PAGE_SIZE
    # linear worst case: every slot carries the long bucket's buffer
    buf_tokens = PAGED_BUCKETS[-1] + cap + spec.w + 2
    linear_equiv_pages = max_batch * (-(-buf_tokens // ps))
    num_pages = int(linear_equiv_pages * 0.6)    # the pool linear can't match

    def make_engine(paged: bool):
        return ServingEngine(params, cfg, spec, tables=tables,
                             max_batch=max_batch, buckets=PAGED_BUCKETS,
                             max_new_cap=cap, paged=paged,
                             num_pages=num_pages if paged else None,
                             page_size=ps)

    res = {"workload": {"n": n, "rate_hz": rate_hz, "seed": seed,
                        "max_batch": max_batch,
                        "buckets": list(PAGED_BUCKETS),
                        "long_every": LONG_EVERY, "page_size": ps,
                        "num_pages": num_pages,
                        "linear_equiv_pages": linear_equiv_pages,
                        "spec": {"k": spec.k, "w": spec.w,
                                 "strategy": spec.strategy}}}
    for mode in ("linear", "paged"):
        eng = make_engine(paged=(mode == "paged"))
        for text in ("warmup", "w" * (PAGED_BUCKETS[-1] - 1)):  # both buckets
            for mnt in MAX_NEW_CHOICES:
                eng.submit(text, max_new_tokens=mnt)
            eng.serve_continuous()
        if mode == "paged":
            eng.reset_pool_counters()   # peak/deferrals measure the
                                        # workload, not the warmup
        summary = run_continuous(eng, make_longctx_workload(n, rate_hz,
                                                            seed))
        if mode == "paged":
            pool = eng.pool_stats()
            assert pool["free_pages"] == pool["num_pages"], (
                f"leaked pages: {pool}")
            assert pool["rejected"] == 0, (
                f"workload must fit the buckets, got rejections: {pool}")
            summary.update(
                peak_kv_tokens=pool["peak_pages"] * ps,
                pool_pages=pool["num_pages"],
                peak_pages=pool["peak_pages"],
                admission_deferrals=pool["deferrals"],
                rejected=pool["rejected"],
                leaked_pages=pool["num_pages"] - pool["free_pages"])
        else:
            # linear residency is static: every slot, whole buffer, always
            summary.update(
                peak_kv_tokens=max_batch * eng._cont_state.buf_size)
        res[mode] = summary
    with open("BENCH_paged.json", "w") as f:
        json.dump(res, f, indent=1)
    return res


# ---------------------------------------------------------------------------
# adaptive (k, w) regret vs the best static arm (--adaptive): BENCH_adaptive
# ---------------------------------------------------------------------------
# a compact arm ladder: greedy, a cheap shallow arm, the paper's sweet spot
# region, and an aggressive deep arm (kept small so the CPU nightly finishes)
ADAPT_ARMS = ((1, 0), (4, 2), (8, 4), (8, 8))


def run_adaptive(n: int = 24, rate_hz: float = 4.0, max_batch: int = 4,
                 seed: int = 0) -> Dict:
    ensure_dirs()
    cfg, params = get_trained()
    arm_k = max(a[0] for a in ADAPT_ARMS)
    arm_w = max(a[1] for a in ADAPT_ARMS)
    tables = get_tables(cfg, params, k_max=max(16, arm_k),
                        w_max=max(10, arm_w))
    cap = max(MAX_NEW_CHOICES)

    def make_engine(arm=None):
        """arm=None: the adaptive engine; else one static-arm engine."""
        if arm is None:
            spec = SpecConfig(k=arm_k, w=arm_w, strategy="mixed",
                              max_new_tokens=cap)
            return ServingEngine(params, cfg, spec, tables=tables,
                                 max_batch=max_batch, buckets=BUCKETS,
                                 max_new_cap=cap, adaptive=True,
                                 arms=ADAPT_ARMS)
        k, w = arm
        spec = (SpecConfig(strategy="greedy", max_new_tokens=cap) if w == 0
                else SpecConfig(k=k, w=w, strategy="mixed",
                                max_new_tokens=cap))
        return ServingEngine(params, cfg, spec, tables=tables,
                             max_batch=max_batch, buckets=BUCKETS,
                             max_new_cap=cap)

    res = {"workload": {"n": n, "rate_hz": rate_hz, "seed": seed,
                        "max_batch": max_batch, "buckets": list(BUCKETS),
                        "arms": [list(a) for a in ADAPT_ARMS]},
           "static_arms": {}}
    workload = make_workload(n, rate_hz, seed)
    for arm in ADAPT_ARMS:
        eng = make_engine(arm)
        eng.submit("warmup", max_new_tokens=min(MAX_NEW_CHOICES))
        eng.serve_continuous()
        res["static_arms"][f"k{arm[0]}_w{arm[1]}"] = run_continuous(
            eng, workload)
    eng = make_engine()
    eng.submit("warmup", max_new_tokens=min(MAX_NEW_CHOICES))
    eng.serve_continuous()
    eng.reset_pool_counters()     # pull counts measure the workload window
    adaptive = run_continuous(eng, workload)
    pulls = eng.adaptive_stats()["pulls_retired"]
    adaptive["arm_pulls"] = pulls
    res["adaptive"] = adaptive
    # Two regret views.  (a) raw wall-clock: on CPU this structurally
    # favours the small static arms, because the masked step always pays
    # the (k_max, w_max)-shaped verify compute whichever arm a slot picks —
    # the roofline says exactly those extra rows/positions are bandwidth-
    # free on TPU, which is the hardware the masking trades for.  (b) the
    # bandit's own objective, tokens-per-call / roofline slowdown: per-arm
    # scores for the static runs vs the adaptive run's pull-weighted
    # realized score — hardware-independent, and the number that should
    # approach zero regret as the workload grows.
    from repro.core.controller import arm_slowdowns
    slow = arm_slowdowns(cfg, ADAPT_ARMS)
    scores = {}
    for arm, s in zip(ADAPT_ARMS, slow):
        r = res["static_arms"][f"k{arm[0]}_w{arm[1]}"]
        scores[f"k{arm[0]}_w{arm[1]}"] = round(
            r["tokens_per_call"] / s, 4)
    w_slow = (sum(p * s for p, s in zip(pulls, slow))
              / max(sum(pulls), 1))
    adaptive_score = round(adaptive["tokens_per_call"] / w_slow, 4)
    best_arm, best = max(res["static_arms"].items(),
                         key=lambda kv: kv[1]["throughput_tok_s"])
    best_score_arm = max(scores, key=scores.get)
    res["regret"] = {
        "best_static_arm_wallclock": best_arm,
        "throughput_regret_tok_s": round(
            best["throughput_tok_s"] - adaptive["throughput_tok_s"], 2),
        "modeled_scores": scores,
        "adaptive_modeled_score": adaptive_score,
        "best_static_arm_modeled": best_score_arm,
        # positive = exploration cost; near zero = the bandit matched the
        # best static arm under its objective
        "modeled_regret": round(scores[best_score_arm] - adaptive_score, 4),
        "modeled_regret_frac": round(
            1.0 - adaptive_score / max(scores[best_score_arm], 1e-9), 4)}
    with open("BENCH_adaptive.json", "w") as f:
        json.dump(res, f, indent=1)
    return res


# ---------------------------------------------------------------------------
# sharded continuous serving over a debug mesh (--mesh): BENCH_sharded.json
# ---------------------------------------------------------------------------
def run_mesh(mesh_shape, n: int = 24, rate_hz: float = 4.0,
             max_batch: int = 4, seed: int = 0) -> Dict:
    ensure_dirs()
    from repro.launch.dryrun import collective_bytes
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(mesh_shape)
    cfg, params = get_trained()
    tables = get_tables(cfg, params, k_max=16, w_max=10)
    spec = SpecConfig(k=8, w=8, strategy="mixed",
                      max_new_tokens=max(MAX_NEW_CHOICES))

    def make_engine(mesh_arg):
        return ServingEngine(params, cfg, spec, tables=tables,
                             max_batch=max_batch, buckets=BUCKETS,
                             max_new_cap=max(MAX_NEW_CHOICES), mesh=mesh_arg)

    res = {"workload": {"n": n, "rate_hz": rate_hz, "seed": seed,
                        "max_batch": max_batch, "buckets": list(BUCKETS),
                        "spec": {"k": spec.k, "w": spec.w,
                                 "strategy": spec.strategy}},
           "mesh": "x".join(str(d) for d in mesh_shape)}
    workload = make_workload(n, rate_hz, seed)
    outputs = {}
    for mode, mesh_arg in (("baseline_1dev", None), ("sharded", mesh)):
        eng = make_engine(mesh_arg)
        eng.submit("warmup", max_new_tokens=min(MAX_NEW_CHOICES))
        eng.serve_continuous()
        outs: Dict[int, list] = {}
        summary = run_continuous(eng, workload, out_ids=outs)
        outputs[mode] = outs
        if mode == "sharded":
            rep = eng.mesh_report()
            assert rep["state_sharded"] > 0 and rep["params_sharded"] > 0, (
                f"mesh {res['mesh']} sharded NOTHING — "
                f"fallbacks: {rep['replication_fallbacks']}")
            summary["mesh_report"] = rep
            # per-step collective profile of the live sharded spec_step —
            # the quantity the 512-device dry-run reports, now for serving
            summary["collectives_per_step"] = collective_bytes(
                eng.step_hlo())
        res[mode] = summary
    # the whole point: sharded serving is bit-identical, token for token
    assert outputs["baseline_1dev"] == outputs["sharded"], (
        "sharded serving diverged from the 1-device baseline")
    res["parity"] = "bit-exact"
    with open("BENCH_sharded.json", "w") as f:
        json.dump(res, f, indent=1)
    return res


# ---------------------------------------------------------------------------
# tree vs linear speculation at matched verify-call cost (--tree): BENCH_tree
# ---------------------------------------------------------------------------
# Verify-call cost = query positions scored per call: k*(w+1) for linear
# batched rows (every row re-scores the shared root), num_nodes+1 for a
# tree (the ancestor mask scores the root ONCE).  That root dedup is the
# measured tree lever on this byte-level model: a branch-1 tree (width, d)
# carries the exact acceptance behaviour of linear (k=width, w=d) for
# width-1 fewer positions, and spending the savings on extra width/depth
# beats the best same-cost linear reshape (probed against a dense
# (k, w) frontier, 4 workload seeds).  Multi-level branching (b >= 2)
# costs width^2 positions per branched level, which byte-level branching
# entropy never pays back — kept as one arm so the JSON documents that
# verdict honestly (negative advantage).
#
# Pairs put the tree at most ONE position above its linear partner:
#   tree w10 d3 b1 =  31  vs  linear (6, 4)  = 30  (best linear <= 31)
#   tree w14 d5 b1 =  71  vs  linear (12, 5) = 72  (best linear <= 72)
#   tree w16 d5 b1 =  81  vs  linear (16, 4) = 80  (best linear <= 81)
#   tree w4  d5 b2 =  69  vs  linear (12, 5) = 72  (branching verdict)
LINEAR_ARMS = ((4, 4), (6, 4), (5, 5), (12, 5), (14, 4), (16, 4), (16, 8))
TREE_ARMS = ((10, 3, 1), (14, 5, 1), (16, 5, 1), (4, 5, 2))
TREE_PAIRS = (("tree_w10_d3_b1", "linear_k6_w4"),
              ("tree_w14_d5_b1", "linear_k12_w5"),
              ("tree_w16_d5_b1", "linear_k16_w4"),
              ("tree_w4_d5_b2", "linear_k12_w5"))
TREE_BUCKET = 128


def make_repetitive_prompts(n: int, seed: int = 0) -> List[str]:
    """Repetitive mix with BRANCHING ambiguity — the workload trees are for.

    Half the prompts loop one chunk verbatim (pure repetition: n-gram
    drafters chain the tail, any k works).  The other half alternate TWO
    chunks sharing a prefix, so at the seam the top-1 n-gram successor is
    right only half the time while the top-2 set always contains the truth:
    a linear draft burns a whole row per guess, a width>=2 tree covers both
    and keeps chaining below each."""
    rng = np.random.default_rng(seed)
    texts = [p for p, _ in make_prompts("code", n, seed=1)]
    out = []
    for i, t in enumerate(texts):
        a = t[:14].strip() or "for i in"
        if i % 2 == 0:
            body = (a + " ") * 8                          # pure repetition
        else:
            b = (a[:6] + t[20:28]).strip() or a + "x"     # shared prefix
            body = "".join((a if j % 2 else b) + " " for j in range(8))
        out.append(body[:TREE_BUCKET - 1])
    return out


def run_tree(n: int = 12, max_new: int = 48, max_batch: int = 4,
             seed: int = 0) -> Dict:
    ensure_dirs()
    from repro.core.tree import num_nodes
    cfg, params = get_trained()
    tables = get_tables(cfg, params, k_max=16, w_max=10)
    prompts = make_repetitive_prompts(n, seed)

    def serve(spec) -> Tuple[Dict, List[list]]:
        eng = ServingEngine(params, cfg, spec, tables=tables,
                            max_batch=max_batch, buckets=(TREE_BUCKET,),
                            max_new_cap=max_new)
        for p in prompts:
            eng.submit(p, max_new_tokens=max_new)
        t0 = time.perf_counter()
        reqs = sorted(eng.serve_all(), key=lambda r: r.request_id)
        wall = time.perf_counter() - t0
        toks = sum(r.stats["new_tokens"] for r in reqs)
        calls = sum(r.stats["model_calls"] for r in reqs)
        hist: List[int] = []
        for r in reqs:
            hist = _add_hist(hist, r.stats.get("accept_hist", []))
        summary = {"new_tokens": toks, "model_calls": calls,
                   "tokens_per_call": round(toks / max(calls, 1), 3),
                   "wall_s": round(wall, 3),
                   "accept_hist": hist}
        return summary, [np.asarray(r.output_ids).tolist() for r in reqs]

    res = {"workload": {"n": n, "max_new": max_new, "max_batch": max_batch,
                        "seed": seed, "bucket": TREE_BUCKET,
                        "mix": "repetitive + 2-way branching seams"},
           "configs": {}}
    # greedy reference once: every speculative config below must reproduce
    # it token for token (tree mode is lossless, not approximate)
    _, ref_out = serve(SpecConfig(strategy="greedy", max_new_tokens=max_new))
    for k, w in LINEAR_ARMS:
        s, out = serve(SpecConfig(k=k, w=w, strategy="mixed",
                                  max_new_tokens=max_new))
        assert out == ref_out, f"linear ({k},{w}) diverged from greedy"
        s["verify_cost"] = k * (w + 1)
        res["configs"][f"linear_k{k}_w{w}"] = s
    for wd, dp, br in TREE_ARMS:
        s, out = serve(SpecConfig(k=wd, w=dp, strategy="mixed",
                                  max_new_tokens=max_new,
                                  tree=True, tree_branch=br))
        assert out == ref_out, f"tree ({wd},{dp},{br}) diverged from greedy"
        s["verify_cost"] = num_nodes(wd, dp, br) + 1
        res["configs"][f"tree_w{wd}_d{dp}_b{br}"] = s
    res["parity"] = "bit-exact vs greedy"
    res["pairs"] = []
    for tname, lname in TREE_PAIRS:
        t, l = res["configs"][tname], res["configs"][lname]
        res["pairs"].append({
            "tree": tname, "linear": lname,
            "tree_cost": t["verify_cost"], "linear_cost": l["verify_cost"],
            "tree_tokens_per_call": t["tokens_per_call"],
            "linear_tokens_per_call": l["tokens_per_call"],
            "tree_advantage": round(
                t["tokens_per_call"] - l["tokens_per_call"], 3)})
    best_lin = max((r["tokens_per_call"] for name, r in
                    res["configs"].items() if name.startswith("linear")))
    best_tree = max((r["tokens_per_call"] for name, r in
                     res["configs"].items() if name.startswith("tree")))
    res["best_linear_tokens_per_call"] = best_lin
    res["best_tree_tokens_per_call"] = best_tree
    # headline: each tree vs the BEST linear arm it could have been traded
    # for (any linear arm costing at most one position more), not just its
    # named partner — a tree only counts as winning if no same-budget
    # linear reshape beats it
    res["headline"] = []
    for name, t in res["configs"].items():
        if not name.startswith("tree"):
            continue
        elig = {ln: l for ln, l in res["configs"].items()
                if ln.startswith("linear")
                and l["verify_cost"] <= t["verify_cost"] + 1}
        bn = max(elig, key=lambda ln: elig[ln]["tokens_per_call"])
        res["headline"].append({
            "tree": name, "tree_cost": t["verify_cost"],
            "tree_tokens_per_call": t["tokens_per_call"],
            "best_linear_at_cost": bn,
            "best_linear_cost": elig[bn]["verify_cost"],
            "best_linear_tokens_per_call": elig[bn]["tokens_per_call"],
            "advantage": round(t["tokens_per_call"]
                               - elig[bn]["tokens_per_call"], 3)})
    with open("BENCH_tree.json", "w") as f:
        json.dump(res, f, indent=1)
    return res


# ---------------------------------------------------------------------------
# lossless speculative sampling (--temperature): BENCH_sampling.json
# ---------------------------------------------------------------------------
def run_sampling(n: int, rate_hz: float, max_batch: int, seed: int,
                 temperature: float, top_p: float) -> Dict:
    """Mixed greedy/sampled continuous serving (DESIGN.md §12): every even
    submission decodes greedy, every odd one samples at ``--temperature`` /
    ``--top-p``, all through ONE sampling-enabled spec_step.  Reports
    tokens/call + acceptance histograms PER temperature CLASS (how much
    speculation survives rejection sampling vs the greedy walk), and
    asserts the greedy class is bit-identical to the same requests served
    by a pure-greedy engine — the lossless contract under mixed serving.
    Sampled requests pin per-ordinal seeds, so reruns replay exactly."""
    ensure_dirs()
    cfg, params = get_trained()
    tables = get_tables(cfg, params, k_max=16, w_max=10)
    spec = SpecConfig(k=8, w=8, strategy="mixed",
                      max_new_tokens=max(MAX_NEW_CHOICES))

    def make_engine(sampling: bool):
        return ServingEngine(params, cfg, spec, tables=tables,
                             max_batch=max_batch, buckets=BUCKETS,
                             max_new_cap=max(MAX_NEW_CHOICES),
                             sampling=sampling, seed=seed)

    workload = make_workload(n, rate_hz, seed)

    def serve(eng, classes: List[str]):
        pending = list(enumerate(workload))
        out: Dict[int, list] = {}
        cls: Dict[str, Dict] = {c: {"requests": 0, "new_tokens": 0,
                                    "model_calls": 0, "accept_hist": []}
                                for c in set(classes)}
        rid2ord: Dict[int, int] = {}
        busy = 0.0
        t0 = time.perf_counter()
        while pending or eng.scheduler.pending() or eng.in_flight():
            now = time.perf_counter() - t0
            while pending and pending[0][1][2] <= now:
                i, (text, mnt, _) = pending.pop(0)
                temp = temperature if classes[i] == "sampled" else 0.0
                rid = eng.submit(text, max_new_tokens=mnt,
                                 temperature=temp,
                                 top_p=top_p if temp > 0 else 1.0,
                                 seed=10_000 + i).request_id
                rid2ord[rid] = i
            if not (eng.scheduler.pending() or eng.in_flight()):
                time.sleep(min(0.001, max(pending[0][1][2] - now, 0.0)))
                continue
            tb = time.perf_counter()
            retired = eng.step()
            busy += time.perf_counter() - tb
            for r in retired:
                i = rid2ord[r.request_id]
                out[i] = np.asarray(r.output_ids).tolist()
                c = cls[classes[i]]
                c["requests"] += 1
                c["new_tokens"] += r.stats["new_tokens"]
                c["model_calls"] += r.stats.get("model_calls", 0)
                c["accept_hist"] = _add_hist(
                    c["accept_hist"], r.stats.get("accept_hist", []))
        for c in cls.values():
            c["tokens_per_call"] = round(
                c["new_tokens"] / max(c["model_calls"], 1), 3)
        return out, cls, busy

    classes = ["greedy" if i % 2 == 0 else "sampled" for i in range(n)]
    eng = make_engine(True)
    eng.submit("warmup", max_new_tokens=min(MAX_NEW_CHOICES),
               temperature=temperature, top_p=top_p)
    eng.serve_continuous()
    out_mixed, cls_stats, busy = serve(eng, classes)

    # lossless check: the greedy-class rows must be bit-identical to the
    # same requests on a PINNED pure-greedy engine (whose step executable
    # is byte-identical to the pre-sampling engine)
    eng_g = make_engine(False)
    eng_g.submit("warmup", max_new_tokens=min(MAX_NEW_CHOICES))
    eng_g.serve_continuous()
    out_greedy, _, _ = serve(eng_g, ["greedy"] * n)
    lossless = all(out_mixed[i] == out_greedy[i]
                   for i in range(n) if classes[i] == "greedy")
    total = sum(c["new_tokens"] for c in cls_stats.values())
    res = {"workload": {"n": n, "rate_hz": rate_hz, "seed": seed,
                        "max_batch": max_batch, "buckets": list(BUCKETS),
                        "temperature": temperature, "top_p": top_p,
                        "spec": {"k": spec.k, "w": spec.w,
                                 "strategy": spec.strategy}},
           "classes": cls_stats,
           "busy_wall_s": round(busy, 3),
           "throughput_tok_s": round(total / max(busy, 1e-9), 2),
           "greedy_class_lossless": bool(lossless)}
    with open("BENCH_sampling.json", "w") as f:
        json.dump(res, f, indent=1)
    return res


def run(n: int = 24, rate_hz: float = 4.0, max_batch: int = 4,
        seed: int = 0) -> Dict:
    ensure_dirs()
    cfg, params = get_trained()
    tables = get_tables(cfg, params, k_max=16, w_max=10)
    spec = SpecConfig(k=8, w=8, strategy="mixed",
                      max_new_tokens=max(MAX_NEW_CHOICES))

    def make_engine():
        return ServingEngine(params, cfg, spec, tables=tables,
                             max_batch=max_batch, buckets=BUCKETS,
                             max_new_cap=max(MAX_NEW_CHOICES))

    # warm the jit caches of the engines we measure, out of the timed region
    # (generate is compiled per (engine, max_new); spec_step/admit_slot are
    # module-level and compile once per shape)
    # static generate compiles per (batch_size, max_new): warm every combo
    # the replay can produce, else compile time pollutes the busy window
    eng_static = make_engine()
    for mnt in MAX_NEW_CHOICES:
        for b in range(1, max_batch + 1):
            for _ in range(b):
                eng_static.submit("warmup", max_new_tokens=mnt)
            eng_static.serve_all()
    eng_cont = make_engine()
    eng_cont.submit("warmup", max_new_tokens=min(MAX_NEW_CHOICES))
    eng_cont.serve_continuous()

    workload = make_workload(n, rate_hz, seed)
    res = {"workload": {"n": n, "rate_hz": rate_hz, "seed": seed,
                        "max_batch": max_batch, "buckets": list(BUCKETS),
                        "spec": {"k": spec.k, "w": spec.w,
                                 "strategy": spec.strategy}},
           "static": run_static(eng_static, workload),
           "continuous": run_continuous(eng_cont, workload)}
    with open("BENCH_continuous.json", "w") as f:
        json.dump(res, f, indent=1)
    return res


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=24)
    ap.add_argument("--rate", type=float, default=4.0)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="run the paged long-context arrival mix and write "
                         "BENCH_paged.json (linear vs paged KV layouts)")
    ap.add_argument("--adaptive", action="store_true",
                    help="benchmark per-slot adaptive (k, w) continuous "
                         "serving against every static arm of its table "
                         "and write BENCH_adaptive.json (regret report)")
    ap.add_argument("--mesh", default="",
                    help="serve the workload sharded over a DxM debug mesh "
                         "(e.g. 2x2) vs the 1-device engine, assert bit "
                         "parity, report per-step collective bytes, and "
                         "write BENCH_sharded.json")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="serve a mixed greedy/sampled workload (half the "
                         "requests sample at this temperature) through one "
                         "sampling-enabled spec_step and write "
                         "BENCH_sampling.json (per-class tokens/call + "
                         "acceptance hists + greedy-class lossless "
                         "assertion, DESIGN.md §12)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus mass for the sampled class of "
                         "--temperature (1 = off)")
    ap.add_argument("--tree", action="store_true",
                    help="benchmark tree-structured speculation against "
                         "linear batched rows at matched verify-call cost "
                         "on the repetitive/branching mix and write "
                         "BENCH_tree.json (DESIGN.md §11)")
    args = ap.parse_args()
    if args.temperature > 0:
        res = run_sampling(args.n, args.rate, args.max_batch, args.seed,
                           args.temperature, args.top_p)
        print("class,requests,tokens_per_call,accept_hist")
        for name in ("greedy", "sampled"):
            c = res["classes"][name]
            print(f"{name},{c['requests']},{c['tokens_per_call']},"
                  f"\"{c['accept_hist']}\"")
        print(f"throughput {res['throughput_tok_s']} tok/s | greedy class "
              f"lossless: {res['greedy_class_lossless']}")
        if not res["greedy_class_lossless"]:
            raise SystemExit("greedy-class rows diverged from the pure-"
                             "greedy engine: lossless contract broken")
        print("wrote BENCH_sampling.json")
        return
    if args.tree:
        res = run_tree(max(args.n, 4), max_batch=args.max_batch,
                       seed=args.seed)
        print("config,verify_cost,tokens_per_call,accept_hist")
        for name, r in res["configs"].items():
            print(f"{name},{r['verify_cost']},{r['tokens_per_call']},"
                  f"\"{r['accept_hist']}\"")
        for p in res["pairs"]:
            print(f"pair {p['tree']} (cost {p['tree_cost']}) vs "
                  f"{p['linear']} (cost {p['linear_cost']}): "
                  f"{p['tree_tokens_per_call']} vs "
                  f"{p['linear_tokens_per_call']} tokens/call "
                  f"(advantage {p['tree_advantage']:+.3f})")
        for h in res["headline"]:
            print(f"headline {h['tree']} (cost {h['tree_cost']}) vs best "
                  f"same-cost linear {h['best_linear_at_cost']} "
                  f"(cost {h['best_linear_cost']}): "
                  f"{h['tree_tokens_per_call']} vs "
                  f"{h['best_linear_tokens_per_call']} tokens/call "
                  f"(advantage {h['advantage']:+.3f})")
        print(f"parity: {res['parity']}")
        print("wrote BENCH_tree.json")
        return
    if args.mesh:
        res = run_mesh(hostdev.parse_mesh_shape(args.mesh), args.n,
                       args.rate, args.max_batch, args.seed)
        print("mode,throughput_tok_s,tokens_per_call,p50_latency_s")
        for mode in ("baseline_1dev", "sharded"):
            r = res[mode]
            print(f"{mode},{r['throughput_tok_s']},"
                  f"{r.get('tokens_per_call', 0)},{r['p50_latency_s']}")
        coll = res["sharded"]["collectives_per_step"]
        rep = res["sharded"]["mesh_report"]
        counts = {k: v for k, v in coll["counts"].items() if v}
        print(f"parity: {res['parity']} | collective bytes/step "
              f"{coll['total']} {counts} | params sharded "
              f"{rep['params_sharded']}/{rep['params_leaves']} | "
              f"state leaves sharded {rep['state_sharded']}")
        print("wrote BENCH_sharded.json")
        return
    if args.adaptive:
        res = run_adaptive(args.n, args.rate, args.max_batch, args.seed)
        print("mode,throughput_tok_s,tokens_per_call,p50_latency_s")
        for name, r in res["static_arms"].items():
            print(f"{name},{r['throughput_tok_s']},"
                  f"{r.get('tokens_per_call', 0)},{r['p50_latency_s']}")
        r = res["adaptive"]
        print(f"adaptive,{r['throughput_tok_s']},"
              f"{r.get('tokens_per_call', 0)},{r['p50_latency_s']}")
        rg = res["regret"]
        print(f"modeled scores (tokens/call / roofline slowdown): "
              f"{rg['modeled_scores']} | adaptive "
              f"{rg['adaptive_modeled_score']} -> modeled regret "
              f"{rg['modeled_regret']} ({rg['modeled_regret_frac']:.1%} "
              f"of best arm {rg['best_static_arm_modeled']})")
        print(f"adaptive arm pulls: {r['arm_pulls']}")
        print("wrote BENCH_adaptive.json")
        return
    if args.paged:
        res = run_paged(args.n, args.rate, args.max_batch, args.seed)
        print("mode,throughput_tok_s,p50_latency_s,p99_latency_s,"
              "peak_kv_tokens,admission_deferrals")
        for mode in ("linear", "paged"):
            r = res[mode]
            print(f"{mode},{r['throughput_tok_s']},{r['p50_latency_s']},"
                  f"{r['p99_latency_s']},{r['peak_kv_tokens']},"
                  f"{r.get('admission_deferrals', 0)}")
        print("wrote BENCH_paged.json")
        return
    res = run(args.n, args.rate, args.max_batch, args.seed)
    print("mode,throughput_tok_s,p50_latency_s,p99_latency_s")
    for mode in ("static", "continuous"):
        r = res[mode]
        print(f"{mode},{r['throughput_tok_s']},{r['p50_latency_s']},"
              f"{r['p99_latency_s']}")
    print("wrote BENCH_continuous.json")


if __name__ == "__main__":
    main()
