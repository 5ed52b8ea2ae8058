"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Loads (or quickly trains) a model, builds the learning-free tables from its
own weights, then serves a batch of prompts with batched speculation and
reports tokens/call + wall time vs the greedy baseline.
"""
from __future__ import annotations

import argparse

from repro.launch import hostdev

if __name__ == "__main__":
    # --mesh needs placeholder devices BEFORE the jax import below locks
    # the count (appends to XLA_FLAGS; respects a caller-provided count)
    hostdev.ensure_for_mesh_argv()

import jax

from repro.configs import ALL_ARCHS, get_smoke_config
from repro.core.spec_engine import SpecConfig
from repro.data.datasets import make_prompts
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import ServingEngine
from repro.train import AdamWConfig, init_train_state, make_train_step
from repro.train.checkpoint import load


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS, default="mistral-7b")
    ap.add_argument("--ckpt", default="", help="params npz (else quick-train)")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--w", type=int, default=10)
    ap.add_argument("--strategy", default="mixed",
                    choices=["mixed", "bigram", "unigram", "context",
                             "greedy"])
    ap.add_argument("--max-new", type=int, default=48)
    ap.add_argument("--n-prompts", type=int, default=4)
    ap.add_argument("--task", default="code", choices=["code", "math",
                                                       "chat"])
    ap.add_argument("--continuous", action="store_true",
                    help="serve with slot-level continuous batching instead "
                         "of static batches")
    ap.add_argument("--adaptive", action="store_true",
                    help="pick (k, w) online with the UCB controller "
                         "instead of the static --k/--w: per batch under "
                         "static serving, per slot per step (shape-stable "
                         "arm masking inside the one jitted spec_step, "
                         "DESIGN.md §9) under --continuous")
    ap.add_argument("--tree", action="store_true",
                    help="tree-structured speculation (DESIGN.md §11): "
                         "branch on the top --k candidates at the first "
                         "--tree-branch depths, verify the whole token tree "
                         "in ONE ancestor-masked forward call; bit-identical "
                         "outputs, attention-only archs")
    ap.add_argument("--tree-branch", type=int, default=2,
                    help="number of branching levels in the draft tree "
                         "(deeper levels chain greedily); only with --tree")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache for continuous batching: slots "
                         "share a page pool with per-slot page tables "
                         "(DESIGN.md §8) instead of worst-case linear "
                         "buffers; bit-identical outputs")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page-pool size for --paged (0 = linear worst "
                         "case; smaller pools defer admission when "
                         "exhausted)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="positions per page for --paged (0 = the verify "
                         "kernel's cache block)")
    ap.add_argument("--mesh", default="",
                    help="serve SHARDED over a DxM debug mesh (e.g. 2x2 = "
                         "data 2 x model 2; 3 dims add a leading pod axis). "
                         "On CPU the launcher forces placeholder devices "
                         "via XLA_FLAGS when none are configured; outputs "
                         "stay bit-identical to unsharded serving "
                         "(DESIGN.md §10)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for every submitted request "
                         "(0 = greedy, bit-exact spec path; > 0 serves "
                         "losslessly via rejection-verified speculative "
                         "sampling inside the same spec_step, DESIGN.md "
                         "§12)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus mass for --temperature > 0 (1 = off)")
    ap.add_argument("--seed", type=int, default=0,
                    help="engine rng seed: request keys derive from it, so "
                         "a rerun with the same seed replays the same "
                         "sampled outputs")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "xla", "pallas"],
                    help="kernel-dispatch backend (kernels/dispatch.py): "
                         "auto = pallas on TPU, xla elsewhere; pallas "
                         "off-TPU runs in interpret mode (slow, parity "
                         "checking only)")
    args = ap.parse_args()
    if args.paged and not args.continuous:
        raise SystemExit("--paged applies to --continuous serving")
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(hostdev.parse_mesh_shape(args.mesh))

    cfg = get_smoke_config(args.arch)
    if cfg.encoder_only:
        raise SystemExit(f"{args.arch}: encoder-only arch has no decode loop")
    import dataclasses
    cfg = dataclasses.replace(cfg, vocab_size=max(cfg.vocab_size, 259),
                              backend=args.backend)
    ts = init_train_state(jax.random.PRNGKey(0), cfg)
    params = ts["params"]
    if args.ckpt:
        params = load(args.ckpt, params)
    else:
        import jax.numpy as jnp

        from repro.data.pipeline import mixed_batches
        print("quick-training the smoke model (pass --ckpt to skip)...")
        step = jax.jit(make_train_step(cfg, AdamWConfig(
            lr=1e-3, total_steps=80, warmup_steps=8), remat=False))
        for b in mixed_batches(8, 128, 80):
            ts, m = step(ts, jnp.asarray(b))
        params = ts["params"]
        print(f"  final loss {float(m['loss']):.3f}")

    spec = SpecConfig(k=args.k, w=args.w, strategy=args.strategy,
                      max_new_tokens=args.max_new, backend=args.backend,
                      tree=args.tree, tree_branch=args.tree_branch)
    eng = ServingEngine(params, cfg, spec, max_batch=args.n_prompts,
                        max_new_cap=args.max_new, adaptive=args.adaptive,
                        paged=args.paged,
                        num_pages=args.num_pages or None,
                        page_size=args.page_size, mesh=mesh,
                        sampling=args.temperature > 0 or None,
                        seed=args.seed)
    if eng.tables_s is not None:
        print(f"drafter tables built in {eng.tables_s:.1f} s")
    for prompt, _ in make_prompts(args.task, args.n_prompts):
        eng.submit(prompt, max_new_tokens=args.max_new,
                   temperature=args.temperature, top_p=args.top_p)
    served = eng.serve_continuous() if args.continuous else eng.serve_all()
    for r in served:
        if "error" in r.stats:
            print(f"[req {r.request_id}] REJECTED: {r.stats['error']}")
            continue
        print(f"[req {r.request_id}] tokens/call="
              f"{r.stats['tokens_per_call']:.2f} "
              f"calls={r.stats['model_calls']} "
              f"output={r.output[:60]!r}")
    if args.paged:
        print(f"pool: {eng.pool_stats()}")
    if args.adaptive and args.continuous:
        print(f"bandit: {eng.adaptive_stats()}")
    if mesh is not None:
        rep = eng.mesh_report()
        print(f"mesh: {rep.get('mesh')} params sharded "
              f"{rep.get('params_sharded')}/{rep.get('params_leaves')} "
              f"state leaves sharded {rep.get('state_sharded', 'n/a')} "
              f"fallbacks {rep.get('replication_fallbacks')}")


if __name__ == "__main__":
    main()
