"""Model-call microbenchmark (the engine-level analogue of the paper's
CUDA-event timings): CPU wall time per call for decode (1,1) vs verification
(k, w+1), plus the drafter cost — demonstrating 'negligible-cost' drafting
(P1/P2): the drafter must be orders of magnitude cheaper than a model call.

``run_backends`` additionally sweeps the kernel-dispatch backend
(xla | pallas) through the same verify call and a short end-to-end
``generate``, writing ``BENCH_backends.json`` (repo root) so the perf
trajectory of the Pallas fast path is recorded from day one.  On this CPU
container pallas numbers are interpret-mode (correctness signal, not speed);
on a TPU the same sweep measures the real kernels.
"""
from __future__ import annotations

import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from repro.core.drafters import mixed_draft
from repro.core.spec_engine import SpecConfig, generate
from repro.kernels import dispatch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M

from .common import ensure_dirs, get_tables, get_trained, task_prompts


def _time(fn, *args, n=20):
    out = fn(*args)
    jax.tree_util.tree_leaves(out)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.tree_util.tree_leaves(out)[0].block_until_ready()
    return (time.perf_counter() - t0) / n * 1e6  # us


def run(max_len: int = 256) -> dict:
    ensure_dirs()
    cfg, params = get_trained()
    tables = get_tables(cfg, params)
    B, P = 4, 64
    toks = jax.random.randint(jax.random.PRNGKey(0), (B, P), 0,
                              cfg.vocab_size)
    state = M.init_state(cfg, B, max_len)
    _, state = jax.jit(lambda s, t: M.prefill(params, cfg, s, tokens=t)
                       )(state, toks)
    rows = []

    dec = jax.jit(lambda s, t: M.decode(params, cfg, s, t))
    us_dec = _time(lambda: dec(state, toks[:, :1]))
    rows.append(("call_decode_1x1", us_dec, "baseline"))

    for (k, w) in [(5, 4), (10, 10), (25, 14)]:
        vt = jax.random.randint(jax.random.PRNGKey(1), (B, k, w + 1), 0,
                                cfg.vocab_size)
        ver = jax.jit(lambda s, r: M.verify(params, cfg, s, r))
        us_v = _time(lambda: ver(state, vt))
        rows.append((f"call_verify_k{k}_w{w}", us_v,
                     f"slowdown_vs_decode={us_v/us_dec:.2f}x"))

    buf = jnp.zeros((B, max_len), jnp.int32
                    ).at[:, :P].set(toks)
    cur = jnp.full((B,), P, jnp.int32)
    drafter = jax.jit(lambda b, c, l: mixed_draft(tables, b, c, l, 1, 10, 10))
    us_d = _time(lambda: drafter(buf, cur, toks[:, -1]))
    rows.append(("drafter_mixed_k10_w10", us_d,
                 f"fraction_of_decode_call={us_d/us_dec:.3f}"))
    return {"rows": rows}


def run_backends(max_len: int = 192, gen_tokens: int = 24,
                 k: int = 10, w: int = 4) -> dict:
    """Backend sweep: per-verify-call latency + end-to-end tokens/s under
    ``backend="xla"`` vs ``backend="pallas"``.  Writes BENCH_backends.json.
    """
    ensure_dirs()
    cfg0, params = get_trained()
    tables = get_tables(cfg0, params)
    B, P = 4, 64
    toks = jax.random.randint(jax.random.PRNGKey(0), (B, P), 0,
                              cfg0.vocab_size)
    vt = jax.random.randint(jax.random.PRNGKey(1), (B, k, w + 1), 0,
                            cfg0.vocab_size)
    prompts = task_prompts("chat", B, P)
    res = {"interpret": dispatch.default_interpret(),
           "k": k, "w": w, "gen_tokens": gen_tokens, "backends": {}}
    for backend in ("xla", "pallas"):
        cfg = dataclasses.replace(cfg0, backend=backend).validate()
        state = M.init_state(cfg, B, max_len)
        _, state = jax.jit(lambda s, t: M.prefill(params, cfg, s, tokens=t)
                           )(state, toks)
        ver = jax.jit(lambda s, r: M.verify(params, cfg, s, r))
        # interpret-mode pallas is orders slower on CPU; fewer reps suffice
        reps = 20 if backend == "xla" else 3
        us_v = _time(lambda: ver(state, vt), n=reps)
        spec = SpecConfig(k=k, w=w, strategy="mixed",
                          max_new_tokens=gen_tokens, backend=backend)
        gen = jax.jit(lambda p, t, tbl: generate(p, cfg, spec, t, tbl))
        buf, _, stats = gen(params, prompts, tables)     # compile
        buf.block_until_ready()
        t0 = time.perf_counter()
        buf, _, stats = gen(params, prompts, tables)
        buf.block_until_ready()
        wall = time.perf_counter() - t0
        tokens = int(jnp.sum(stats["tokens"]))
        calls = int(jnp.sum(stats["calls"]))
        res["backends"][backend] = {
            "verify_call_us": us_v,
            "tokens_per_s": tokens / wall,
            "tokens_per_call": tokens / max(calls, 1),
            "generate_wall_s": wall,
        }
    with open("BENCH_backends.json", "w") as f:
        json.dump(res, f, indent=1)
    return res


def main():
    enable_compile_cache()
    for name, us, derived in run()["rows"]:
        print(f"{name:24s} {us:10.0f} us   {derived}")
    res = run_backends()
    for backend, r in res["backends"].items():
        print(f"backend_{backend:7s} verify={r['verify_call_us']:10.0f} us  "
              f"tokens/s={r['tokens_per_s']:8.1f}  "
              f"tok/call={r['tokens_per_call']:.2f}")
    print("wrote BENCH_backends.json"
          + (" (pallas in interpret mode)" if res["interpret"] else ""))


if __name__ == "__main__":
    main()
