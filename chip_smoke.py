#!/usr/bin/env python3
"""Smoke test of the serving path on TPU chips.

    python chip_smoke.py              # StableLM-2-1.6B on one chip
    python chip_smoke.py --chips 4    # Mistral-7B sharded over (data=1, model=4)

One chip: StableLM-2-1.6B at its published widths (24 layers, d_model
2048, 32/32 heads, vocab 100352; random float32 weights from ``--seed``,
matmuls at full precision) is served through ``repro.serving.ServingEngine``
with the default ``SpecConfig`` (mixed drafter, k=10, w=10, backend
"auto"), once over the linear KV cache and once over the paged one.  Before
any request is served, the compiled step must hold both Pallas kernels as
TPU custom calls (verify attention and the n-gram sweep), and the kernels
must agree with their references on the chip.  Every request's output must
equal the engine's own ``strategy="greedy"`` path token for token.  Every
served token is also witnessed by the uncached model: the full forward over
the prompt plus the served tokens, run on the request alone, must pick it
as argmax, or rank it below the argmax by no more than the same forward
moves between batch shapes there (on a v5e that change stays near 3e-6 in
float32 at highest precision, where every served token is the argmax; in
bfloat16 it reaches about 0.05, above many of the top-2 gaps that random
weights leave).  The first divergence from the batched
``greedy_reference`` is printed with its top-2 margin.  Four chips: Mistral-7B at its published widths, parameters
initialised straight into their shardings, speculative output equal to
greedy on the same mesh and witnessed the same way.

Every line before the last is one JSON object per phase.  Timings there
are one-off smoke timings (compilation included), not benchmark numbers;
tokens/call is a count, and meaningless with random weights.  The last
line is printed only when every phase passed:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The script exits non-zero, printing no result, on any other platform, and
when it is not run from a checkout of the repository.  It runs every phase
in this one process and starts no other.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

# the smoke's workload: prompts from the in-repo code corpus, left-padded
# to one 512-token bucket; budgets alternate so that slots retire at
# different steps and queued requests are admitted into freed slots
N_REQUESTS = 12
SLOTS = 8
BUCKET = 512
MAX_NEW = 32
SHORT_NEW = 20
# float32 verify attention against its reference: a sound kernel read
# 1.85e-6 on a v5e; the same inputs rounded to bfloat16 must read above it
VERIFY_TOL = 1e-4


def require_tpu():
    """The devices JAX sees, or exit: the smoke never runs on the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX's first device is "
            f"{devices[0].platform!r} ({devices[0].device_kind}); refusing "
            f"to run on it")
    return devices


def import_repro():
    """Import the package from this checkout's ``src/``, or exit."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit(f"chip_smoke: {SRC}/repro not found — run this "
                         f"script from a checkout of the repository")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    import repro.serving
    origin = os.path.abspath(repro.serving.__file__)
    if not origin.startswith(SRC + os.sep):
        raise SystemExit(f"chip_smoke: imported repro from {origin}, not "
                         f"from {SRC}")


def emit(rec: dict) -> dict:
    print(json.dumps(rec), flush=True)
    return rec


def peak_bytes(devices) -> list:
    return [d.memory_stats().get("peak_bytes_in_use")
            if d.memory_stats() else None for d in devices]


def model_config(arch: str):
    """``arch`` at its published widths, in float32: the smoke checks exact
    equality with greedy decoding, and in bfloat16 random weights put
    near-ties where the verify call and a one-token decode round apart."""
    import dataclasses

    import jax.numpy as jnp

    from repro.configs import get_config
    return dataclasses.replace(get_config(arch), param_dtype=jnp.float32,
                               compute_dtype=jnp.float32).validate()


def init_params(cfg, seed: int, mesh=None):
    """Random params built by one jitted init on the device(s): with a mesh
    straight into their shardings, so no chip ever holds the whole model."""
    import jax

    from repro.distributed import sharding as shd
    from repro.models import model as M
    key = jax.random.PRNGKey(seed)
    init = lambda k: M.init_params(k, cfg)
    out = None
    if mesh is not None:
        out = shd.params_shardings(mesh, jax.eval_shape(init, key))
    return jax.jit(init, out_shardings=out)(key)


def requests():
    from repro.data.datasets import make_prompts
    prompts = [p for p, _ in make_prompts("code", N_REQUESTS)]
    return [(p, MAX_NEW if i % 2 == 0 else SHORT_NEW)
            for i, p in enumerate(prompts)]


def make_engine(params, cfg, spec, *, paged=False, mesh=None, tables=None):
    from repro.serving import ServingEngine
    return ServingEngine(params, cfg, spec, tables=tables, max_batch=SLOTS,
                         buckets=(BUCKET,), max_new_cap=MAX_NEW, paged=paged,
                         mesh=mesh)


def serve(engine, reqs):
    """Submit every request, drain the queue; (outputs, tokens, calls)."""
    submitted = [engine.submit(p, max_new_tokens=m) for p, m in reqs]
    engine.serve_continuous()
    for r in submitted:
        if r.stats is None or "error" in r.stats:
            raise RuntimeError(f"request {r.request_id} not served: "
                               f"{r.stats}")
    tokens = sum(r.stats["new_tokens"] for r in submitted)
    calls = sum(r.stats["model_calls"] for r in submitted)
    return [np.asarray(r.output_ids) for r in submitted], tokens, calls


def padded_prompts(engine, reqs) -> np.ndarray:
    """The (n, bucket) token rows the engine admits for ``reqs``."""
    return np.stack([engine.scheduler.pad_to_bucket(engine.tok.encode(p))
                     for p, _ in reqs])


def compare(got, want) -> dict:
    """Per-request equality of ``got`` with the prefix of ``want``."""
    div = []
    for i, (g, w) in enumerate(zip(got, want)):
        w = np.asarray(w)[:len(g)]
        if len(g) == len(w) and (g == w).all():
            continue
        j = int(np.argmax(g[:len(w)] != w)) if len(w) else 0
        div.append({"request": i, "position": j,
                    "got": int(g[j]), "want": int(w[j])})
    return {"equal": len(got) - len(div), "total": len(got),
            "divergences": div}


@functools.lru_cache(maxsize=None)
def _teacher_forced_fn(cfg, P: int):
    """One jitted program: the full forward (no KV cache) over ``rows``,
    on each row alone and on the whole batch, read at positions P-1.. —
    the logits that choose every token after the prompt.  params stay an
    argument: a closure would put the whole model into the program."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as M

    def logits(params, rows):
        x, _ = M.forward_hidden(params, cfg, tokens=rows)
        return M.lm_logits(params["embed"], x[:, P - 1:-1], cfg)

    def fn(params, rows):
        lone = jax.lax.map(lambda r: logits(params, r[None])[0], rows)
        batched = logits(params, rows)
        served = rows[:, P:]
        top2 = jax.lax.top_k(lone, 2)[0]
        got = jnp.take_along_axis(lone, served[..., None], -1)[..., 0]
        return {"argmax": jnp.argmax(lone, -1).astype(jnp.int32),
                "gap": top2[..., 0] - got,
                "margin": top2[..., 0] - top2[..., 1],
                "noise": jnp.max(jnp.abs(lone - batched), -1)}
    return jax.jit(fn)


def teacher_forced(params, cfg, prompts, outs) -> dict:
    """The uncached model's reading of every served token.  Each request's
    prompt plus its served tokens (zero-padded to ``MAX_NEW``; causality
    keeps the padding out of every position read) goes through the full
    forward.  Per request and served position: the lone forward's argmax;
    ``gap``, how far the served token's logit falls below that argmax (0
    when it is the argmax); the top-2 ``margin``; and ``noise``, the largest
    change of any logit between the lone and the batched forward — how far
    the uncached model itself moves with its batch shape there."""
    import jax.numpy as jnp
    rows = np.zeros((len(outs), prompts.shape[1] + MAX_NEW), np.int32)
    rows[:, :prompts.shape[1]] = prompts
    for i, o in enumerate(outs):
        rows[i, prompts.shape[1]:prompts.shape[1] + len(o)] = o
    fn = _teacher_forced_fn(cfg, prompts.shape[1])
    return {k: np.asarray(v) for k, v in
            fn(params, jnp.asarray(rows)).items()}


def witness(tf: dict, outs) -> dict:
    """Every served token against the uncached model: it must be the lone
    forward's argmax, or lose to it by no more than that forward's own
    ``noise`` there — a near-tie the uncached model cannot settle between
    two batch shapes.  Anything else is a disagreement."""
    near, bad = [], []
    for i, o in enumerate(outs):
        for j in np.flatnonzero(tf["argmax"][i, :len(o)] != o):
            d = {"request": i, "position": int(j), "got": int(o[j]),
                 "argmax": int(tf["argmax"][i, j]),
                 "gap": float(tf["gap"][i, j]),
                 "noise": float(tf["noise"][i, j])}
            (near if d["gap"] <= d["noise"] else bad).append(d)
    n = sum(len(o) for o in outs)
    return {"tokens": n, "argmax": n - len(near) - len(bad),
            "near_ties": near, "disagreements": bad,
            "max_noise": float(max((tf["noise"][i, :len(o)].max()
                                    for i, o in enumerate(outs) if len(o)),
                                   default=0.0))}


def mark_margins(cmp: dict, tf: dict) -> None:
    """Give each first divergence in ``cmp`` the lone forward's top-2 logit
    margin at that position (both sides share the prefix before it)."""
    for d in cmp["divergences"]:
        d["top2_margin"] = float(tf["margin"][d["request"], d["position"]])


def witness_failures(wit: dict) -> list:
    bad = wit["disagreements"]
    return ([f"{len(bad)} served token(s) disagree with the uncached "
             f"forward beyond its own noise: {bad[:5]}"] if bad else [])


def check_kernels(kernels, expected) -> list:
    missing = sorted(set(expected) - set(kernels))
    return ([f"Pallas kernels not compiled for the chip: {missing}"]
            if missing else [])


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def kernels_phase(cfg, *, slots: int, cache_len: int, seed: int) -> dict:
    """Both kernels against their references at the model's widths: the
    n-gram sweep bit for bit, float32 verify attention to ``VERIFY_TOL``.
    The same inputs rounded to bfloat16 must miss that limit, so a kernel
    that quietly computed in bfloat16 fails here."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import dispatch, ops
    rng = np.random.default_rng(seed)
    failures = []
    for q, w in ((1, 10), (2, 130)):
        buf = jnp.asarray(rng.integers(0, 4, (slots, cache_len)), jnp.int32)
        query = jnp.asarray(rng.integers(0, 4, (slots, q)), jnp.int32)
        cur = jnp.asarray(rng.integers(q, cache_len + 1, slots), jnp.int32)
        got = dispatch.ngram_sweep(buf, query, cur, w=w, backend="pallas")
        want = dispatch.ngram_sweep(buf, query, cur, w=w, backend="xla")
        for name, g, x in zip(("match", "hash"), got, want):
            if not (np.asarray(g) == np.asarray(x)).all():
                failures.append(f"ngram_match {name} differs from the XLA "
                                f"sweep at q={q} w={w}")
    k, w1 = 10, 11
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    mk = lambda kk, shape: jax.random.normal(kk, shape).astype(
        cfg.compute_dtype)
    q_ = mk(keys[0], (slots, k, w1, H, hd))
    kc = mk(keys[1], (slots, cache_len, KV, hd))
    vc = mk(keys[2], (slots, cache_len, KV, hd))
    kt = mk(keys[3], (slots, k, w1, KV, hd))
    vt = mk(keys[4], (slots, k, w1, KV, hd))
    cur = jnp.asarray(rng.integers(1, cache_len + 1, slots), jnp.int32)
    want = ops.spec_attention_ref_op(q_, kc, vc, kt, vt, cur, w1=w1)
    err = lambda dt: float(jnp.max(jnp.abs(dispatch.verify_attention(
        *(x.astype(dt) for x in (q_, kc, vc, kt, vt)), cur, w1=w1,
        block_s=cfg.kernel_block_s).astype(jnp.float32) - want)))
    f32_err, bf16_err = err(jnp.float32), err(jnp.bfloat16)
    if not f32_err <= VERIFY_TOL < bf16_err:
        failures.append(f"spec_attention max |err| {f32_err} (bf16 inputs: "
                        f"{bf16_err}) is not within {VERIFY_TOL} and below "
                        f"the bf16 error")
    return {"phase": "kernels", "ok": not failures, "failures": failures,
            "verify_max_abs_err": f32_err, "verify_bf16_max_abs_err": bf16_err,
            "verify_tol": VERIFY_TOL}


def greedy_phase(params, cfg, reqs, *, mesh=None, devices=()) -> dict:
    """The two greedy baselines: the engine's own strategy="greedy" path,
    and the uncached full-forward ``greedy_reference``; every token of the
    engine's path is witnessed by the uncached forward."""
    import jax.numpy as jnp

    from repro.core.spec_engine import SpecConfig, greedy_reference
    t0 = time.perf_counter()
    eng = make_engine(params, cfg, SpecConfig(strategy="greedy"), mesh=mesh)
    prompts = padded_prompts(eng, reqs)
    engine_out, _, _ = serve(eng, reqs)
    del eng
    gc.collect()
    ref = np.asarray(greedy_reference(params, cfg, jnp.asarray(prompts),
                                      MAX_NEW))[:, prompts.shape[1]:]
    cmp = compare(engine_out, ref)
    tf = teacher_forced(params, cfg, prompts, engine_out)
    mark_margins(cmp, tf)
    wit = witness(tf, engine_out)
    failures = witness_failures(wit)
    rec = {"phase": "greedy", "ok": not failures, "failures": failures,
           "requests": len(reqs), "engine_greedy_vs_reference": cmp,
           "uncached_witness": wit,
           "smoke_wall_s": round(time.perf_counter() - t0, 3),
           "peak_bytes_in_use": peak_bytes(devices)}
    rec["_outputs"] = {"engine_greedy": engine_out, "reference": ref,
                       "prompts": prompts}
    return rec


def spec_phase(name, params, cfg, reqs, greedy: dict, *, spec=None,
               paged=False, mesh=None, tables=None, expect_kernels=(),
               devices=()) -> dict:
    """Serve ``reqs`` speculatively; check the kernels, equality with the
    engine's greedy path, and every token against the uncached forward."""
    from repro.core.spec_engine import SpecConfig
    from repro.kernels import dispatch
    t0 = time.perf_counter()
    eng = make_engine(params, cfg, spec or SpecConfig(), paged=paged,
                      mesh=mesh, tables=tables)
    tables_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    hlo = eng.step_hlo()
    compile_s = time.perf_counter() - t1
    kernels = dispatch.kernels_in_hlo(hlo)
    failures = check_kernels(kernels, expect_kernels)
    t2 = time.perf_counter()
    out, tokens, calls = serve(eng, reqs)
    serve_s = time.perf_counter() - t2
    extra = {"_tables": eng.tables}
    if paged:
        extra["pool"] = eng.pool_stats()
    if mesh is not None:
        rep = eng.mesh_report()
        extra["mesh"] = {k: rep.get(k) for k in
                         ("mesh", "params_sharded", "params_leaves",
                          "state_sharded", "replication_fallbacks")}
    del eng                 # free its DecodeState before the witness
    gc.collect()
    outs = greedy["_outputs"]
    vs_engine = compare(out, outs["engine_greedy"])
    vs_ref = compare(out, outs["reference"])
    tf = teacher_forced(params, cfg, outs["prompts"], out)
    mark_margins(vs_engine, tf)
    mark_margins(vs_ref, tf)
    wit = witness(tf, out)
    if vs_engine["equal"] != vs_engine["total"]:
        failures.append(f"{vs_engine['total'] - vs_engine['equal']} "
                        f"request(s) differ from the engine greedy path")
    failures += witness_failures(wit)
    return {"phase": name, "ok": not failures, "failures": failures,
            "dtype": np.dtype(cfg.compute_dtype).name,
            "paged": paged, "requests": len(reqs), "kernels": list(kernels),
            "tokens": tokens, "calls": calls,
            "tokens_per_call": round(tokens / max(calls, 1), 4),
            "vs_engine_greedy": vs_engine, "vs_greedy_reference": vs_ref,
            "uncached_witness": wit,
            "smoke_build_s": round(tables_s, 3),
            "smoke_step_compile_s": round(compile_s, 3),
            "smoke_serve_s": round(serve_s, 3),
            "peak_bytes_in_use": peak_bytes(devices), **extra}


def public(rec: dict) -> dict:
    return {k: v for k, v in rec.items() if not k.startswith("_")}


def smoke_one_chip(cfg, *, seed: int, devices=(), spec=None) -> list:
    """Kernel checks, greedy baselines, linear and paged speculative
    serving of ``cfg`` on the default device.  Returns the phase records
    (each also printed as it completes)."""
    from repro.kernels import dispatch
    from repro.models import cache as C
    reqs = requests()
    t0 = time.perf_counter()
    params = init_params(cfg, seed)
    cache_len = dispatch.align_cache_len(BUCKET + MAX_NEW + 12,
                                         cfg.kernel_block_s)
    recs = [emit(dict(kernels_phase(cfg, slots=SLOTS, cache_len=cache_len,
                                    seed=seed),
                      init_s=round(time.perf_counter() - t0, 3)))]
    greedy = greedy_phase(params, cfg, reqs, devices=devices)
    recs.append(greedy)
    emit(public(greedy))
    lin = spec_phase("linear", params, cfg, reqs, greedy, spec=spec,
                     expect_kernels=("spec_attention", "ngram_match"),
                     devices=devices)
    recs.append(lin)
    emit(public(lin))
    tables = lin.pop("_tables")
    if C.paged_supported(cfg):
        pg = spec_phase("paged", params, cfg, reqs, greedy, spec=spec,
                        paged=True, tables=tables,
                        expect_kernels=("paged_spec_attention",
                                        "ngram_match"),
                        devices=devices)
        pg.pop("_tables")
        recs.append(pg)
        emit(public(pg))
    return recs


def smoke_mesh(cfg, mesh, *, seed: int, devices=()) -> list:
    """Sharded serving on ``mesh``: speculative output equal to greedy on
    the same mesh (engine greedy path and greedy_reference)."""
    t0 = time.perf_counter()
    reqs = requests()
    params = init_params(cfg, seed, mesh=mesh)
    init = {"phase": "init", "ok": True, "failures": [],
            "init_s": round(time.perf_counter() - t0, 3),
            "peak_bytes_in_use": peak_bytes(devices)}
    recs = [emit(init)]
    greedy = greedy_phase(params, cfg, reqs, mesh=mesh, devices=devices)
    recs.append(greedy)
    emit(public(greedy))
    sh = spec_phase("sharded", params, cfg, reqs, greedy, mesh=mesh,
                    devices=devices)
    sh.pop("_tables")
    recs.append(sh)
    emit(public(sh))
    return recs


def last_line(devices, count: int) -> str:
    return json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": count}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: StableLM-2-1.6B on one chip (linear + paged); "
                         "4: Mistral-7B sharded over a (data=1, model=4) "
                         "mesh, and nothing else")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights")
    args = ap.parse_args(argv)
    devices = require_tpu()
    import jax
    import_repro()
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    print(json.dumps({"phase": "setup", "compile_cache": cache,
                      "dtype": "float32", "chips": args.chips,
                      "device_kind": devices[0].device_kind,
                      "jax": jax.__version__,
                      "note": "timings are one-off smoke timings, "
                              "compilation included; not benchmark "
                              "numbers"}), flush=True)
    with jax.default_matmul_precision("highest"):
        if args.chips == 4:
            if len(devices) < 4:
                raise SystemExit(f"chip_smoke: --chips 4 needs 4 chips, "
                                 f"JAX sees {len(devices)}")
            from repro.launch.mesh import make_debug_mesh
            mesh = make_debug_mesh((1, 4))
            used = list(mesh.devices.flat)
            recs = smoke_mesh(model_config("mistral-7b"), mesh,
                              seed=args.seed, devices=used)
        else:
            used = devices[:1]
            recs = smoke_one_chip(model_config("stablelm-1.6b"),
                                  seed=args.seed, devices=used)
    failed = [r for r in recs if not r["ok"]]
    if failed:
        for r in failed:
            print(f"chip_smoke: phase {r['phase']} failed: {r['failures']}",
                  file=sys.stderr)
        return 1
    print(last_line(devices, len(used)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
