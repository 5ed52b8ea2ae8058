"""Sharded-vs-single-device bit parity for LIVE serving over a real mesh
(DESIGN.md §10).

The contract: a ``ServingEngine(mesh=...)`` — params placed by
``params_shardings``, DecodeState by ``decode_state_shardings``, the
activation sharder scoped to the engine's own traces — produces BIT-
IDENTICAL token streams to the same engine without a mesh, for one-shot
``generate()`` and the continuous ``admit_slot``/``spec_step`` drive,
across every drafting strategy, over the linear and the paged KV layout,
compiling the sharded step exactly once.

This module needs placeholder devices: jax locks the device count at first
init, so the flag must precede interpreter-wide jax import — run it in its
OWN process (the CI ``sharded`` lane):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest tests/test_sharded_serving.py

Under the plain tier-1 run (1 CPU device) everything here skips.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

NEEDED_DEVICES = 4

pytestmark = pytest.mark.skipif(
    jax.device_count() < NEEDED_DEVICES,
    reason="sharded lane: run with XLA_FLAGS="
           "--xla_force_host_platform_device_count=8 in a fresh process")

from repro.core import spec_engine                              # noqa: E402
from repro.core.ngram_tables import (NGramTables, build_bigram,  # noqa: E402
                                     build_unigram)
from repro.core.spec_engine import SpecConfig                   # noqa: E402
from repro.distributed import act_sharding                      # noqa: E402
from repro.distributed import sharding as shd                   # noqa: E402
from repro.kernels import ops                                   # noqa: E402
from repro.launch.mesh import make_debug_mesh                   # noqa: E402
from repro.models import model as M                             # noqa: E402
from repro.models.config import ModelConfig                     # noqa: E402
from repro.serving import ServingEngine                         # noqa: E402

F32 = dict(param_dtype=jnp.float32, compute_dtype=jnp.float32)

PROMPTS = [("hello world", 16), ("a rather different prompt", 12),
           ("third request!", 16), ("four", 9), ("five arrives late", 16)]


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig(name="mesh-tiny", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=61,
                      **F32).validate()
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def tables(model):
    cfg, params = model
    fwd = jax.jit(lambda t: M.forward(params, cfg, tokens=t)[0][:, -1])
    topk, chain = build_bigram(fwd, cfg.vocab_size, k_max=8, w_max=8,
                               batch=cfg.vocab_size)
    uni = build_unigram(params["embed"]["embedding"],
                        params["embed"]["lm_head"], k_max=8)
    return NGramTables(uni, topk, chain)


@pytest.fixture(scope="module")
def mesh22():
    return make_debug_mesh((2, 2))


def _spec(strategy):
    return SpecConfig(k=4, w=3, strategy=strategy, max_new_tokens=16)


def _engine(model, tables, spec, mesh, **kw):
    cfg, params = model
    return ServingEngine(params, cfg, spec,
                         tables=tables if spec.strategy != "greedy" else None,
                         max_batch=4, buckets=(16,), max_new_cap=16,
                         mesh=mesh, **kw)


def _serve(eng, mode="continuous", prompts=PROMPTS):
    reqs = [eng.submit(p, max_new_tokens=m) for p, m in prompts]
    done = eng.serve_continuous() if mode == "continuous" else eng.serve_all()
    by_id = {r.request_id: r for r in done}
    assert sorted(by_id) == sorted(r.request_id for r in reqs)
    return [by_id[r.request_id] for r in reqs]


def _assert_parity(plain, meshed):
    for a, b in zip(plain, meshed):
        np.testing.assert_array_equal(a.output_ids, b.output_ids,
                                      err_msg=a.prompt)
        assert a.stats["new_tokens"] == b.stats["new_tokens"]
        assert a.stats["model_calls"] == b.stats["model_calls"]


# ---------------------------------------------------------------------------
# generate(): sharded serve_all == single-device serve_all, every strategy
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("strategy", ["greedy", "bigram", "unigram",
                                      "context", "mixed"])
def test_generate_sharded_parity(model, tables, mesh22, strategy):
    plain = _serve(_engine(model, tables, _spec(strategy), None),
                   mode="static")
    meshed = _serve(_engine(model, tables, _spec(strategy), mesh22),
                    mode="static")
    _assert_parity(plain, meshed)
    assert not act_sharding.installed(), "engine leaked its mesh globally"


# ---------------------------------------------------------------------------
# continuous admit/step drive: every strategy (linear), mixed+greedy (paged)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("strategy", ["greedy", "bigram", "unigram",
                                      "context", "mixed"])
def test_continuous_sharded_parity(model, tables, mesh22, strategy):
    plain = _serve(_engine(model, tables, _spec(strategy), None))
    meshed = _serve(_engine(model, tables, _spec(strategy), mesh22))
    _assert_parity(plain, meshed)
    assert not act_sharding.installed()


@pytest.mark.parametrize("strategy", ["greedy", "mixed"])
def test_continuous_sharded_parity_paged(model, tables, mesh22, strategy):
    """The paged pool under a mesh: the pool's page axis shards like the
    sequence axis (decode_state_pspec) and outputs stay bit-identical."""
    kw = dict(paged=True, page_size=8)
    plain = _serve(_engine(model, tables, _spec(strategy), None, **kw))
    meshed = _serve(_engine(model, tables, _spec(strategy), mesh22, **kw))
    _assert_parity(plain, meshed)


def test_adaptive_sharded_parity(model, tables, mesh22):
    """In-flight adaptive (k, w) arm masking composes with the mesh: the
    per-slot bandit state rides the sharded DecodeState.stats."""
    arms = ((1, 0), (2, 2), (4, 3))
    kw = dict(adaptive=True, arms=arms)
    spec = _spec("mixed")
    plain = _serve(_engine(model, tables, spec, None, **kw))
    meshed = _serve(_engine(model, tables, spec, mesh22, **kw))
    _assert_parity(plain, meshed)
    for r in meshed:
        assert sum(r.stats["arm_pulls"].values()) == r.stats["model_calls"]


# ---------------------------------------------------------------------------
# the speculative commit under a mesh: in place where every device holds
# whole cache rows, the scatter where the slot or sequence dim is split
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["continuous", "static"])
@pytest.mark.parametrize("shape,in_place", [((1, 2), True),
                                            ((1, 4), False),
                                            ((2, 2), False)],
                         ids=["kv-heads", "sequence", "slots"])
def test_commit_moves_no_whole_cache(model, tables, shape, in_place, mode):
    """kv=2 heads over "model" of (1, 2) leave slot and sequence whole: the
    commit writes in place.  On (1, 4) the sequence takes "model" (kv=2
    does not divide 4; 16 + 19 + w + 2 = 40 positions do), on (2, 2) the
    slots take "data": a per-row window there would all-gather the cache,
    so the commit scatters.  Either way no collective of the commit
    carries a whole cache, and the outputs are the unsharded engine's, in
    the continuous step and in one-shot ``generate()`` alike."""
    cfg, params = model
    spec = _spec("mixed")
    mesh = make_debug_mesh(shape)
    engines = [ServingEngine(params, cfg, spec, tables=tables, max_batch=4,
                             buckets=(16,), max_new_cap=19, mesh=m)
               for m in (None, mesh)]
    prompts = [(p, 19) for p, _ in PROMPTS[:4]]
    _assert_parity(*(_serve(e, mode, prompts) for e in engines))
    R, B, S = 2, 4, 16 + 19 + spec.w + 2       # layers, slots, positions
    eng = engines[1]
    if mode == "continuous":
        hlo = eng.step_hlo()
    else:
        [((max_new, _, _), gen)] = eng._gen_cache.items()
        assert max_new == 19
        rows = lambda x: jax.device_put(x, shd.batch_sharding(mesh, x.shape))
        with act_sharding.activated(mesh):
            hlo = gen.lower(eng.params, rows(jnp.zeros((B, 16), jnp.int32)),
                            rows(jnp.zeros((B,), jnp.int32)),
                            eng.tables).compile().as_text()
    whole = re.compile(rf"=\s*\(?f32\[{R},{B},{S},.*\s(all-gather|all-to-all"
                       rf"|all-reduce|collective-permute)(-start)?\(")
    assert not [line for line in hlo.splitlines()
                if whole.search(line) and "spec.commit" in line]
    assert ("spec.commit/vmap()/scatter" in hlo) == (not in_place)


# ---------------------------------------------------------------------------
# one trace under the mesh: NamedSharding-pinned outputs keep the state's
# placement a fixed point, so step N+1 never re-lowers
# ---------------------------------------------------------------------------
def test_sharded_step_single_trace_with_donation(model, tables, mesh22,
                                                 monkeypatch):
    import warnings as W
    cfg, params = model
    cfg = dataclasses.replace(cfg, name="mesh-spy").validate()  # fresh jit
    traces = {"n": 0}
    real = spec_engine._step_body

    def spy(*a, **k):
        traces["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(spec_engine, "_step_body", spy)
    eng = _engine((cfg, params), tables, _spec("mixed"), mesh22)
    with W.catch_warnings(record=True) as caught:
        W.simplefilter("always")
        done = _serve(eng)
    assert all(r.stats["new_tokens"] > 0 for r in done)
    assert traces["n"] == 1, (
        f"sharded spec_step traced {traces['n']} times — the state's "
        f"sharding is not a fixed point of the step (donation/out_shardings "
        f"drift forces per-step recompiles)")
    # donation must survive NamedSharding: jax warns when a donated buffer
    # could not be aliased into the output (sharding mismatch = copies of
    # the whole KV cache every step)
    donation_leaks = [str(w.message) for w in caught
                      if "donated" in str(w.message).lower()]
    assert not donation_leaks, donation_leaks


# ---------------------------------------------------------------------------
# mesh-state hygiene: a meshed engine must not pin LATER engines off the
# Pallas-eligible path (the act_sharding global-leak regression)
# ---------------------------------------------------------------------------
def test_meshed_then_plain_engine_keeps_pallas_path(model, tables, mesh22,
                                                    monkeypatch):
    cfg, params = model
    _serve(_engine(model, tables, _spec("mixed"), mesh22))      # uses mesh
    assert not act_sharding.installed()
    hits = {"attn": 0}
    real_attn = ops.spec_attention_op

    def spy(*a, **k):
        hits["attn"] += 1
        return real_attn(*a, **k)

    monkeypatch.setattr(ops, "spec_attention_op", spy)
    cfg_p = dataclasses.replace(cfg, name="mesh-then-pallas",
                                backend="pallas",
                                kernel_block_s=16).validate()
    plain = _serve(_engine((cfg_p, params), tables, _spec("mixed"), None),
                   prompts=PROMPTS[:2])
    assert hits["attn"] > 0, (
        "a previously-built meshed engine left the activation sharder "
        "installed: the plain engine fell off the Pallas verify kernel")
    assert all(r.stats["new_tokens"] > 0 for r in plain)


def test_mesh_pins_xla_backend_with_warning(model, tables, mesh22):
    """The documented dispatch seam: backend='pallas' under a mesh warns
    and serves on the sharded XLA path (never reaches the kernel)."""
    cfg, params = model
    cfg_p = dataclasses.replace(cfg, name="mesh-pallas-seam",
                                backend="pallas",
                                kernel_block_s=16).validate()
    with pytest.warns(UserWarning, match="pins the Pallas kernels"):
        eng = _engine((cfg_p, params), tables, _spec("mixed"), mesh22)
    done = _serve(eng, prompts=PROMPTS[:2])
    assert all(r.stats["new_tokens"] > 0 for r in done)
    assert eng.mesh_report()["backend"] == "xla"


# ---------------------------------------------------------------------------
# the mesh_report must prove the state actually sharded
# ---------------------------------------------------------------------------
def test_mesh_report_shows_sharded_state(model, tables, mesh22):
    eng = _engine(model, tables, _spec("mixed"), mesh22)
    _serve(eng, prompts=PROMPTS[:2])
    rep = eng.mesh_report()
    assert rep["mesh"] == {"data": 2, "model": 2}
    assert rep["params_sharded"] > 0
    specs = rep["state_specs"]
    assert "'data'" in specs["buf"]                  # slots over data
    assert "'data'" in specs["model/groups/p0/k"]    # cache batch over data
    assert "'model'" in specs["model/groups/p0/k"]   # kv heads over model
    assert rep["state_sharded"] >= 3
    # vocab 61 divides nothing on a (2,2) mesh: the replication fallback
    # must be SURFACED, not silent
    assert ["vocab", 61] in rep["replication_fallbacks"]


def test_paged_pool_sharded_and_free_list_replicated(model, tables, mesh22):
    eng = _engine(model, tables, _spec("mixed"), mesh22, paged=True,
                  page_size=8)
    _serve(eng, prompts=PROMPTS[:2])
    specs = eng.mesh_report()["state_specs"]
    pool = specs["model/groups/p0/k"]
    assert "'data'" in pool or "'model'" in pool     # page axis / kv sharded
    assert specs["model/free_list"] == "(None,)"
    assert "'data'" in specs["model/page_table"]
    pool_stats = eng.pool_stats()
    assert pool_stats["free_pages"] == pool_stats["num_pages"]  # no leaks


# ---------------------------------------------------------------------------
# property: ANY debug-mesh shape whose axes divide (B, S) serves lossless
# ---------------------------------------------------------------------------
def test_any_dividing_mesh_shape_is_lossless(model, tables):
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    shapes = [s for s in [(1, 2), (2, 1), (2, 2), (4, 1), (1, 4), (4, 2),
                          (2, 4)]
              if s[0] * s[1] <= jax.device_count()]
    plain = _serve(_engine(model, tables, _spec("mixed"), None),
                   prompts=PROMPTS[:3])

    @settings(max_examples=4, deadline=None,
              suppress_health_check=[hypothesis.HealthCheck.too_slow])
    @given(shape=st.sampled_from(shapes))
    def check(shape):
        meshed = _serve(_engine(model, tables, _spec("mixed"),
                                make_debug_mesh(shape)),
                        prompts=PROMPTS[:3])
        _assert_parity(plain, meshed)

    check()
