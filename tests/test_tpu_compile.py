"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler that ships with JAX compiles each kernel for
a chip that is described, not attached, and raises what the chip's own
compiler (Mosaic) would raise — unaligned loads, memory spaces a kernel may
not read, more VMEM than a kernel may use.  Interpret mode, which the rest
of the suite uses, checks none of that.

Shapes are StableLM-2-1.6B's at serving widths (bf16, 32 heads, 32 KV
heads, head_dim 64; 8 slots of 2048 positions; cache block 512).  The
speculative commit is compiled at the served shapes of the benchmark's
decode-b4 cell (4 slots of 1024 positions), alone and inside the step.
The held experts' grouped matmul is compiled at DeepSeek-V2-Lite's widths
(16 experts of 2048 x 1408), alone and inside that model's served step.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import spec_engine as E
from repro.core import tree as T
from repro.core.ngram_tables import abstract_tables
from repro.kernels import dispatch, ops
from repro.kernels.expert_gmm import expert_gmm_call
from repro.kernels.ngram_match import LANE, TILE, ngram_match_call
from repro.kernels.spec_attention import (paged_spec_attention_call,
                                          spec_attention_call)
from repro.models import model as M
from repro.models.config import BlockSpec, ModelConfig

B, H, KV, HD, S, BLOCK_S = 8, 32, 32, 64, 2048, 512
DT = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    """AOT-compile ``fn`` for the described chip; return the HLO text."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


@pytest.mark.parametrize("k,w,dt", [(10, 10, DT), (4, 4, DT),
                                    (10, 10, jnp.float32)])
def test_spec_attention_compiles(one_chip, k, w, dt):
    """bf16 serving widths, and the float32 cache of the exactness runs."""
    s = _sds(one_chip)
    kw1 = k * (w + 1)
    fn = lambda q, kc, vc, kt, vt, cl: spec_attention_call(
        q, kc, vc, kt, vt, cl, w1=w + 1, block_s=BLOCK_S)
    hlo = _compile(fn, s((B, H, kw1, HD), dt), s((B, KV, S, HD), dt),
                   s((B, KV, S, HD), dt), s((B, KV, kw1, HD), dt),
                   s((B, KV, kw1, HD), dt), s((B,), jnp.int32))
    assert dispatch.kernels_in_hlo(hlo) == ("spec_attention",)


def test_spec_attention_tree_mask_compiles(one_chip):
    """The 81-node draft tree: one row, ancestor mask as a static operand."""
    s = _sds(one_chip)
    topo = T.topology(16, 5, 1)
    n = len(topo.pos_off)
    assert n == 81
    mask = np.asarray(topo.anc_mask, bool)
    fn = lambda q, kc, vc, kt, vt, cl: spec_attention_call(
        q, kc, vc, kt, vt, cl, w1=n, block_s=BLOCK_S, tail_mask=mask)
    hlo = _compile(fn, s((B, H, n, HD), DT), s((B, KV, S, HD), DT),
                   s((B, KV, S, HD), DT), s((B, KV, n, HD), DT),
                   s((B, KV, n, HD), DT), s((B,), jnp.int32))
    assert dispatch.kernels_in_hlo(hlo) == ("spec_attention",)


def test_paged_spec_attention_compiles(one_chip):
    s = _sds(one_chip)
    k, w = 10, 10
    kw1 = k * (w + 1)
    pps = S // BLOCK_S
    fn = lambda q, kp, vp, pt, kt, vt, cl: paged_spec_attention_call(
        q, kp, vp, pt, kt, vt, cl, w1=w + 1)
    hlo = _compile(fn, s((B, H, kw1, HD), DT),
                   s((B * pps, KV, BLOCK_S, HD), DT),
                   s((B * pps, KV, BLOCK_S, HD), DT),
                   s((B, pps), jnp.int32), s((B, KV, kw1, HD), DT),
                   s((B, KV, kw1, HD), DT), s((B,), jnp.int32))
    assert dispatch.kernels_in_hlo(hlo) == ("paged_spec_attention",)


@pytest.mark.parametrize("q,w,L", [(1, 10, S), (1, 4, 640), (2, 16, 1500)])
def test_ngram_match_op_compiles(one_chip, q, w, L):
    """The engine-facing wrapper: padding, (rows, 128) view, kernel."""
    s = _sds(one_chip)
    fn = lambda buf, qry, cl: ops.ngram_match_op(buf, qry, cl, w=w,
                                                 interpret=False)
    hlo = _compile(fn, s((B, L), jnp.int32), s((B, q), jnp.int32),
                   s((B,), jnp.int32))
    assert dispatch.kernels_in_hlo(hlo) == ("ngram_match",)


def test_ngram_match_call_compiles(one_chip):
    """The bare kernel over two tiles of 1024 positions plus the halo."""
    s = _sds(one_chip)
    rows = 3 * TILE // LANE
    fn = lambda buf, qry, cl: ngram_match_call(buf, qry, cl, w=10)
    hlo = _compile(fn, s((B, rows, LANE), jnp.int32), s((B, 1), jnp.int32),
                   s((B,), jnp.int32))
    assert dispatch.kernels_in_hlo(hlo) == ("ngram_match",)


# ---------------------------------------------------------------------------
# the speculative commit: the KV cache is updated in place
# ---------------------------------------------------------------------------
def _stablelm(layers):
    """StableLM-2-1.6B's widths (no q/k/v bias), ``layers`` deep."""
    return ModelConfig(name=f"stablelm-2-1.6b-l{layers}", num_layers=layers,
                       d_model=2048, num_heads=32, num_kv_heads=32,
                       head_dim=64, d_ff=5632, vocab_size=100352,
                       block_pattern=(BlockSpec("attn", "swiglu"),),
                       norm="layernorm", partial_rotary_factor=0.25,
                       backend="pallas", param_dtype=DT,
                       compute_dtype=DT).validate()


def _on_chip(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _cache_copies(hlo, shape):
    """Copies (sync or async) whose result has the cache leaf's shape."""
    pat = re.compile(r"=\s*\(?" + re.escape(shape)
                     + r"\{.*\s(copy|copy-start)\(")
    return [line.strip()[:120] for line in hlo.splitlines()
            if pat.search(line)]


def _assert_cache_aliased(hlo, out_tree, arg):
    """Every K/V leaf of the output aliases the donated input it came from
    (the entry parameter named ``arg`` + the leaf's path)."""
    alias = {int(o): int(p) for o, p in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}, may-alias\)", hlo.splitlines()[0])}
    params = {}
    for line in hlo.splitlines():
        m = re.search(r'parameter\((\d+)\).*op_name="([^"]*)"',
                      line.replace("\\'", "'"))
        if m:
            params[m.group(2)] = int(m.group(1))
    flat = jax.tree_util.tree_flatten_with_path(out_tree)[0]
    leaves = [(i, arg + jax.tree_util.keystr(path))
              for i, (path, _) in enumerate(flat)
              if jax.tree_util.keystr(path).endswith(("['k']", "['v']"))]
    assert leaves
    for i, name in leaves:
        assert alias.get(i) == params[name], (name, i, alias.get(i))


def test_commit_updates_the_cache_in_place(one_chip):
    """decode-b4's commit alone (24 layers, 4 slots of 1024, k=10, w=10):
    no whole-cache copy, and K and V alias the donated state."""
    R, B, S, K, W1 = 24, 4, 1024, 10, 11
    cfg = _stablelm(R)
    state = _on_chip(jax.eval_shape(lambda: M.init_state(cfg, B, S)),
                     one_chip)
    tails = {g: {f"{kk}_tail": jax.ShapeDtypeStruct(
                 c.shape[:2] + (K, W1) + c.shape[3:], c.dtype,
                 sharding=one_chip) for kk, c in d.items()}
             for g, d in state["groups"].items()}
    rows = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    fn = jax.jit(lambda st, t, w, n: M.commit_kv_tails(cfg, st, t, w, n),
                 donate_argnums=0)
    hlo = fn.lower(state, tails, rows, rows).compile().as_text()
    assert _cache_copies(hlo, f"bf16[{R},{B},{S},32,64]") == []
    _assert_cache_aliased(hlo, state, "st")


def test_spec_step_updates_the_cache_in_place(one_chip, monkeypatch):
    """The whole served step, both kernels compiled for the chip, at
    StableLM widths cut to 2 layers (the smallest cache, where XLA is the
    most ready to relayout it): no whole-cache copy, K and V aliased."""
    monkeypatch.setattr(dispatch, "default_interpret", lambda: False)
    R, B, S = 2, 4, 1024
    cfg = _stablelm(R)
    spec = E.SpecConfig(backend="pallas")
    params = _on_chip(jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg)), one_chip)
    state = _on_chip(jax.eval_shape(
        lambda: E.empty_decode_state(cfg, spec, B, S)), one_chip)
    tables = _on_chip(abstract_tables(cfg.vocab_size, spec.k, spec.w),
                      one_chip)
    hlo = E.spec_step.lower(params, cfg, spec, state,
                            tables).compile().as_text()
    assert dispatch.kernels_in_hlo(hlo) == ("ngram_match", "spec_attention")
    assert _cache_copies(hlo, f"bf16[{R},{B},{S},32,64]") == []
    _assert_cache_aliased(hlo, state, "state")


# ---------------------------------------------------------------------------
# the held experts' grouped matmul, and the MLA + expert step around it
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)])
def test_expert_gmm_compiles(one_chip, k, n):
    """Gate/up (2048 -> 1408) and down (1408 -> 2048) over the rows of a
    verify call of 8 slots x 110 positions, top-6 of 64, 16 experts held:
    the dropless bound of 57 tiles of 128 rows; the weights of all 26
    expert layers, read by layer index."""
    s = _sds(one_chip)
    tiles = (880 * 6 + 16 * 127) // 128
    fn = lambda x, w, r, g, live: expert_gmm_call(x, w, r, g, live, tm=128)
    hlo = _compile(fn, s((tiles * 128, k), DT), s((26, 16, k, n), DT),
                   s((), jnp.int32), s((tiles,), jnp.int32),
                   s((), jnp.int32))
    assert dispatch.kernels_in_hlo(hlo) == ("expert_gmm",)


def test_mla_expert_step_updates_the_latent_cache_in_place(one_chip,
                                                           monkeypatch):
    """DeepSeek-V2-Lite's served step at published widths, cut to its
    dense layer and two expert layers (one layer alone fits the chip's
    fast memory, and XLA prefetches it there whole), 8 slots of 524
    positions: the grouped matmul and the drafter kernel compiled for the
    chip, no copy of the latent cache, and the cache aliased to the
    donated state."""
    import dataclasses
    from repro.configs import get_config
    monkeypatch.setattr(dispatch, "default_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("deepseek-v2-lite"), num_layers=3,
                              experts_held=16, backend="pallas",
                              param_dtype=DT, compute_dtype=DT).validate()
    spec = E.SpecConfig(backend="pallas")
    params = _on_chip(jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg)), one_chip)
    state = _on_chip(jax.eval_shape(
        lambda: E.empty_decode_state(cfg, spec, 8, 524)), one_chip)
    tables = _on_chip(abstract_tables(cfg.vocab_size, spec.k, spec.w),
                      one_chip)
    hlo = E.spec_step.lower(params, cfg, spec, state,
                            tables).compile().as_text()
    assert dispatch.kernels_in_hlo(hlo) == ("expert_gmm", "ngram_match")
    assert _cache_copies(hlo, "bf16[2,8,524,576]") == []
    # the kernel reads the stacked experts in place: no layer of them is
    # sliced out (copied) before a call
    assert not re.search(r"= bf16\[16,(2048,1408|1408,2048)\]", hlo)
    alias = {int(o): int(p) for o, p in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}, may-alias\)", hlo.splitlines()[0])}
    flat = jax.tree_util.tree_flatten_with_path(state)[0]
    latent = [i for i, (path, _) in enumerate(flat)
              if jax.tree_util.keystr(path).endswith("['latent']")]
    assert len(latent) == 2 and all(i in alias for i in latent)
