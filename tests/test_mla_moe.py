"""Latent attention (models/mla.py) and the dropless held-expert layer
(models/moe.py, kernels/expert_gmm.py) at CPU sizes, in float32.

Tolerances: logits agree to 1e-4 where two computations of the same
float32 model sum in different orders (a cache against a full forward, a
batch against a row alone); that is a hundred times the noise measured
(about 1e-6) and far below any logit gap a dropped or misrouted expert
opens (tenths).  Kernel against XLA: 1e-5 on float32 products of
magnitude about 1.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import spec_engine as E
from repro.core.ngram_tables import NGramTables, build_bigram, build_unigram
from repro.kernels import dispatch
from repro.models import mla
from repro.models import model as M
from repro.models import moe as moe_lib
from repro.models.config import BlockSpec, ModelConfig, YaRN

F32 = dict(param_dtype=jnp.float32, compute_dtype=jnp.float32)
TOL = 1e-4


def _cfg(name, **kw):
    base = dict(
        name=name, arch_type="moe", num_layers=3, d_model=48, num_heads=4,
        num_kv_heads=4, d_ff=96, moe_d_ff=24, vocab_size=97,
        prefix_blocks=(BlockSpec("attn", "swiglu"),),
        block_pattern=(BlockSpec("attn", "moe"),),
        kv_lora_rank=24, qk_nope_head_dim=12, qk_rope_head_dim=8,
        v_head_dim=12,
        yarn=YaRN(factor=40.0, original_max_position=32, mscale=0.707,
                  mscale_all_dim=0.707),
        num_experts=8, experts_held=4, experts_offset=2,
        num_experts_per_tok=3, num_shared_experts=1, norm_topk_prob=False,
        moe_impl="gmm", norm_eps=1e-6, **F32)
    base.update(kw)
    return ModelConfig(**base).validate()


@pytest.fixture(scope="module")
def tiny():
    cfg = _cfg("mla-moe-tiny")
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg)


def _tables(params, cfg):
    fwd = jax.jit(lambda t: M.forward(params, cfg, tokens=t)[0][:, -1])
    topk, chain = build_bigram(fwd, cfg.vocab_size, k_max=8, w_max=8,
                               batch=cfg.vocab_size)
    uni = build_unigram(params["embed"]["embedding"],
                        params["embed"]["lm_head"], k_max=8)
    return NGramTables(uni, topk, chain)


def test_yarn_range_and_scale_follow_the_formula():
    cfg = get_config("deepseek-v2-lite")
    assert mla.yarn_range(cfg) == (10, 23)
    assert mla.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2)
    f = mla.inv_freq(cfg)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(f[:10], plain[:10], rtol=1e-6)   # kept
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-6)
    assert cfg.latent_dim == 576
    assert cfg.param_count() == pytest.approx(15.7e9, rel=1e-2)


def test_cache_paths_equal_the_full_forward(tiny):
    """Prefill, a batched verify, the commit of a winner's tail and a
    plain decode step through the latent cache each give the full
    forward's logits at the same positions."""
    cfg, params = tiny
    B, P, K, W1 = 2, 9, 3, 4
    rng = np.random.RandomState(1)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, P)), jnp.int32)
    rows = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, K, W1)),
                       jnp.int32)
    state = M.init_state(cfg, B, 32)
    assert state["groups"]["p0"]["latent"].shape == (2, B, 32, 32)
    lp, state = M.prefill(params, cfg, state, tokens=prompt)
    full, _ = M.forward(params, cfg, tokens=prompt)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(full), atol=TOL)
    vl, tails = M.verify(params, cfg, state, rows)
    tails, routed = M.split_expert_rows(tails)
    assert routed.shape == (2, B, cfg.held_experts)
    for k in range(K):
        seq = jnp.concatenate([prompt, rows[:, k]], 1)
        f, _ = M.forward(params, cfg, tokens=seq)
        np.testing.assert_allclose(np.asarray(vl[:, k]),
                                   np.asarray(f[:, P:]), atol=TOL)
    winner = jnp.asarray([2, 0], jnp.int32)
    n = jnp.asarray([3, 1], jnp.int32)
    state = M.commit_kv_tails(cfg, state, tails, winner, n)
    nxt = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, 1)), jnp.int32)
    dl, state = M.decode(params, cfg, state, nxt)
    for b in range(B):
        acc = rows[b, winner[b], :int(n[b])]
        seq = jnp.concatenate([prompt[b], acc, nxt[b]])[None]
        f, _ = M.forward(params, cfg, tokens=seq)
        np.testing.assert_allclose(np.asarray(dl[b, -1]),
                                   np.asarray(f[0, -1]), atol=TOL)
    assert np.asarray(state["cur_len"]).tolist() == [P + 4, P + 2]


def _moe_cfg(**kw):
    return _cfg("moe-layer", **kw)


def _apply(params, x, cfg):
    y, _, _ = moe_lib.apply_moe(params, x, cfg)
    return y


def test_expert_shares_sum_to_the_uncut_layer():
    """Four chips' shares (2 of 8 experts each), with the shared expert
    that every chip computes alike counted once, add up to the layer that
    holds all 8."""
    whole = _moe_cfg(experts_held=0, experts_offset=0)
    p = moe_lib.init_moe(jax.random.PRNGKey(3), whole)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 7, whole.d_model))
    want = _apply(p, x, whole)
    parts = []
    for off in range(0, 8, 2):
        cfg = dataclasses.replace(whole, experts_held=2, experts_offset=off)
        ps = {k: (v[off:off + 2] if k in ("w_gate", "w_up", "w_down")
                  else v) for k, v in p.items()}
        parts.append(_apply(ps, x, cfg))
    shared = (jax.nn.silu(x @ p["shared_gate"]) * (x @ p["shared_up"])
              ) @ p["shared_down"]
    np.testing.assert_allclose(np.asarray(sum(parts) - 3 * shared),
                               np.asarray(want), atol=1e-5)
    # and the uncut gmm layer is the dense oracle's
    dense = dataclasses.replace(whole, moe_impl="dense")
    np.testing.assert_allclose(np.asarray(want),
                               np.asarray(_apply(p, x, dense)), atol=1e-5)


def test_dropless_when_every_row_picks_the_same_experts():
    """Every row routed to experts 0 and 1: the capacity path drops rows
    past twice the mean, the grouped matmul computes them all and equals
    the dense oracle."""
    cfg = _moe_cfg(experts_held=0, experts_offset=0, num_experts_per_tok=2,
                   num_shared_experts=0, norm_topk_prob=True)
    p = moe_lib.init_moe(jax.random.PRNGKey(5), cfg)
    d = cfg.d_model
    p["router"] = jnp.zeros((d, 8)).at[:, 0].set(1.0).at[:, 1].set(0.5)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (4, 50, d))) + 0.1
    want = _apply(p, x, dataclasses.replace(cfg, moe_impl="dense"))
    got = _apply(p, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    drop = _apply(p, x, dataclasses.replace(cfg, moe_impl="scatter"))
    assert float(jnp.abs(drop - want).max()) > 0.1


def test_a_verified_row_does_not_depend_on_its_batch(tiny):
    """Three slots verified together give each slot the logits it gets
    verified alone: routing is per row and no row is ever dropped."""
    cfg, params = tiny
    B, P, K, W1 = 3, 8, 4, 3
    rng = np.random.RandomState(2)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, P)), jnp.int32)
    rows = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, K, W1)),
                       jnp.int32)
    st = M.init_state(cfg, B, 16)
    _, st = M.prefill(params, cfg, st, tokens=prompt)
    batch, _ = M.verify(params, cfg, st, rows)
    for b in range(B):
        one = M.init_state(cfg, 1, 16)
        _, one = M.prefill(params, cfg, one, tokens=prompt[b:b + 1])
        alone, _ = M.verify(params, cfg, one, rows[b:b + 1])
        np.testing.assert_allclose(np.asarray(batch[b]),
                                   np.asarray(alone[0]), atol=TOL)


@pytest.mark.parametrize("shape", [(8, 3, 4), (64, 6, 16)])
def test_expert_gmm_kernel_equals_the_xla_path(shape):
    """The Pallas kernel (interpret mode) against ``lax.ragged_dot`` over
    the same layout, on the rows a random routing sends to the held
    experts of the middle one of three stacked layers; one case fills more
    than one tile per expert."""
    N, K, E = shape
    d, f = 64, 48
    rng = np.random.RandomState(N)
    idx = jnp.asarray(np.stack([rng.choice(E + 4, K, replace=False)
                                for _ in range(N)]), jnp.int32)
    local, held = moe_lib._held(idx, _moe_cfg(num_experts=E + 4,
                                              experts_held=E,
                                              experts_offset=0))
    dest, src, group, live = moe_lib.expert_layout(local, held, E,
                                                   dispatch.EXPERT_TILE)
    x = jax.random.normal(jax.random.PRNGKey(0), (N, d))[src]
    stacked = jax.random.normal(jax.random.PRNGKey(1), (3, E, d, f))
    w = stacked[1]
    a = dispatch.expert_matmul(x, stacked, 1, group, live, backend="pallas")
    b = dispatch.expert_matmul(x, stacked, 1, group, live, backend="xla")
    rows = np.asarray(dest)[np.asarray(held)]
    assert rows.size == int(np.asarray(held).sum()) and rows.max() < \
        int(live) * dispatch.EXPERT_TILE
    np.testing.assert_allclose(np.asarray(a)[rows], np.asarray(b)[rows],
                               atol=1e-5)
    # each pair's row holds its token times its expert's matrix
    tok, kk = np.nonzero(np.asarray(held))
    e = np.asarray(local)[tok, kk]
    want = np.einsum("nd,ndf->nf", np.asarray(x)[rows], np.asarray(w)[e])
    np.testing.assert_allclose(np.asarray(a)[rows], want, atol=1e-4)


def test_the_layer_serves_through_the_kernel(tiny):
    cfg, params = tiny
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 6, cfg.d_model))
    p = jax.tree_util.tree_map(lambda a: a[0], params["p0"]["mlp"])
    got = _apply(p, x, dataclasses.replace(cfg, backend="pallas"))
    want = _apply(p, x, dataclasses.replace(cfg, backend="xla"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_rows_past_the_live_tiles_are_never_read(backend, monkeypatch):
    """Every row picks experts 0, 1 and 2 of 8, and only 2 is held here
    (offset 2): one live tile of four, and two of each row's pairs have no
    row.  The kernel leaves the dead tiles unwritten, so they are filled
    with NaN here; the layer's output stays finite and equal."""
    cfg = _moe_cfg(backend=backend)
    p = moe_lib.init_moe(jax.random.PRNGKey(8), cfg)
    d = cfg.d_model
    p["router"] = jnp.zeros((d, 8)).at[0, :3].set([3.0, 2.0, 1.0])
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 3, d))
    x = x.at[..., 0].set(5.0)
    idx, _, _ = moe_lib._router(p, x.reshape(6, d), cfg)
    local, held = moe_lib._held(idx, cfg)
    _, _, group, live = moe_lib.expert_layout(local, held, 4,
                                              dispatch.EXPERT_TILE)
    assert int(held.sum()) == 6 and int(live) == 1 < group.shape[0]
    want = _apply(p, x, cfg)
    real = dispatch.expert_matmul

    def poisoned(lhs, rhs, layer, tile_group, live, **kw):
        out = real(lhs, rhs, layer, tile_group, live, **kw)
        dead = jnp.arange(out.shape[0]) >= live * dispatch.EXPERT_TILE
        return jnp.where(dead[:, None], jnp.nan, out)

    monkeypatch.setattr(dispatch, "expert_matmul", poisoned)
    got = _apply(p, x, cfg)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("norm", [True, False])
def test_router_top_k_normalisation_switch(norm):
    """A hand-computed 4-expert row: logits 2, 1, 0, -1; top-2 keeps
    experts 0 and 1 with their softmax weights, renormalised to sum to 1
    only where ``norm_topk_prob`` (Mixtral, Jamba, DeepSeek-MoE-16B; not
    DeepSeek-V2)."""
    cfg = ModelConfig(name="r", num_experts=4, num_experts_per_tok=2,
                      norm_topk_prob=norm, **F32)
    x = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    idx, w, _ = moe_lib._router({"router": jnp.eye(4)}, x, cfg)
    e = np.exp([2.0, 1.0, 0.0, -1.0])
    p = e / e.sum()
    want = p[:2] / p[:2].sum() if norm else p[:2]
    assert np.asarray(idx).tolist() == [[0, 1]]
    np.testing.assert_allclose(np.asarray(w[0]), want, rtol=1e-6)
    assert ModelConfig().norm_topk_prob          # the default keeps today's
    assert get_config("deepseek-moe-16b").norm_topk_prob
    assert not get_config("deepseek-v2-lite").norm_topk_prob


def test_expert_row_counter_equals_a_host_recount(tiny, monkeypatch):
    """The per-slot counter of one spec step against the routing each
    expert layer of its verify call chose, recounted on the host."""
    cfg, params = tiny
    cfg = dataclasses.replace(cfg, name="mla-moe-counter")
    seen = []
    router = moe_lib._router

    def recording(p, x2d, c):
        idx, w, aux = router(p, x2d, c)
        jax.debug.callback(lambda i: seen.append(np.asarray(i)), idx)
        return idx, w, aux

    monkeypatch.setattr(moe_lib, "_router", recording)
    spec = E.SpecConfig(k=3, w=2, strategy="context", max_new_tokens=6)
    B, P = 2, 7
    prompt = jnp.asarray(np.random.RandomState(3).randint(
        0, cfg.vocab_size, (B, P)), jnp.int32)
    state = E.init_decode_state(params, cfg, spec, prompt, buf_size=24)
    seen.clear()
    state = E.spec_step(params, cfg, spec, state)
    jax.block_until_ready(state)
    assert len(seen) == 2                   # the two expert layers
    total = np.zeros(B, int)
    top = np.zeros(B, int)
    lo, hi = cfg.experts_offset, cfg.experts_offset + cfg.held_experts
    for idx in seen:                          # (B * k * (w+1), top-k)
        per = idx.reshape(B, -1)
        counts = np.stack([((per == e).sum(1)) for e in range(lo, hi)], 1)
        total += counts.sum(1)
        top = np.maximum(top, counts.max(1))
    assert np.asarray(state.stats["expert_rows"]).tolist() == total.tolist()
    assert np.asarray(state.stats["expert_rows_max"]).tolist() == \
        top.tolist()
    assert total.min() > 0


@pytest.mark.parametrize("tree", [False, True])
def test_speculation_equals_greedy_decoding(tiny, tree):
    """Linear and tree speculation on latent attention and held experts
    reproduce plain greedy decoding token for token."""
    cfg, params = tiny
    tables = _tables(params, cfg)
    B, P, N = 2, 8, 16
    prompt = jax.random.randint(jax.random.PRNGKey(5), (B, P), 0,
                                cfg.vocab_size)
    ref = E.greedy_reference(params, cfg, prompt, N)
    spec = E.SpecConfig(k=3, w=3, strategy="mixed", max_new_tokens=N,
                        tree=tree)
    buf, _, stats = E.generate(params, cfg, spec, prompt, tables)
    np.testing.assert_array_equal(np.asarray(buf[:, :P + N]),
                                  np.asarray(ref))
    assert (np.asarray(stats["expert_rows"]) > 0).all()


def test_the_engine_serves_and_reports_expert_rows():
    from repro.serving import ServingEngine
    cfg = _cfg("mla-moe-engine", vocab_size=300)
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    eng = ServingEngine(params, cfg, E.SpecConfig(k=3, w=3), max_batch=2,
                        buckets=(16,), max_new_cap=8, sampling=False)
    for p in ("abc abc", "xyzxyz x", "abcabc"):
        eng.submit(p, max_new_tokens=8)
    done = eng.serve_continuous()
    assert len(done) == 3
    for r in done:
        assert r.stats["new_tokens"] == 8
        assert r.stats["expert_rows"] >= r.stats["expert_rows_max"] > 0
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(params, cfg, E.SpecConfig(k=3, w=3), max_batch=2,
                      buckets=(16,), max_new_cap=8, paged=True)
