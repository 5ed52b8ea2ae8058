"""End-to-end backend parity: pallas (interpret) == xla, bit for bit.

The dispatch layer (kernels/dispatch.py) must be invisible in the outputs:
``generate()`` and the continuous ``ServingEngine.step()`` path produce
bit-identical tokens under ``backend="xla"`` and ``backend="pallas"``
(interpret mode on CPU), and both match ``greedy_reference`` — the paper's
lossless guarantee holds under every backend.  Also proves the kernels are
actually REACHED from the production entry points (no orphaned kernels) and
that a cache length that does not divide ``kernel_block_s`` exercises the
padding path correctly.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.drafters import context_ngram_draft, match_hash_sweep
from repro.core.ngram_tables import NGramTables, build_bigram, build_unigram
from repro.core.spec_engine import (SpecConfig, generate, greedy_reference,
                                    init_decode_state, spec_step)
from repro.kernels import dispatch, ngram_match, ops
from repro.models import model as M
from repro.models.config import ModelConfig

F32 = dict(param_dtype=jnp.float32, compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def parity_model():
    """Tiny attention arch with a small kernel block so a handful of decode
    steps cross block boundaries (and interpret mode stays fast)."""
    cfg = ModelConfig(name="parity", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=2, d_ff=128, vocab_size=61,
                      backend="xla", kernel_block_s=16, **F32).validate()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def parity_tables(parity_model):
    cfg, params = parity_model
    fwd = jax.jit(lambda t: M.forward(params, cfg, tokens=t)[0][:, -1])
    topk, chain = build_bigram(fwd, cfg.vocab_size, k_max=8, w_max=8,
                               batch=cfg.vocab_size)
    uni = build_unigram(params["embed"]["embedding"],
                        params["embed"]["lm_head"], k_max=8)
    return NGramTables(uni, topk, chain)


def _pallas(cfg: ModelConfig) -> ModelConfig:
    return dataclasses.replace(cfg, backend="pallas").validate()


# ---------------------------------------------------------------------------
# dispatch unit behaviour
# ---------------------------------------------------------------------------
def test_resolve_backend():
    assert dispatch.resolve_backend("xla") == "xla"
    assert dispatch.resolve_backend("pallas") == "pallas"
    on_tpu = jax.default_backend() == "tpu"
    assert dispatch.resolve_backend("auto") == ("pallas" if on_tpu else "xla")
    assert dispatch.default_interpret() == (not on_tpu)
    with pytest.raises(ValueError):
        dispatch.resolve_backend("cuda")


def test_align_cache_len_never_repads():
    for n, bs in [(1, 16), (15, 16), (16, 16), (17, 16), (96, 32),
                  (100, 32), (513, 0), (7, 0)]:
        a = dispatch.align_cache_len(n, bs)
        eff = bs or ops.DEFAULT_BLOCK_S
        assert a >= n
        # aligned length streams in whole blocks: no per-call repad
        assert a % min(eff, a) == 0


# ---------------------------------------------------------------------------
# drafter: sweep + scoring split
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("q,w", [(1, 3), (2, 2), (3, 4)])
def test_context_drafts_identical_backends(q, w):
    rng = np.random.default_rng(q * 10 + w)
    buf = jnp.asarray(rng.integers(0, 5, (3, 48)), jnp.int32)
    cur = jnp.asarray([40, 37, q], jnp.int32)   # incl. a cur_len < q+1 row
    dx, vx = context_ngram_draft(buf, cur, q, 4, w, backend="xla")
    dp, vp = context_ngram_draft(buf, cur, q, 4, w, backend="pallas")
    np.testing.assert_array_equal(np.asarray(vx), np.asarray(vp))
    # invalid rows carry unspecified tokens; compare where valid
    np.testing.assert_array_equal(
        np.asarray(jnp.where(vx[..., None], dx, 0)),
        np.asarray(jnp.where(vp[..., None], dp, 0)))


def test_sweep_identical_backends_nonmultiple_block():
    """L that is not a whole number of kernel tiles exercises the ngram
    padding path."""
    rng = np.random.default_rng(7)
    buf = jnp.asarray(rng.integers(0, 6, (2, 1500)), jnp.int32)
    cur = jnp.asarray([1500, 733], jnp.int32)
    q, w = 2, 3
    qx, mx, hx = match_hash_sweep(buf, cur, q, w, backend="xla")
    qp, mp, hp = match_hash_sweep(buf, cur, q, w, backend="pallas")
    np.testing.assert_array_equal(np.asarray(qx), np.asarray(qp))
    np.testing.assert_array_equal(np.asarray(mx), np.asarray(mp))
    np.testing.assert_array_equal(np.asarray(hx), np.asarray(hp))
    # padding path explicitly: L=1500 pads to two 1024-position tiles, the
    # second mostly padding
    assert ngram_match.TILE == 1024
    m2, h2 = dispatch.ngram_sweep(buf, qx, cur, w=w, backend="pallas")
    np.testing.assert_array_equal(np.asarray(m2), np.asarray(mx))
    np.testing.assert_array_equal(np.asarray(h2), np.asarray(hx))


# ---------------------------------------------------------------------------
# end-to-end: generate() parity (and vs greedy_reference)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("strategy", ["context", "mixed"])
def test_generate_parity(parity_model, parity_tables, strategy):
    cfg, params = parity_model
    B, P, N = 2, 10, 16
    prompt = jax.random.randint(jax.random.PRNGKey(5), (B, P), 0,
                                cfg.vocab_size)
    ref = greedy_reference(params, cfg, prompt, N)
    spec_x = SpecConfig(k=3, w=3, q=1, strategy=strategy, max_new_tokens=N,
                        backend="xla")
    spec_p = dataclasses.replace(spec_x, backend="pallas")
    buf_x, len_x, _ = generate(params, cfg, spec_x, prompt, parity_tables)
    buf_p, len_p, _ = generate(params, _pallas(cfg), spec_p, prompt,
                               parity_tables)
    np.testing.assert_array_equal(np.asarray(len_x), np.asarray(len_p))
    # buffers may differ in length (pallas aligns the cache); tokens do not
    np.testing.assert_array_equal(np.asarray(buf_x[:, :P + N]),
                                  np.asarray(buf_p[:, :P + N]))
    np.testing.assert_array_equal(np.asarray(buf_p[:, :P + N]),
                                  np.asarray(ref))


def test_generate_parity_nonmultiple_cache(parity_model, parity_tables):
    """Cache length 41 with block_s 16: spec_attention_op pads to 48 and
    masks the phantom slots — tokens must still be bit-identical."""
    cfg, params = parity_model
    B, P, N = 2, 8, 12
    prompt = jax.random.randint(jax.random.PRNGKey(11), (B, P), 0,
                                cfg.vocab_size)
    outs = {}
    for backend in ("xla", "pallas"):
        c = dataclasses.replace(cfg, backend=backend).validate()
        spec = SpecConfig(k=3, w=3, strategy="mixed", max_new_tokens=N,
                          backend=backend)
        state = init_decode_state(params, c, spec, prompt, buf_size=41)
        for _ in range(64):
            if not bool(np.asarray(~state.done).any()):
                break
            state = spec_step(params, c, spec, state, parity_tables)
        outs[backend] = np.asarray(state.buf[:, :P + N])
        assert (np.asarray(state.buf_len) == P + N).all()
    np.testing.assert_array_equal(outs["xla"], outs["pallas"])
    ref = greedy_reference(params, cfg, prompt, N)
    np.testing.assert_array_equal(outs["pallas"], np.asarray(ref))


def test_generate_parity_hybrid_arch():
    """The kernel also runs inside the scanned heterogeneous stack (Jamba
    pattern: attention layer among recurrent mixers, gated replay commit)."""
    from repro.models.config import BlockSpec
    cfg = ModelConfig(
        name="hyb-parity", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=61,
        block_pattern=(BlockSpec("mamba", "swiglu"),
                       BlockSpec("attn", "swiglu")),
        backend="pallas", kernel_block_s=16, **F32).validate()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    B, P, N = 2, 8, 10
    prompt = jax.random.randint(jax.random.PRNGKey(5), (B, P), 0,
                                cfg.vocab_size)
    ref = greedy_reference(params, cfg, prompt, N)
    spec = SpecConfig(k=3, w=3, strategy="context", max_new_tokens=N,
                      backend="pallas")
    buf, _, _ = generate(params, cfg, spec, prompt, None)
    np.testing.assert_array_equal(np.asarray(buf[:, :P + N]),
                                  np.asarray(ref))


# ---------------------------------------------------------------------------
# kernels actually reached from the production entry points
# ---------------------------------------------------------------------------
def test_kernels_reached_from_generate(parity_model, parity_tables,
                                       monkeypatch):
    """No orphaned kernels: under backend="pallas" a fresh trace of the
    engine step must route through BOTH Pallas ops via the dispatch layer."""
    cfg, params = parity_model
    hits = {"attn": 0, "ngram": 0}
    real_attn, real_ngram = ops.spec_attention_op, ops.ngram_match_op

    def spy_attn(*a, **k):
        hits["attn"] += 1
        return real_attn(*a, **k)

    def spy_ngram(*a, **k):
        hits["ngram"] += 1
        return real_ngram(*a, **k)

    monkeypatch.setattr(ops, "spec_attention_op", spy_attn)
    monkeypatch.setattr(ops, "ngram_match_op", spy_ngram)
    cfg_p = dataclasses.replace(
        _pallas(cfg), name="parity-spy").validate()   # force a fresh trace
    spec = SpecConfig(k=3, w=3, strategy="context", max_new_tokens=6,
                      backend="pallas")
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                cfg.vocab_size)
    generate(params, cfg_p, spec, prompt, parity_tables)
    assert hits["attn"] > 0, "spec_attention_op never dispatched"
    assert hits["ngram"] > 0, "ngram_match_op never dispatched"


# ---------------------------------------------------------------------------
# continuous serving step() parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("strategy", ["context", "mixed"])
def test_continuous_step_parity(parity_model, parity_tables, strategy):
    """The ServingEngine.step() path (admit -> spec_step -> retire) returns
    identical per-request outputs under both backends."""
    from repro.serving import ServingEngine
    cfg, params = parity_model
    spec = SpecConfig(k=3, w=3, strategy=strategy, max_new_tokens=12,
                      backend="xla")
    outs = {}
    for backend in ("xla", "pallas"):
        c = dataclasses.replace(cfg, backend=backend).validate()
        s = dataclasses.replace(spec, backend=backend)
        # bucket_align=1 keeps the prompt padding identical across
        # backends (lane-aligned buckets change the padded prompt itself,
        # which is a scheduling policy, not a numerics difference)
        eng = ServingEngine(params, c, s, tables=parity_tables, max_batch=2,
                            buckets=(16,), max_new_cap=12, bucket_align=1)
        r1 = eng.submit("backend parity", max_new_tokens=12)
        r2 = eng.submit("one step behind", max_new_tokens=7)
        eng.step()
        r3 = eng.submit("late arrival", max_new_tokens=9)
        done = eng.serve_continuous()
        assert sorted(r.request_id for r in done) == \
            sorted(r.request_id for r in (r1, r2, r3))
        outs[backend] = {r.prompt: np.asarray(r.output_ids) for r in done}
    assert outs["xla"].keys() == outs["pallas"].keys()
    for prompt in outs["xla"]:
        np.testing.assert_array_equal(outs["xla"][prompt],
                                      outs["pallas"][prompt],
                                      err_msg=prompt)
