"""Model substrate: prefill/decode/verify/commit consistency across families."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import act_sharding
from repro.models import model as M
from repro.models.config import BlockSpec, ModelConfig

pytestmark = pytest.mark.slow  # model-level suite; excluded from -m 'not slow' fast lane

F32 = dict(param_dtype=jnp.float32, compute_dtype=jnp.float32, vocab_size=61)

FAMILIES = {
    "dense-gqa": ModelConfig(name="d", num_layers=2, d_model=64, num_heads=4,
                             num_kv_heads=2, d_ff=128, **F32),
    "mqa-geglu": ModelConfig(name="m", num_layers=2, d_model=64, num_heads=4,
                             num_kv_heads=1, d_ff=128, tie_embeddings=True,
                             scale_embed=True,
                             block_pattern=(BlockSpec("attn", "geglu"),),
                             **F32),
    "partial-rope-ln": ModelConfig(name="p", num_layers=2, d_model=64,
                                   num_heads=4, num_kv_heads=4, d_ff=128,
                                   norm="layernorm",
                                   partial_rotary_factor=0.5,
                                   block_pattern=(BlockSpec("attn", "relu2"),),
                                   **F32),
    "mrope": ModelConfig(name="q", num_layers=2, d_model=64, num_heads=4,
                         num_kv_heads=2, d_ff=128, rope="mrope",
                         mrope_sections=(4, 2, 2), **F32),
    "swa": ModelConfig(name="s", num_layers=2, d_model=64, num_heads=4,
                       num_kv_heads=2, d_ff=128, sliding_window=8, **F32),
    "mamba": ModelConfig(name="mb", num_layers=2, d_model=64, num_heads=4,
                         num_kv_heads=4, d_ff=128, rope="none",
                         block_pattern=(BlockSpec("mamba", "swiglu"),), **F32),
    "hybrid-moe": ModelConfig(
        name="h", num_layers=4, d_model=64, num_heads=4, num_kv_heads=2,
        d_ff=128, num_experts=4, num_experts_per_tok=2,
        block_pattern=(BlockSpec("mamba", "swiglu"), BlockSpec("mamba", "moe"),
                       BlockSpec("attn", "swiglu"), BlockSpec("mamba", "moe")),
        **F32),
    "xlstm": ModelConfig(name="x", num_layers=2, d_model=64, num_heads=4,
                         num_kv_heads=4, d_ff=0, rope="none",
                         block_pattern=(BlockSpec("mlstm", "none"),
                                        BlockSpec("slstm", "none")), **F32),
    "deepseek": ModelConfig(name="ds", num_layers=3, d_model=64, num_heads=4,
                            num_kv_heads=4, d_ff=128, moe_d_ff=32,
                            num_experts=4, num_experts_per_tok=2,
                            num_shared_experts=1,
                            prefix_blocks=(BlockSpec("attn", "swiglu"),),
                            block_pattern=(BlockSpec("attn", "moe"),), **F32),
}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_prefill_decode_verify_commit(family):
    cfg = FAMILIES[family].validate()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    B, T = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                              cfg.vocab_size)
    full, _ = M.forward(params, cfg, tokens=toks)
    assert bool(jnp.isfinite(full).all())

    state = M.init_state(cfg, B, 48)
    _, state = M.prefill(params, cfg, state, tokens=toks[:, :12])
    ld, state = M.decode(params, cfg, state, toks[:, 12:])
    np.testing.assert_allclose(np.asarray(ld), np.asarray(full[:, 12:]),
                               rtol=5e-4, atol=5e-4)

    k, w1 = 3, 4
    vt = jnp.broadcast_to(toks[:, 12:12 + w1][:, None], (B, k, w1))
    st2 = M.init_state(cfg, B, 48)
    _, st2 = M.prefill(params, cfg, st2, tokens=toks[:, :12])
    vl, tails = M.verify(params, cfg, st2, vt)
    np.testing.assert_allclose(np.asarray(vl[:, 0]),
                               np.asarray(full[:, 12:12 + w1]),
                               rtol=5e-4, atol=5e-4)

    # partial replay commit then continue
    ncommit = jnp.full((B,), 2, jnp.int32)
    _, st2 = M.decode(params, cfg, st2, vt[:, 0], n_commit=ncommit)
    assert int(st2["cur_len"][0]) == 14
    ld3, _ = M.decode(params, cfg, st2, toks[:, 14:15])
    np.testing.assert_allclose(np.asarray(ld3), np.asarray(full[:, 14:15]),
                               rtol=5e-4, atol=5e-4)


def test_commit_kv_tails_matches_replay(tiny_dense):
    cfg, params = tiny_dense
    B, T, k, w1 = 2, 12, 3, 4
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, T + w1 + 1), 0,
                              cfg.vocab_size)
    vt = jnp.broadcast_to(toks[:, T:T + w1][:, None], (B, k, w1))
    sA = M.init_state(cfg, B, 48)
    _, sA = M.prefill(params, cfg, sA, tokens=toks[:, :T])
    _, tails = M.verify(params, cfg, sA, vt)
    n = jnp.full((B,), 3, jnp.int32)
    sA = M.commit_kv_tails(cfg, sA, tails, jnp.zeros((B,), jnp.int32), n)
    sB = M.init_state(cfg, B, 48)
    _, sB = M.prefill(params, cfg, sB, tokens=toks[:, :T])
    _, sB = M.decode(params, cfg, sB, vt[:, 0], n_commit=n)
    nxt = toks[:, T + 3:T + 4]
    lA, _ = M.decode(params, cfg, sA, nxt)
    lB, _ = M.decode(params, cfg, sB, nxt)
    np.testing.assert_allclose(np.asarray(lA), np.asarray(lB),
                               rtol=1e-5, atol=1e-5)


# (buffer length, cur_len, n_commit, winner) per row; W1 = 4.  A buffer of
# whole 128-position tiles (384) commits through 256-position windows, one
# of 40 through 16-position windows aligned to 8, one of 16 through its
# whole row
COMMIT_CASES = {
    "n0": (16, [0, 3, 7, 12], [0, 0, 0, 0], [0, 1, 2, 0]),
    "n1": (16, [0, 3, 7, 12], [1, 1, 1, 1], [0, 1, 2, 0]),
    "nW1": (16, [0, 3, 7, 12], [4, 4, 4, 4], [0, 1, 2, 0]),
    "mixed-winners": (16, [2, 5, 9, 1], [4, 2, 3, 1], [2, 0, 1, 2]),
    "b1": (16, [5], [3], [1]),
    "past-end": (16, [13, 12, 15, 14], [4, 4, 4, 2], [0, 1, 2, 1]),
    "inactive": (16, [3, 10, 0, 6], [3, 0, 0, 4], [1, 2, 0, 0]),
    "tiles": (384, [126, 255, 0, 300], [4, 3, 4, 2], [2, 0, 1, 2]),
    "tiles-past-end": (384, [381, 380, 384, 130], [4, 4, 0, 0],
                       [1, 2, 0, 1]),
    "small-tiles": (40, [37, 30, 8, 0], [4, 4, 3, 0], [0, 1, 2, 1]),
    "ring": (16, [6, 10, 3, 13], [4, 3, 4, 1], [1, 0, 2, 2]),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", list(COMMIT_CASES))
def test_commit_kv_tails_matches_scatter(case, dtype, monkeypatch):
    """The commit equals the gated scatter bit for bit: positions cur ..
    cur + n_commit - 1 of each row take the winner's tail, positions past
    the buffer are dropped (linear) or wrap (ring, 8 slots), everything
    else is left as it was.  Linear caches write in place (no scatter in
    the program), or scatter where a mesh splits their rows
    (``act_sharding.splits_cache_rows``); ring caches always scatter."""
    S, *rows = COMMIT_CASES[case]
    cur, n, win = (jnp.asarray(x, jnp.int32) for x in rows)
    ring = case == "ring"
    B, K, W1 = cur.shape[0], 3, 4
    cfg = ModelConfig(name="commit", num_layers=3, d_model=32, num_heads=4,
                      num_kv_heads=2, d_ff=64, vocab_size=61,
                      param_dtype=dtype, compute_dtype=dtype,
                      sliding_window=8 if ring else None,
                      prefix_blocks=(BlockSpec("attn", "swiglu"),)
                      ).validate()
    state = M.init_state(cfg, B, S)
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 16))
    rand = lambda shape: jax.random.normal(next(keys), shape).astype(dtype)
    state["groups"] = {g: {kk: rand(c.shape) for kk, c in d.items()}
                       for g, d in state["groups"].items()}
    state["cur_len"] = cur
    tails = {g: {f"{kk}_tail": rand(c.shape[:2] + (K, W1) + c.shape[3:])
                 for kk, c in d.items()}
             for g, d in state["groups"].items()}
    want = {}
    for g, d in state["groups"].items():
        for kk, c in d.items():
            c = np.array(c)
            t = np.asarray(tails[g][f"{kk}_tail"])
            S = c.shape[2]
            for b in range(B):
                for j in range(int(n[b])):
                    p = int(cur[b]) + j
                    if ring:
                        p %= S
                    elif p >= S:
                        continue
                    c[:, b, p] = t[:, b, int(win[b]), j]
            want[g, kk] = c

    for in_place in ((True,) if ring else (True, False)):
        monkeypatch.setattr(act_sharding, "splits_cache_rows",
                            lambda shape, split=not in_place: split)
        fn = jax.jit(lambda st, t, w, nc: M.commit_kv_tails(
            cfg, st, t, w, nc))
        got = fn(state, tails, win, n)
        np.testing.assert_array_equal(np.asarray(got["cur_len"]),
                                      np.asarray(cur + n))
        for (g, kk), c in want.items():
            assert np.array_equal(np.asarray(got["groups"][g][kk]), c), \
                (case, in_place, g, kk)
        jaxpr = str(jax.make_jaxpr(fn)(state, tails, win, n))
        assert ("scatter" in jaxpr) == (ring or not in_place), in_place


def test_encoder_only_forward():
    cfg = ModelConfig(name="enc", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=4, d_ff=128, causal=False,
                      encoder_only=True, embedding_inputs=True, rope="none",
                      block_pattern=(BlockSpec("attn", "gelu"),),
                      param_dtype=jnp.float32, compute_dtype=jnp.float32,
                      vocab_size=32).validate()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, 64))
    logits, _ = M.forward(params, cfg, embeds=x)
    assert logits.shape == (2, 10, 32)
    assert bool(jnp.isfinite(logits).all())
    # bidirectional: flipping the sequence flips the outputs
    logits2, _ = M.forward(params, cfg, embeds=x[:, ::-1])
    np.testing.assert_allclose(np.asarray(logits2[:, ::-1]),
                               np.asarray(logits), rtol=2e-3, atol=2e-3)


def test_blockwise_attention_matches_dense():
    """Flash-style blockwise path == exact softmax attention."""
    import repro.models.attention as A
    from repro.models.config import ModelConfig
    cfg = ModelConfig(name="bw", num_layers=1, d_model=32, num_heads=4,
                      num_kv_heads=2, d_ff=64, vocab_size=11,
                      param_dtype=jnp.float32, compute_dtype=jnp.float32,
                      sliding_window=24).validate()
    B, T, H, hd, KV = 2, 32, 4, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, T, KV, hd))
    v = jax.random.normal(ks[2], (B, T, KV, hd))
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    kpos = jnp.where(pos < 30, pos, -1)     # padding mask exercised
    dense = A.masked_attention(q, k, v, pos, kpos, cfg, causal=True)
    bw = A._blockwise_attention(q, k, v, pos, kpos, cfg, causal=True,
                                block=8)
    np.testing.assert_allclose(np.asarray(bw), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)
