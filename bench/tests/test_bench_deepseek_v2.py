"""The DeepSeek-V2 family (``families/deepseek_v2.py``): its plain reference
against the program at a small size in float32, the share of experts and
the sizes its cell is counted by."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tiny import BENCH, ROOT
from harness import model, spec

CELL = "deepseek-v2-lite-e16.decode-b8"


def _published():
    with open(os.path.join(BENCH, "configs",
                           "deepseek-v2-lite-e16.json")) as f:
        return json.load(f)


def _small(**over):
    """The published configuration at a CPU test's size, in float32: every
    mechanism kept (YaRN over a 64-position original context, a dense
    first layer, 8 router outputs of which 4 held, 2 shared experts)."""
    c = _published()
    c.update(hidden_size=64, intermediate_size=96, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             num_attention_heads=4, num_key_value_heads=4,
             num_hidden_layers=3, moe_intermediate_size=24,
             n_routed_experts=4, router_experts=8, experts_offset=2,
             num_experts_per_tok=3, vocab_size=300, torch_dtype="float32")
    c["rope_scaling"] = dict(c["rope_scaling"],
                             original_max_position_embeddings=64)
    c.update(over)
    return c


@pytest.fixture(scope="module")
def fam():
    return spec.family(ROOT, "deepseek_v2")


def _reference_logits(fam, c, w, toks):
    m = fam.Dims(c)
    x = fam.embed(w, toks)
    for i in range(m.layers):
        x = fam.layer(x, w, jnp.int32(i), m, False)
    return fam.head(x, w, m, False)


def test_the_program_serves_what_the_reference_computes(fam):
    """The program's full forward (absorbed MLA, dropless held experts)
    against the family reference (expanded MLA, every held expert on every
    token) on the same weights.  Tolerance 2e-5 on logits of magnitude
    about 3: float32 sums in another order (the absorbed product
    reassociates the score), far below the gaps a lower precision opens
    (the float8 control reads tenths, test_bench_control)."""
    from repro.models import model as M
    c = _small()
    m = fam.Dims(c)
    w = model.make_weights(fam, m, 5)
    cfg = fam.program_config(c, "dsv2-parity")
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 300, (2, 24)),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = M.forward(fam.program_params(m, w), cfg, tokens=toks)
        want = _reference_logits(fam, c, w, toks)
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_expert_shares_sum_to_the_uncut_layer(fam):
    """Four chips' shares of an 8-expert layer (2 experts each), with the
    shared experts counted once, add up to the uncut layer, in the
    reference."""
    c = _small(n_routed_experts=8, router_experts=8, experts_offset=0)
    m = fam.Dims(c)
    w = model.make_weights(fam, m, 7)
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 5, m.d))
    with jax.default_matmul_precision("highest"):
        whole = fam._experts(h, w, 0, m, False)
        parts = []
        for off in range(0, 8, 2):
            cs = dict(c, n_routed_experts=2, experts_offset=off)
            ws = {k: (v[:, off:off + 2] if k in ("w_gate", "w_up", "w_down")
                      else v) for k, v in w.items()}
            parts.append(fam._experts(h, ws, 0, fam.Dims(cs), False))
        g = lambda n: w[n][0]
        shared = fam._swiglu(h, g("shared_gate"), g("shared_up"),
                             g("shared_down"), False)
    np.testing.assert_allclose(np.asarray(sum(parts) - 3 * shared),
                               np.asarray(whole), atol=1e-5)


def test_yarn_is_derived_from_the_published_settings(fam):
    m = fam.Dims(_published())
    assert m.yarn_range() == (10, 23)
    # 192 ** -0.5 * (0.1 * 0.707 * ln 40 + 1) ** 2
    assert m.scale() == pytest.approx(0.114721, abs=1e-6)
    inv = m.inv_freq()
    assert inv[0] == 1.0 and inv[-1] == pytest.approx(
        10000 ** (-62 / 64) / 40)


def test_the_cell_counts_a_tokens_work_at_the_held_share(fam):
    m = fam.Dims(_published())
    # attention 27 x 13.76 M, the dense layer 67.2 M, and per expert layer
    # the router, 2 shared experts and 6 x 16 / 64 held ones
    assert m.non_embedding_params() == pytest.approx(1.2295e9, rel=1e-3)
    assert m.flops_dims()["head_dim"] == 160
    shapes = fam.weight_shapes(m)
    assert sum(int(np.prod(s)) for s in shapes.values()) == \
        pytest.approx(4.91e9, rel=1e-3)
    cell = spec.load_cell(ROOT, CELL)
    assert cell.family.__name__.endswith("deepseek_v2")
    # the one-chip per-layer metrics; MLA calls no spec_attention, and
    # no roofline of the expert layer is admitted before the program's
    # count of the experts a call reaches reaches the harness
    assert sorted(x.name for x in cell.per_layer) == sorted([
        "device_idle_share", "step_ms", "prefill_share", "tokens_per_call",
        "ngram_match_ms", "step_mfu"])
