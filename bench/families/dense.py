"""The dense decoder block: per layer a norm, causal attention (grouped
key/value heads, rotary embedding on the first ``rotary`` dimensions of each
head in the half-split layout, an optional sliding window), a norm and a
SwiGLU MLP, each added to the residual; RMSNorm, or LayerNorm with a bias
where the configuration states ``layer_norm_eps``.  The program serves it
as ``arch_type="dense"`` with one ``BlockSpec("attn", "swiglu")``.

A configuration names this module with ``"family": "dense"``; what a family
module defines is listed in ``harness/model.py``.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from harness.reference import act, attention, mm, norm, rope

# keys whose published value the program cannot run; a configuration file
# states them as run (and lists them in ``reduced`` where that differs)
_UNSUPPORTED = {"use_qkv_bias": True, "qk_layernorm": True,
                "use_parallel_residual": True}


class Dims:
    """The sizes a configuration file states, under short names."""

    def __init__(self, c: dict):
        for key, bad in _UNSUPPORTED.items():
            if c.get(key) == bad:
                raise ValueError(f"{key}={bad} is not something the program "
                                 f"can serve")
        if c.get("hidden_act", "silu") != "silu":
            raise ValueError(f"hidden_act {c['hidden_act']!r}: only silu "
                             f"(SwiGLU) is supported")
        self.d = int(c["hidden_size"])
        self.ff = int(c["intermediate_size"])
        self.heads = int(c["num_attention_heads"])
        self.kv = int(c.get("num_key_value_heads", self.heads))
        self.hd = int(c.get("head_dim") or self.d // self.heads)
        self.layers = int(c["num_hidden_layers"])
        self.vocab = int(c["vocab_size"])
        self.theta = float(c.get("rope_theta", 10000.0))
        rot = int(self.hd * float(c.get("partial_rotary_factor", 1.0)))
        self.rotary = rot - rot % 2
        self.layernorm = "layer_norm_eps" in c
        self.eps = float(c["layer_norm_eps"] if self.layernorm
                         else c["rms_norm_eps"])
        self.window = c.get("sliding_window")
        self.tie = bool(c.get("tie_word_embeddings", False))
        self.dtype = jnp.dtype(c.get("torch_dtype", "bfloat16"))

    def non_embedding_params(self) -> int:
        attn = self.d * self.hd * (2 * self.heads + 2 * self.kv)
        return self.layers * (attn + 3 * self.d * self.ff)

    def flops_dims(self) -> dict:
        """The sizes ``bench/roofline/step.py`` counts a token's work by."""
        return dict(non_embedding=self.non_embedding_params(),
                    d_model=self.d, vocab=self.vocab, layers=self.layers,
                    heads=self.heads, head_dim=self.hd, window=self.window)


def program_config(c: dict, name: str):
    """The program's ``ModelConfig`` for configuration file ``c``."""
    from repro.models.config import BlockSpec, ModelConfig
    m = Dims(c)
    return ModelConfig(
        name=name, arch_type="dense", source=c.get("source", ""),
        num_layers=m.layers, d_model=m.d, num_heads=m.heads,
        num_kv_heads=m.kv, head_dim=m.hd, d_ff=m.ff, vocab_size=m.vocab,
        block_pattern=(BlockSpec("attn", "swiglu"),),
        norm="layernorm" if m.layernorm else "rmsnorm", norm_eps=m.eps,
        rope="rope", rope_theta=m.theta,
        partial_rotary_factor=float(c.get("partial_rotary_factor", 1.0)),
        sliding_window=m.window, tie_embeddings=m.tie,
        param_dtype=m.dtype, compute_dtype=m.dtype).validate()


def weight_shapes(m: Dims) -> Dict[str, tuple]:
    L, d, ff = m.layers, m.d, m.ff
    s = {"embed": (m.vocab, d), "norm_f_w": (d,),
         "ln1_w": (L, d), "ln2_w": (L, d),
         "wq": (L, d, m.heads * m.hd), "wk": (L, d, m.kv * m.hd),
         "wv": (L, d, m.kv * m.hd), "wo": (L, m.heads * m.hd, d),
         "w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d)}
    if not m.tie:
        s["lm_head"] = (d, m.vocab)
    if m.layernorm:
        s.update(norm_f_b=(d,), ln1_b=(L, d), ln2_b=(L, d))
    return s


def init(name: str, key, shape: tuple, m: Dims) -> jax.Array:
    """One weight, drawn in the served type: embeddings N(0, 0.02);
    matrices a truncated normal with std 1/sqrt(fan-in); norms at scale 1,
    bias 0."""
    if name == "embed":
        w = 0.02 * jax.random.normal(key, shape, m.dtype)
    elif name.endswith("_b"):
        w = jnp.zeros(shape, m.dtype)
    elif name.endswith("_w"):
        w = jnp.ones(shape, m.dtype)
    else:
        w = jax.random.truncated_normal(key, -2.0, 2.0, shape, m.dtype)
        w = w * jnp.asarray(shape[-2] ** -0.5, m.dtype)
    return w.astype(m.dtype)


def program_params(m: Dims, w: dict) -> dict:
    """The same leaves under the program's parameter tree."""

    def nrm(prefix):
        n = {"scale": w[prefix + "_w"]}
        if m.layernorm:
            n["bias"] = w[prefix + "_b"]
        return n

    embed = {"embedding": w["embed"]}
    if not m.tie:
        embed["lm_head"] = w["lm_head"]
    return {"embed": embed, "final_norm": nrm("norm_f"),
            "p0": {"norm1": nrm("ln1"), "norm2": nrm("ln2"),
                   "mixer": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                             "wo": w["wo"]},
                   "mlp": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                           "w_down": w["w_down"]}}}


def embed(w, tokens):
    return w["embed"][tokens].astype(jnp.float32)


def layer(x, w, i, m: Dims, quant: bool):
    """Decoder layer ``i`` of the stacked weights ``w``, in float32 (in
    float8 for the control: ``reference.act``)."""
    B, T, _ = x.shape
    g = lambda n: w[n][i]
    b = (lambda n: g(n)) if m.layernorm else (lambda n: None)
    r = lambda t: act(t, quant)
    x = r(x)
    h = norm(x, g("ln1_w"), b("ln1_b"), m.eps)
    q = r(rope(mm(h, g("wq"), quant).reshape(B, T, m.heads, m.hd), m.rotary,
               m.theta))
    k = r(rope(mm(h, g("wk"), quant).reshape(B, T, m.kv, m.hd), m.rotary,
               m.theta))
    v = r(mm(h, g("wv"), quant).reshape(B, T, m.kv, m.hd))
    x = r(x + mm(attention(q, k, v, m.window), g("wo"), quant))
    h = norm(x, g("ln2_w"), b("ln2_b"), m.eps)
    f = jax.nn.silu(mm(h, g("w_gate"), quant)) * mm(h, g("w_up"), quant)
    return r(x + mm(f, g("w_down"), quant))


def head(h, w, m: Dims, quant: bool):
    """Final norm and output head of hidden states ``h``."""
    h = norm(h, w["norm_f_w"], w.get("norm_f_b"), m.eps)
    return mm(h, w["embed"].T if m.tie else w["lm_head"], quant)
