"""The DeepSeek-V2 decoder block (arXiv:2405.04434; ``modeling_deepseek.py``
of hf:deepseek-ai/DeepSeek-V2-Lite), with the share of the routed experts
that one chip of an expert-parallel deployment holds.  The program serves it
as ``arch_type="moe"``: a dense ``BlockSpec("attn", "swiglu")`` first and
then ``BlockSpec("attn", "moe")`` layers, with latent attention
(``models/mla.py``) and the dropless held-expert layer (``models/moe.py``).

Per layer, every RMSNorm at ``rms_norm_eps``:

    x += MLA(RMSNorm(x));  x += MLP(RMSNorm(x))

MLA without a query latent (``q_lora_rank`` null), in the expanded form the
published code computes (the program attends in the absorbed form):

    q = x W_q                       heads of qk_nope + qk_rope
    [c_kv, k_pe] = x W_kva;  c_kv = RMSNorm(c_kv) (kv_a_layernorm)
    [k_nope, v]_h = c_kv W_kvb      per head
    score_h = [q_nope, rope(q_pe)]_h . [k_nope_h, rope(k_pe)] * scale
    y = (softmax(score) v) W_o

k_pe is one rotary head shared by every head.  RoPE is YaRN's: the inverse
frequencies over the rotary dims blend the plain and the ``factor``-
interpolated ones across the correction range that ``beta_fast`` and
``beta_slow`` give (``yarn_range``); scale = (qk_nope + qk_rope) ** -0.5 *
mscale(factor, mscale_all_dim) ** 2, and cos and sin are not scaled since
``mscale`` equals ``mscale_all_dim``.

MLP: a dense SwiGLU in the first ``first_k_dense_replace`` layers, else the
expert layer: router logits in float32, softmax over all
``router_experts`` experts, greedy top-``num_experts_per_tok``, weights
not renormalised (``norm_topk_prob`` false, ``routed_scaling_factor`` 1);
output the sum over the chosen experts this chip holds of weight times
that expert's SwiGLU, plus the shared SwiGLU of ``n_shared_experts`` times
the expert width.  The final RMSNorm feeds an untied head.

Departures:
  - experts: the configuration's ``n_routed_experts`` are the ones held
    (from ``experts_offset`` on); what the others would add is left out,
    here as in the program.
  - RoPE in the half-split layout.  The checkpoint stores q_pe and k_pe
    interleaved and permutes them to this layout before rotating; on
    random weights that is the same permutation of the rotary columns of
    W_q and W_kva on both sides, so the model is the same.
  - the reference computes every held expert on every token and keeps the
    chosen ones (plain, and exactly the routed sum).

A configuration names this module with ``"family": "deepseek_v2"``; what a
family module defines is listed in ``harness/model.py``.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from harness.reference import HI, act, mm, norm

# keys the program cannot serve other than as published here
_REQUIRED = {"q_lora_rank": None, "attention_bias": False,
             "hidden_act": "silu", "scoring_func": "softmax",
             "topk_method": "greedy", "n_group": 1, "topk_group": 1,
             "routed_scaling_factor": 1, "moe_layer_freq": 1}


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


class Dims:
    """The sizes a configuration file states, under short names."""

    def __init__(self, c: dict):
        for key, want in _REQUIRED.items():
            if c.get(key, want) != want:
                raise ValueError(f"{key}={c[key]!r}: only {want!r} can be "
                                 f"served")
        self.d = int(c["hidden_size"])
        self.heads = int(c["num_attention_heads"])
        self.kv = self.heads
        self.rank = int(c["kv_lora_rank"])
        self.nope = int(c["qk_nope_head_dim"])
        self.rot = int(c["qk_rope_head_dim"])
        self.vd = int(c["v_head_dim"])
        self.hd = self.nope + self.rot
        self.layers = int(c["num_hidden_layers"])
        self.dense_layers = int(c["first_k_dense_replace"])
        self.moe_layers = self.layers - self.dense_layers
        self.ff = int(c["intermediate_size"])
        self.expert_ff = int(c["moe_intermediate_size"])
        self.experts = int(c["n_routed_experts"])           # held here
        self.router_experts = int(c["router_experts"])
        self.offset = int(c.get("experts_offset", 0))
        self.topk = int(c["num_experts_per_tok"])
        self.shared = int(c["n_shared_experts"])
        self.norm_topk = bool(c["norm_topk_prob"])
        self.vocab = int(c["vocab_size"])
        self.eps = float(c["rms_norm_eps"])
        self.theta = float(c["rope_theta"])
        self.yarn = c.get("rope_scaling")
        if self.yarn is not None and self.yarn["type"] != "yarn":
            raise ValueError(f"rope_scaling {self.yarn['type']!r}")
        self.window = None
        self.tie = bool(c.get("tie_word_embeddings", False))
        if self.tie:
            raise ValueError("tied embeddings: the published model is untied")
        self.dtype = jnp.dtype(c.get("torch_dtype", "bfloat16"))

    # ---- rotary ----
    def yarn_range(self):
        y, dim = self.yarn, self.rot

        def corr(rot):
            return (dim * math.log(y["original_max_position_embeddings"]
                                   / (rot * 2 * math.pi))
                    / (2 * math.log(self.theta)))

        return (max(math.floor(corr(y["beta_fast"])), 0),
                min(math.ceil(corr(y["beta_slow"])), dim - 1))

    def inv_freq(self) -> np.ndarray:
        f = 1.0 / self.theta ** (np.arange(0, self.rot, 2, dtype=np.float64)
                                 / self.rot)
        if self.yarn is None:
            return f
        lo, hi = self.yarn_range()
        ramp = np.clip((np.arange(self.rot // 2) - lo) / max(hi - lo, 1e-3),
                       0, 1)
        keep = 1.0 - ramp
        return f / self.yarn["factor"] * (1 - keep) + f * keep

    def scale(self) -> float:
        s = self.hd ** -0.5
        if self.yarn is not None and self.yarn.get("mscale_all_dim"):
            if self.yarn["mscale"] != self.yarn["mscale_all_dim"]:
                raise ValueError("YaRN mscale != mscale_all_dim")
            s *= _mscale(self.yarn["factor"], self.yarn["mscale_all_dim"]) ** 2
        return s

    # ---- work ----
    def non_embedding_params(self) -> int:
        """Weights a token's forward multiplies by, with the routed experts
        at their expected count: of ``topk`` chosen among
        ``router_experts``, ``topk * experts / router_experts`` are held
        here on average (the count varies per token)."""
        d, H = self.d, self.heads
        attn = (d * H * self.hd + d * (self.rank + self.rot)
                + self.rank * H * (self.nope + self.vd) + H * self.vd * d)
        expert = 3 * d * self.expert_ff
        moe = (d * self.router_experts + self.shared * expert
               + self.topk * self.experts / self.router_experts * expert)
        return int(self.layers * attn + self.dense_layers * 3 * d * self.ff
                   + self.moe_layers * moe)

    def flops_dims(self) -> dict:
        """The sizes ``bench/roofline/step.py`` counts a token's work by.
        ``head_dim`` 160: a key costs 2 (qk_nope + qk_rope) + 2 v_head
        flops a head, the expanded form's work, which 4 * 160 gives."""
        return dict(non_embedding=self.non_embedding_params(),
                    d_model=self.d, vocab=self.vocab, layers=self.layers,
                    heads=self.heads, head_dim=(self.hd + self.vd) // 2,
                    window=None)


def program_config(c: dict, name: str):
    """The program's ``ModelConfig`` for configuration file ``c``."""
    from repro.models.config import BlockSpec, ModelConfig, YaRN
    m = Dims(c)
    y = m.yarn
    return ModelConfig(
        name=name, arch_type="moe", source=c.get("source", ""),
        num_layers=m.layers, d_model=m.d, num_heads=m.heads,
        num_kv_heads=m.heads, d_ff=m.ff, moe_d_ff=m.expert_ff,
        vocab_size=m.vocab,
        prefix_blocks=(BlockSpec("attn", "swiglu"),) * m.dense_layers,
        block_pattern=(BlockSpec("attn", "moe"),),
        kv_lora_rank=m.rank, qk_nope_head_dim=m.nope,
        qk_rope_head_dim=m.rot, v_head_dim=m.vd,
        yarn=None if y is None else YaRN(
            factor=float(y["factor"]),
            original_max_position=int(y["original_max_position_embeddings"]),
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"])),
        num_experts=m.router_experts, experts_held=m.experts,
        experts_offset=m.offset, num_experts_per_tok=m.topk,
        num_shared_experts=m.shared, norm_topk_prob=m.norm_topk,
        moe_impl="gmm", norm="rmsnorm", norm_eps=m.eps, rope="rope",
        rope_theta=m.theta, tie_embeddings=False,
        param_dtype=m.dtype, compute_dtype=m.dtype).validate()


# per-layer weights, by the layers that have them: "d_*" for the dense
# first layers, the rest for the expert layers
_ATTN = ("ln1_w", "ln2_w", "wq", "wkv_a", "kv_norm_w", "wkv_b", "wo")


def _attn_shapes(m: Dims, n: int, p: str) -> Dict[str, tuple]:
    d, H = m.d, m.heads
    return {p + "ln1_w": (n, d), p + "ln2_w": (n, d),
            p + "wq": (n, d, H * m.hd), p + "wkv_a": (n, d, m.rank + m.rot),
            p + "kv_norm_w": (n, m.rank),
            p + "wkv_b": (n, m.rank, H * (m.nope + m.vd)),
            p + "wo": (n, H * m.vd, d)}


def weight_shapes(m: Dims) -> Dict[str, tuple]:
    d, L, E, f = m.d, m.moe_layers, m.experts, m.expert_ff
    s = {"embed": (m.vocab, d), "lm_head": (d, m.vocab), "norm_f_w": (d,)}
    s.update(_attn_shapes(m, m.dense_layers, "d_"))
    s.update({"d_w_gate": (m.dense_layers, d, m.ff),
              "d_w_up": (m.dense_layers, d, m.ff),
              "d_w_down": (m.dense_layers, m.ff, d)})
    s.update(_attn_shapes(m, L, ""))
    s.update({"router": (L, d, m.router_experts),
              "w_gate": (L, E, d, f), "w_up": (L, E, d, f),
              "w_down": (L, E, f, d),
              "shared_gate": (L, d, m.shared * f),
              "shared_up": (L, d, m.shared * f),
              "shared_down": (L, m.shared * f, d)})
    return s


def init(name: str, key, shape: tuple, m: Dims) -> jax.Array:
    """One weight, drawn in the served type: embeddings N(0, 0.02);
    matrices a truncated normal with std 1/sqrt(fan-in); norms at scale
    1."""
    if name == "embed":
        w = 0.02 * jax.random.normal(key, shape, m.dtype)
    elif name.endswith("_w"):
        w = jnp.ones(shape, m.dtype)
    else:
        w = jax.random.truncated_normal(key, -2.0, 2.0, shape, m.dtype)
        w = w * jnp.asarray(shape[-2] ** -0.5, m.dtype)
    return w.astype(m.dtype)


def program_params(m: Dims, w: dict) -> dict:
    """The same leaves under the program's parameter tree."""

    def block(p, mlp):
        return {"norm1": {"scale": w[p + "ln1_w"]},
                "norm2": {"scale": w[p + "ln2_w"]},
                "mixer": {"wq": w[p + "wq"], "wkv_a": w[p + "wkv_a"],
                          "kv_norm": w[p + "kv_norm_w"],
                          "wkv_b": w[p + "wkv_b"], "wo": w[p + "wo"]},
                "mlp": mlp}

    out = {"embed": {"embedding": w["embed"], "lm_head": w["lm_head"]},
           "final_norm": {"scale": w["norm_f_w"]},
           "p0": block("", {k: w[k] for k in (
               "router", "w_gate", "w_up", "w_down", "shared_gate",
               "shared_up", "shared_down")})}
    for i in range(m.dense_layers):
        # one prefix group per dense layer, each stacked over one layer
        take = (lambda k: w[k]) if m.dense_layers == 1 else \
            (lambda k: w[k][i:i + 1])
        out[f"pre{i}"] = {
            "norm1": {"scale": take("d_ln1_w")},
            "norm2": {"scale": take("d_ln2_w")},
            "mixer": {"wq": take("d_wq"), "wkv_a": take("d_wkv_a"),
                      "kv_norm": take("d_kv_norm_w"),
                      "wkv_b": take("d_wkv_b"), "wo": take("d_wo")},
            "mlp": {"w_gate": take("d_w_gate"), "w_up": take("d_w_up"),
                    "w_down": take("d_w_down")}}
    return out


def embed(w, tokens):
    return w["embed"][tokens].astype(jnp.float32)


def _rope(x, m: Dims):
    """x (B, T, N, rot): rotate half-split at positions 0 .. T-1."""
    T, h = x.shape[1], m.rot // 2
    ang = np.arange(T, dtype=np.float64)[:, None] * m.inv_freq()[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(x, g, m: Dims, quant: bool):
    """Causal MLA of x (B, T, d) with this layer's weights ``g(name)``."""
    B, T, _ = x.shape
    H, r = m.heads, lambda t: act(t, quant)
    q = mm(x, g("wq"), quant).reshape(B, T, H, m.hd)
    kva = mm(x, g("wkv_a"), quant)
    c = r(norm(kva[..., :m.rank], g("kv_norm_w"), None, m.eps))
    k_pe = r(_rope(kva[..., None, m.rank:], m))                  # one head
    kv = mm(c, g("wkv_b"), quant).reshape(B, T, H, m.nope + m.vd)
    q = r(jnp.concatenate([q[..., :m.nope], _rope(q[..., m.nope:], m)], -1))
    k = r(jnp.concatenate([kv[..., :m.nope],
                           jnp.broadcast_to(k_pe, (B, T, H, m.rot))], -1))
    v = r(kv[..., m.nope:])
    s = jnp.einsum("bqhd,bshd->bhqs", q, k, precision=HI) * m.scale()
    keep = np.tril(np.ones((T, T), bool))
    s = jnp.where(jnp.asarray(keep), s, -jnp.inf)
    o = jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(s, -1), v,
                   precision=HI).reshape(B, T, H * m.vd)
    return mm(o, g("wo"), quant)


def _swiglu(x, wg, wu, wd, quant):
    return mm(jax.nn.silu(mm(x, wg, quant)) * mm(x, wu, quant), wd, quant)


def _experts(h, w, i, m: Dims, quant: bool):
    """Expert layer i (of the expert layers): the held experts' part of
    the routed sum, and the shared experts."""
    g = lambda n: w[n][i]
    logits = jnp.einsum("...d,de->...e", act(h, quant),
                        g("router").astype(jnp.float32), precision=HI)
    wt, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), m.topk)
    if m.norm_topk:
        wt = wt / wt.sum(-1, keepdims=True)
    y = _swiglu(h, g("shared_gate"), g("shared_up"), g("shared_down"), quant)
    for e in range(m.experts):
        coef = jnp.where(idx == m.offset + e, wt, 0.0).sum(-1, keepdims=True)
        y = y + coef * _swiglu(h, g("w_gate")[e], g("w_up")[e],
                               g("w_down")[e], quant)
    return y


def layer(x, w, i, m: Dims, quant: bool):
    """Decoder layer ``i`` of the weights ``w``, in float32 (in float8 for
    the control: ``reference.act``).  ``i`` may be traced: the dense and
    the expert layers are the two branches of a ``lax.cond``."""
    r = lambda t: act(t, quant)

    def dense(x):
        j = jnp.minimum(i, m.dense_layers - 1)
        g = lambda n: w["d_" + n][j]
        x = r(x + _mla(norm(x, g("ln1_w"), None, m.eps), g, m, quant))
        h = norm(x, g("ln2_w"), None, m.eps)
        return r(x + _swiglu(h, g("w_gate"), g("w_up"), g("w_down"), quant))

    def expert(x):
        j = jnp.maximum(i - m.dense_layers, 0)
        g = lambda n: w[n][j]
        x = r(x + _mla(norm(x, g("ln1_w"), None, m.eps), g, m, quant))
        h = norm(x, g("ln2_w"), None, m.eps)
        return r(x + _experts(h, w, j, m, quant))

    return jax.lax.cond(i < m.dense_layers, dense, expert, r(x))


def head(h, w, m: Dims, quant: bool):
    """Final norm and output head of hidden states ``h``."""
    return mm(norm(h, w["norm_f_w"], None, m.eps), w["lm_head"], quant)
