"""A configuration file, its weights, and the program's view of both.

The configuration is a JSON file with the keys of the model's published
``config.json`` (see ``bench/configs/``).  The benchmark makes the weights
itself, from the configuration's ``weights_seed``, in one jitted call on
the device and in the type they are served in; the reference reads these
arrays, never the program's.
``program_params`` only renames them into the program's parameter tree
(no copy), as a checkpoint loader would.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

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


def seed_key(seed: int):
    """A PRNG key for any whole number, beyond the 32 bits a key holds."""
    k = jax.random.PRNGKey(seed % 2**32)
    return jax.random.fold_in(k, (seed // 2**32) % 2**32)


def make_weights(c: dict, seed: int) -> Dict[str, jax.Array]:
    """Random weights of configuration ``c`` from ``seed``, made on the
    default device in one jitted call: embeddings N(0, 0.02); matrices a
    truncated normal with std 1/sqrt(fan-in); norms at scale 1, bias 0."""
    m = Dims(c)
    shapes = weight_shapes(m)

    def init(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            # drawn in the served type: no float32 copy of the model is
            # ever live, so this call does not set the process's peak
            if name == "embed":
                w = 0.02 * jax.random.normal(k, shape, m.dtype)
            elif name.endswith("_b"):
                w = jnp.zeros(shape, m.dtype)
            elif name.endswith("_w"):
                w = jnp.ones(shape, m.dtype)
            else:
                w = jax.random.truncated_normal(k, -2.0, 2.0, shape, m.dtype)
                w = w * jnp.asarray(shape[-2] ** -0.5, m.dtype)
            out[name] = w.astype(m.dtype)
        return out

    return jax.jit(init)(seed_key(seed))


def program_params(c: dict, w: Dict[str, jax.Array]) -> dict:
    """The same arrays under the program's parameter tree."""
    m = Dims(c)

    def norm(prefix):
        n = {"scale": w[prefix + "_w"]}
        if m.layernorm:
            n["bias"] = w[prefix + "_b"]
        return n

    embed = {"embedding": w["embed"]}
    if not m.tie:
        embed["lm_head"] = w["lm_head"]
    return {"embed": embed, "final_norm": norm("norm_f"),
            "p0": {"norm1": norm("ln1"), "norm2": norm("ln2"),
                   "mixer": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                             "wo": w["wo"]},
                   "mlp": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                           "w_down": w["w_down"]}}}
