"""The plain reference: the configuration's forward pass in float32.

Straight ``jax.numpy`` at ``Precision.HIGHEST`` on every product, with no
cache, no kernel and no batching trick: the embedding, the configuration's
layers one by one, and the output head, each as its family module
(``bench/families/<family>.py``) writes them with the building blocks
below.  It imports nothing of the program and reads only the benchmark's
own weights (``model.make_weights``), sharded or not: it runs layer
by layer, a few rows at a time, so that it fits beside the weights, and no
chip holds a float32 copy of more than about one layer.

``quant=True`` is the control: the same forward computed in float8 (e4m3)
wherever the program computes in the bfloat16 the configurations state --
both operands of every weight product (one scale per row of activations
and per output column of weights), and the activations the program holds
in that type between them, the residual stream and the attention's
queries, keys and values (one scale per row; ``act``).
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with an absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def act(x, quant: bool):
    """An activation as the control holds it: rounded to float8 along its
    last axis; unchanged in the reference."""
    return _fp8(x, -1) if quant else x


def mm(x, w, quant: bool):
    """x (..., k) @ w (k, n) in float32; float8 operands for the control."""
    w = w.astype(jnp.float32)
    if quant:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def norm(x, w, b, eps: float):
    """LayerNorm with bias ``b``, or RMSNorm where ``b`` is None."""
    if b is not None:
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        y = (x - mu) / jnp.sqrt(var + eps)
        return y * w.astype(jnp.float32) + b.astype(jnp.float32)
    y = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)
    return y * w.astype(jnp.float32)


def rope(x, rotary: int, theta: float):
    """x (B, T, N, hd): rotate the first ``rotary`` dims, half-split."""
    r = rotary
    if r == 0:
        return x
    T = x.shape[1]
    inv = 1.0 / (theta ** (np.arange(0, r, 2, dtype=np.float64) / r))
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def attention(q, k, v, window: Optional[int]):
    """Causal attention of q (B, T, heads, hd) over k, v (B, T, kv, hd),
    each key/value head shared by heads // kv query heads, within
    ``window`` positions where one is given; returns (B, T, heads * hd)."""
    B, T, heads, hd = q.shape
    kv = k.shape[2]
    q = q.reshape(B, T, kv, heads // kv, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, k, precision=HI) / hd ** 0.5
    qi = np.arange(T)[:, None]
    ki = np.arange(T)[None, :]
    keep = ki <= qi
    if window is not None:
        keep = keep & (ki > qi - int(window))
    s = jnp.where(jnp.asarray(keep), s, -jnp.inf)
    return jnp.einsum("bkgqs,bskh->bqkgh", jax.nn.softmax(s, -1), v,
                      precision=HI).reshape(B, T, heads * hd)


@functools.lru_cache(maxsize=None)
def _fns(family, c_json: str, quant: bool):
    m = family.Dims(json.loads(c_json))
    layer = jax.jit(lambda x, w, i: family.layer(x, w, i, m, quant))
    embed = jax.jit(family.embed)

    def head(x, w, P, n_max):
        # positions P-1 .. P+n_max-2 of each row choose its served tokens
        idx = P[:, None] - 1 + jnp.arange(n_max)[None, :]
        idx = jnp.clip(idx, 0, x.shape[1] - 1)
        h = jnp.take_along_axis(x, idx[..., None], 1)
        return family.head(h, w, m, quant)

    return layer, embed, jax.jit(head, static_argnums=3)


def logits(family, c: dict, w, rows: np.ndarray, P: np.ndarray, n_max: int,
           quant: bool = False) -> jax.Array:
    """(B, n_max, V) float32 logits at positions P-1 .. P+n_max-2 of each
    row of ``rows`` (B, T) -- the positions that choose the tokens served
    after a prompt of P tokens.  ``family``: the configuration ``c``'s
    family module."""
    layer, embed, head = _fns(family, json.dumps(c, sort_keys=True), quant)
    x = embed(w, jnp.asarray(rows))
    for i in range(family.Dims(c).layers):
        x = layer(x, w, jnp.int32(i))
    return head(x, w, jnp.asarray(P, jnp.int32), n_max)


@jax.jit
def _gaps(ref, served, n, ctrl):
    """Per position: how far the served token's reference logit lies below
    the reference's best, and the same for the token the control puts
    first.  Positions at or past ``n`` read 0."""
    best = ref.max(-1)
    got = jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
    pick = jnp.argmax(ctrl, -1)
    alt = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
    live = jnp.arange(ref.shape[1])[None, :] < n[:, None]
    return (jnp.where(live, best - got, 0.0),
            jnp.where(live, best - alt, 0.0))


def row_chunk(m, T: int, n_max: int, budget: float = 2e9) -> int:
    """Rows per reference call that keep its largest temporaries (the
    attention scores of one layer, three sets of logits) under budget."""
    per_row = 4.0 * (m.heads * T * T + 3 * n_max * m.vocab + 8 * T * m.d)
    return max(1, int(budget // per_row))


def served_gaps(family, c: dict, w, rows: List[np.ndarray],
                served: List[np.ndarray], T: int, n_max: int,
                control: bool = False) -> Dict:
    """The reference's reading of served tokens.

    ``rows[i]`` is the token row the program prefilled for request i (its
    prompt as bucketed), ``served[i]`` the tokens it served after it.
    Returns the widest gap over every served token (``served_gap``) and,
    with ``control``, the widest gap of the tokens the float8 control puts
    first at the same positions (``control_gap``)."""
    step = row_chunk(family.Dims(c), T, n_max)
    # pad to whole chunks (rows with nothing served) so that every call
    # has one shape and compiles once
    B = -(-len(rows) // step) * step
    toks = np.zeros((B, T), np.int32)
    P = np.ones(B, np.int32)
    S = np.zeros((B, n_max), np.int32)
    n = np.zeros(B, np.int32)
    for i, (r, s) in enumerate(zip(rows, served)):
        P[i], n[i] = len(r), len(s)
        toks[i, :len(r)] = r
        toks[i, len(r):len(r) + len(s)] = s
        S[i, :len(s)] = s
    served_gap, control_gap = [], []
    for lo in range(0, B, step):
        sl = slice(lo, lo + step)
        ref = logits(family, c, w, toks[sl], P[sl], n_max)
        ctl = logits(family, c, w, toks[sl], P[sl], n_max, quant=True) \
            if control else ref
        g, a = _gaps(ref, jnp.asarray(S[sl]), jnp.asarray(n[sl]), ctl)
        served_gap.append(np.asarray(g))
        control_gap.append(np.asarray(a))
        del ref, ctl
    served_gap = np.concatenate(served_gap)
    out = {"served_gap": float(served_gap.max()),
           "served_tokens": int(n.sum()),
           "served_argmax": int(((served_gap == 0) &
                                 (np.arange(n_max)[None] < n[:, None])).sum())}
    if control:
        out["control_gap"] = float(np.concatenate(control_gap).max())
    return out
