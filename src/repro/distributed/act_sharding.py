"""Activation-sharding constraints (Megatron-SP style), installable hook.

Model code is mesh-agnostic; the launcher installs a sharder before lowering
and the transformer calls ``constrain(x, kind)`` at the few points GSPMD
propagation needs help:

  - "residual": the (B, T, d) stream carried between blocks (and the remat
    checkpoint!): batch over ("pod","data"), sequence over "model"
    (sequence-parallelism — the all-gather to full T happens inside each
    block's first matmul, its reduce-scatter at the block output; XLA inserts
    these automatically from the constraint).
  - "logits": (B, Tc, V) loss chunks: vocab over "model".

Without this, Nemotron-340B train activations lower replicated over the
model axis: 864 GiB/device temp (measured) vs ~56 GiB/device after
(EXPERIMENTS.md §Perf it-1).
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .sharding import cache_pspec, resolve_axis

_MESH: Optional[Mesh] = None


def _batch(mesh: Mesh, b: int):
    """Activation batch dims replicate legitimately when odd (a 3-row
    partial batch is routine, not a mis-sized mesh): resolve quietly,
    never through the ShardingFallbackWarning path."""
    return resolve_axis(mesh, "embed", b, warn=False)


def install(mesh: Optional[Mesh]) -> None:
    """Set the process-global activation sharder.  Prefer ``activated`` —
    a bare install leaks the mesh across engines/tests, and an installed
    mesh pins ``attn_verify`` off the Pallas kernel path
    (models/attention.py:_use_verify_kernel)."""
    global _MESH
    _MESH = mesh


def uninstall() -> None:
    install(None)


def installed() -> bool:
    return _MESH is not None


@contextlib.contextmanager
def activated(mesh: Optional[Mesh]) -> Iterator[None]:
    """Scoped install: the sharder is active inside the block and the
    PREVIOUS value is restored on exit (exception-safe), so one engine's
    mesh can never leak into another engine's traces.  ``constrain`` only
    matters at trace time, so owners (ServingEngine, the dry-run) wrap
    every call that may trace in this context instead of installing
    globally.  ``activated(None)`` is a no-op scope."""
    global _MESH
    prev = _MESH
    _MESH = mesh
    try:
        yield
    finally:
        _MESH = prev


def splits_cache_rows(shape) -> bool:
    """Whether the active mesh splits a linear cache (R, B, S, KV, hd), or
    a latent one (R, B, S, L), over its slot or sequence dim, by the
    state's own rule (``sharding.cache_pspec``).  Where it does, a write at a per-row
    dynamic offset makes GSPMD all-gather the cache, so the speculative
    commit scatters instead (``model.commit_kv_tails``)."""
    if _MESH is None:
        return False
    spec = tuple(cache_pspec(_MESH, tuple(shape)))
    return any(_MESH.shape[a] > 1 for ax in spec[1:3] if ax is not None
               for a in ((ax,) if isinstance(ax, str) else ax))


def constrain(x, kind: str):
    if _MESH is None:
        return x
    mesh = _MESH
    if kind == "residual" and x.ndim == 3:
        B, T, _ = x.shape
        # sequence parallelism is opportunistic (decode-time T = w+1 is
        # tiny and legitimately replicated): no fallback warning here
        spec = P(_batch(mesh, B),
                 resolve_axis(mesh, "heads", T, warn=False), None)
    elif kind == "logits" and x.ndim == 3:
        B, T, V = x.shape
        spec = P(_batch(mesh, B), None,
                 resolve_axis(mesh, "vocab", V))
    elif kind == "ctx_logits" and x.ndim == 6:
        # decode/verify context logits (B, K, n_kv, G, w1, S): keep them in
        # the CACHE's sharding (kv heads over "model" when divisible, else
        # cache sequence over "model") so the big KV cache is never
        # all-gathered — the tiny q block is re-sharded instead, and the
        # softmax/value contraction pay only small partial-reduce
        # collectives (flash-decode sequence parallelism, §Perf it-7).
        B, K, n_kv, G, w1, S = x.shape
        n_ax = resolve_axis(mesh, "kv", n_kv, warn=False)  # seq fallback next
        s_ax = None
        if n_ax is None and S % mesh.shape.get("model", 1) == 0:
            s_ax = "model"
        spec = P(_batch(mesh, B), None, n_ax, None, None,
                 s_ax)
    elif kind == "ctx_out" and x.ndim == 6:
        # (B, K, w1, n_kv, G, hd) value-contraction output: batch-only so
        # the s-sharded contraction resolves as partial-sum + small
        # all-reduce instead of all-gathering the V cache.
        spec = P(_batch(mesh, x.shape[0]), None, None, None,
                 None, None)
    elif kind == "hidden_ffn" and x.ndim >= 2:
        spec = P(*([_batch(mesh, x.shape[0])]
                   + [None] * (x.ndim - 2)
                   + [resolve_axis(mesh, "ffn", x.shape[-1])]))
    else:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
