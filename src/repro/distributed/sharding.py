"""Logical-axis sharding rules with divisibility fallbacks.

Scheme (DESIGN.md §6): 2D ("data", "model") per pod, + leading "pod" axis
multi-pod.
  - "embed"-like param dims  -> FSDP over ("pod","data")  (what lets
    Nemotron-340B / Jamba-398B fit v5e HBM),
  - "heads"/"ffn"/"kv"/"vocab"/"expert" dims -> tensor/expert parallel over
    "model",
  - activation batch         -> ("pod", "data"),
  - KV-cache: kv-heads over "model" when divisible, else head_dim;
    batch over ("pod","data") when divisible, else cache sequence over
    "data" (the batch=1 long-context case).

Every rule degrades to replication when the dim isn't divisible by the mesh
axis — a sharding that fails to lower is a bug, a replicated small tensor is
not.
"""
from __future__ import annotations

import contextlib
import warnings
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> preferred mesh axes, in fallback order
_LOGICAL = {
    "embed": (("pod", "data"), ("data",)),
    "heads": (("model",),),
    "kv": (("model",),),
    "ffn": (("model",),),
    "vocab": (("model",),),
    "expert": (("model",),),
    None: (),
}


class ShardingFallbackWarning(UserWarning):
    """A logical axis degraded to replication because no mesh-axis chain
    divides the dim.  Correct but memory-costly: a mis-sized mesh serves
    the full replicated tensor on every device."""


# once-per-(logical, dim, mesh-shape) so traces don't spam; tests reset it
_FALLBACK_WARNED: set = set()
# scoped recorders (recording_fallbacks): every dead-end fallback is added
# to each active recorder, independent of the once-only warning dedup — so
# a caller (ServingEngine.mesh_report) can attribute fallbacks to ITS OWN
# spec resolution instead of reading the process-global history
_RECORDERS: List[Set[Tuple[str, int]]] = []


def _axis_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    return int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64))


def reset_fallback_warnings() -> None:
    _FALLBACK_WARNED.clear()


def fallback_report() -> List[Tuple[str, int]]:
    """(logical, dim) pairs that degraded to replication so far in this
    PROCESS (all meshes, all callers), sorted.  For a single engine's view
    use ``recording_fallbacks`` around its own spec resolution."""
    return sorted({(lg, d) for lg, d, _ in _FALLBACK_WARNED})


@contextlib.contextmanager
def recording_fallbacks():
    """Collect every replication dead-end hit while the context is active
    — repeats included (the once-only warning dedup does not apply), so
    re-resolving a spec tree always yields its full fallback set."""
    rec: Set[Tuple[str, int]] = set()
    _RECORDERS.append(rec)
    try:
        yield rec
    finally:
        # strictly LIFO — pop by position, not remove() (set equality
        # would match a different recorder with equal contents)
        assert _RECORDERS[-1] is rec
        _RECORDERS.pop()


def resolve_axis(mesh: Mesh, logical: Optional[str], dim: int, *,
                 warn: bool = True):
    """Pick the first fallback whose size divides ``dim`` (else None).

    Replication-on-non-divisible is by design (a sharding that fails to
    lower is a bug, a replicated tensor is not), but it must not be
    SILENT: when every candidate chain fails, a once-per-(axis, dim, mesh)
    ``ShardingFallbackWarning`` fires.  Callers that probe one rule only
    to fall back to ANOTHER sharding (e.g. the kv->sequence cache chain in
    ``state_pspec``) pass ``warn=False`` — there the tensor still ends up
    sharded and the warning would be a false alarm.
    """
    if logical is None:
        return None
    tried = False
    for axes in _LOGICAL[logical]:
        axes = tuple(a for a in axes if a in mesh.shape)
        if not axes:
            continue
        tried = True
        if dim % _axis_size(mesh, axes) == 0:
            return axes if len(axes) > 1 else axes[0]
    if tried and warn and dim > 1:     # replicating a size-1 dim is free
        for rec in _RECORDERS:
            rec.add((logical, dim))
        key = (logical, dim, tuple(sorted((str(k), int(v))
                                          for k, v in mesh.shape.items())))
        if key not in _FALLBACK_WARNED:
            _FALLBACK_WARNED.add(key)
            warnings.warn(
                f"logical axis {logical!r} (dim {dim}) divides no mesh axis "
                f"chain of {dict(mesh.shape)} — replicating (full per-device "
                f"memory).  Resize the mesh or the dim to shard it.",
                ShardingFallbackWarning, stacklevel=2)
    return None


def spec_for(mesh: Mesh, logicals: Tuple[Optional[str], ...],
             shape: Tuple[int, ...]) -> P:
    assert len(logicals) == len(shape), (logicals, shape)
    return P(*[resolve_axis(mesh, lg, d) for lg, d in zip(logicals, shape)])


# ----------------------------------------------------------------------------
# parameter rules, keyed by (parent, leaf-name)
# ----------------------------------------------------------------------------
_PARAM_RULES: Dict[str, Tuple[Optional[str], ...]] = {
    # embeddings
    "embedding": ("vocab", "embed"),
    "lm_head": ("embed", "vocab"),
    # norms
    "scale": (None,),
    "bias": (None,),
    # attention
    "wq": ("embed", "heads"),
    "wk": ("embed", "kv"),
    "wv": ("embed", "kv"),
    "wo": ("heads", "embed"),
    # latent attention (MLA): the down-projection to the shared latent and
    # its norm replicate over "model"; the per-head up-projection splits
    # heads like wq
    "wkv_a": ("embed", None),
    "kv_norm": (None,),
    "wkv_b": (None, "heads"),
    # dense mlps (and shared experts)
    "w_gate": ("embed", "ffn"),
    "w_up": ("embed", "ffn"),
    "w_down": ("ffn", "embed"),
    "shared_gate": ("embed", "ffn"),
    "shared_up": ("embed", "ffn"),
    "shared_down": ("ffn", "embed"),
    # moe (3D expert weights override the 2D mlp rules by rank below)
    "router": ("embed", None),
    # mamba
    "in_proj": ("embed", "ffn"),
    "conv_w": (None, "ffn"),
    "conv_b": ("ffn",),
    "x_proj": ("ffn", None),
    "dt_proj": (None, "ffn"),
    "dt_bias": ("ffn",),
    "A_log": ("ffn", None),
    "D": ("ffn",),
    "out_proj": ("ffn", "embed"),
    # mlstm
    "up_proj": ("embed", "ffn"),
    "w_if": (None, None),
    "b_i": (None,),
    "b_f": (None,),
    "gn_scale": (None,),
    "skip": (None,),
    "down_proj": ("ffn", "embed"),
    # slstm
    "w_in": ("embed", "ffn"),
    "r": (None, None, None, None),
    "b": (None,),
    "ffn_gate": ("embed", "ffn"),
    "ffn_up": ("embed", "ffn"),
    "ffn_down": ("ffn", "embed"),
}

_MOE_3D_RULES = {
    "w_gate": (("expert", "embed", None), (None, "embed", "ffn")),
    "w_up": (("expert", "embed", None), (None, "embed", "ffn")),
    "w_down": (("expert", None, "embed"), (None, "ffn", "embed")),
}


def _path_names(path) -> Tuple[str, ...]:
    """Key names along a tree path: dict keys, dataclass attribute names
    (registered dataclasses like DecodeState flatten to GetAttrKey) and
    sequence indices alike."""
    out = []
    for p in path:
        for attr in ("key", "name", "idx"):
            if hasattr(p, attr):
                out.append(str(getattr(p, attr)))
                break
        else:
            out.append(str(p))
    return tuple(out)


def param_pspec(mesh: Mesh, path, leaf) -> P:
    names = _path_names(path)
    name = names[-1]
    shape = tuple(leaf.shape)
    # body/prefix groups are stacked over periods: leading None
    stacked = any(n.startswith("p") and n[1:].isdigit()
                  or n.startswith("pre") for n in names)
    core_shape = shape[1:] if stacked else shape
    if name in _MOE_3D_RULES and len(core_shape) == 3:
        for rule in _MOE_3D_RULES[name]:
            # probe silently (the next rule is the fallback)...
            spec = [resolve_axis(mesh, lg, d, warn=False)
                    for lg, d in zip(rule, core_shape)]
            if spec[0] is not None or rule[0] is None:
                break
        # falls through to the last rule if the expert dim never divided.
        # ...then re-resolve the CHOSEN rule loudly: its dead ends (any
        # dim, not just the leading one) are genuine replication
        spec = [resolve_axis(mesh, lg, d) for lg, d in zip(rule, core_shape)]
    elif name in _PARAM_RULES and len(_PARAM_RULES[name]) == len(core_shape):
        rule = _PARAM_RULES[name]
        spec = [resolve_axis(mesh, lg, d) for lg, d in zip(rule, core_shape)]
    else:
        spec = [None] * len(core_shape)
    if stacked:
        spec = [None] + spec
    return P(*spec)


def params_shardings(mesh: Mesh, params_shapes) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, param_pspec(mesh, path, leaf)),
        params_shapes)


# ----------------------------------------------------------------------------
# decode-state rules
# ----------------------------------------------------------------------------
def _batch_axes(mesh: Mesh, b: int):
    # batch/slot dims are transient and cheap: an odd batch (a 3-prompt
    # partial batch, an odd slot count) replicating is routine, not the
    # mis-sized-mesh memory hazard the fallback warning flags
    return resolve_axis(mesh, "embed", b, warn=False)


def kv_cache_pspec(mesh: Mesh, shape: Tuple[int, ...]) -> P:
    """PartitionSpec of a linear KV cache leaf (R, B, S, KV, hd)."""
    _, B, S, KV, _ = shape
    batch = _batch_axes(mesh, B)
    kv_ax = resolve_axis(mesh, "kv", KV, warn=False)   # seq fallback below
    seq_ax = None
    if kv_ax is None and S % mesh.shape.get("model", 1) == 0:
        # kv heads don't divide the model axis (kv=8/2/1 GQA): shard the
        # cache SEQUENCE over "model" instead — attention contracts hd
        # (replicated) and softmaxes over the sharded seq with small
        # partial-reduce collectives.  Sharding hd instead forces an
        # all-reduce of full (.., S) logits per layer (§Perf it-5).
        seq_ax = "model"
    if batch is None and seq_ax is None:
        # batch=1 long-context: shard the cache sequence over "data"
        seq_ax = "data" if S % mesh.shape.get("data", 1) == 0 else None
    return P(None, batch, seq_ax, kv_ax, None)


def latent_cache_pspec(mesh: Mesh, shape: Tuple[int, ...]) -> P:
    """PartitionSpec of an MLA latent cache leaf (R, B, S, L): the K/V
    rule for one key/value head, which the latent is to every head."""
    R, B, S, L = shape
    spec = tuple(kv_cache_pspec(mesh, (R, B, S, 1, L)))
    return P(*spec[:3], None)


def cache_pspec(mesh: Mesh, shape: Tuple[int, ...]) -> P:
    """PartitionSpec of a linear cache leaf: the K/V pair or the latent."""
    return (latent_cache_pspec(mesh, shape) if len(shape) == 4
            else kv_cache_pspec(mesh, shape))


def state_pspec(mesh: Mesh, path, leaf) -> P:
    names = _path_names(path)
    name = names[-1]
    shape = tuple(leaf.shape)
    if name == "cur_len":
        return P(None)
    R, B = shape[0], shape[1]
    batch = _batch_axes(mesh, B)
    if name in ("k", "v"):                      # (R, B, S, KV, hd)
        return kv_cache_pspec(mesh, shape)
    if name == "latent":                        # (R, B, S, L)
        return latent_cache_pspec(mesh, shape)
    if name == "conv":                          # (R, B, dc-1, di)
        return P(None, batch, None, resolve_axis(mesh, "ffn", shape[-1]))
    if name == "ssm":                           # (R, B, di, ds)
        return P(None, batch, resolve_axis(mesh, "ffn", shape[2]), None)
    if name == "C":                             # (R, B, nh, dh, dh)
        nh_ax = resolve_axis(mesh, "heads", shape[2], warn=False)
        dh_ax = resolve_axis(mesh, "heads", shape[3]) if nh_ax is None \
            else None
        return P(None, batch, nh_ax, dh_ax, None)
    if name in ("n", "h", "c", "m"):            # (R,B,nh[,dh])
        nh_ax = resolve_axis(mesh, "heads", shape[2], warn=False)
        rest = [None] * (len(shape) - 3)
        if nh_ax is None and len(shape) > 3:
            rest[0] = resolve_axis(mesh, "heads", shape[3])
        return P(None, batch, nh_ax, *rest)
    return P(*([None] * len(shape)))


def state_shardings(mesh: Mesh, state_shapes) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, state_pspec(mesh, path, leaf)),
        state_shapes)


# ----------------------------------------------------------------------------
# full DecodeState rules (live sharded serving, DESIGN.md §10)
# ----------------------------------------------------------------------------
# per-slot row leaves of core.spec_engine.DecodeState: dim 0 is the slot
# ("batch") axis; everything trailing is replicated.  The sampling leaves
# (rng_key (B, 2), temperature/top_p (B,)) are ordinary per-slot rows: the
# in-step key split/gumbel draws are row-local, so they shard with their
# slot exactly like the bandit stats.
_STATE_ROW_FIELDS = ("buf", "buf_len", "prompt_len", "budget", "eos_id",
                     "done", "active", "rng_key", "temperature", "top_p")

# The single source of truth for WHICH DecodeState leaves have a sharding
# rule — ``decode_state_pspec(strict=True)`` raises KeyError for any leaf
# matching no entry, and repro-lint's sharding-coverage analyzer runs
# strict over every registry config (so adding a DecodeState leaf without
# extending this table fails CI instead of silently replicating — the
# PR-7 rng_key/temperature/top_p class).  Top-level fields match on the
# path HEAD; model-cache leaves match on the path TAIL (they sit under
# ``model``, arbitrarily nested per layer).
DECODE_STATE_LEAF_RULES: Dict[str, str] = {
    # --- top-level per-slot rows (match on path head) ---
    **{f: "per-slot row: slot axis over ('pod','data'), rest replicated"
       for f in _STATE_ROW_FIELDS},
    "stats": "telemetry rows: slot axis over ('pod','data')",
    # --- model-cache leaves (match on path tail, under `model`) ---
    "cur_len": "scalar step counter: replicated",
    "k": "KV cache: kv-heads over 'model' else sequence fallback; "
         "paged pool: page axis over ('pod','data')[+'model']",
    "v": "same rule as 'k'",
    "latent": "MLA latent cache: the K/V rule for one shared head "
              "(sequence over 'model' where it divides)",
    "conv": "mamba conv window: channel dim over 'ffn'->'model'",
    "ssm": "mamba ssm state: inner dim over 'ffn'->'model'",
    "C": "mlstm covariance: heads over 'model' else head_dim",
    "n": "mlstm/slstm normalizer: heads over 'model'",
    "h": "slstm hidden: heads over 'model'",
    "c": "slstm cell: heads over 'model'",
    "m": "mlstm/slstm max-stabilizer: heads over 'model'",
    "page_table": "per-slot page map: slot axis over ('pod','data')",
    "n_pages": "per-slot page count: slot axis over ('pod','data')",
    "free_list": "free-page stack: replicated (device-identical mutation)",
    "free_top": "free-stack pointer: replicated",
}


def _page_axes(mesh: Mesh, num_pages: int, kv_sharded: bool):
    """The paged pool's page axis shards like the linear cache's
    (batch, sequence) pair it replaces: capacity-parallel over
    ("pod","data") when divisible, extended over "model" too when the kv
    heads could not take the model axis (the GQA kv=8/2/1 case — exactly
    the linear layout's sequence-over-"model" fallback)."""
    axes: Tuple[str, ...] = ()
    for chain in (("pod", "data"), ("data",)):
        c = tuple(a for a in chain if a in mesh.shape)
        if c and num_pages % _axis_size(mesh, c) == 0:
            axes = c
            break
    if not kv_sharded and "model" in mesh.shape:
        cand = axes + ("model",)
        if num_pages % _axis_size(mesh, cand) == 0:
            axes = cand
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def decode_state_pspec(mesh: Mesh, path, leaf, *, paged: bool = False,
                       strict: bool = False) -> P:
    """PartitionSpec for ONE leaf of a full ``DecodeState`` pytree.

    Extends ``state_pspec`` (which covers the model-cache leaves) with the
    serving-level leaves: the token buffer / per-slot scalars / stats rows
    shard their slot axis over ("pod","data"); the paged pool's page axis
    shards like the sequence axis (ROADMAP); page tables are slot-sharded
    and the free stack is replicated (it is mutated identically on every
    device — a tiny int32 vector, and replication keeps alloc/free/grow
    collective-free).

    ``strict=True`` raises ``KeyError`` for a leaf matching no
    ``DECODE_STATE_LEAF_RULES`` entry instead of silently replicating it —
    the mode repro-lint's sharding-coverage analyzer runs in.  The engine
    itself stays non-strict: at serve time a replicated unknown leaf is
    correct (just unreviewed), and the lint gate is where the review is
    forced.
    """
    names = _path_names(path)
    top, name = names[0], names[-1]
    if strict and top not in DECODE_STATE_LEAF_RULES \
            and name not in DECODE_STATE_LEAF_RULES:
        raise KeyError(
            f"DecodeState leaf {'/'.join(names)!r} matches no "
            f"DECODE_STATE_LEAF_RULES entry — add one (plus a pspec branch "
            f"if it needs more than replication/slot-row sharding)")
    shape = tuple(leaf.shape)
    if top in _STATE_ROW_FIELDS or top == "stats":
        return P(_batch_axes(mesh, shape[0]), *([None] * (len(shape) - 1)))
    # below here: the model-cache subtree
    if name == "page_table":
        return P(_batch_axes(mesh, shape[0]), None)
    if name == "n_pages":
        return P(_batch_axes(mesh, shape[0]))
    if name in ("free_list", "free_top"):
        return P(*([None] * len(shape)))
    if paged and name in ("k", "v"):            # pool (R, NP, ps, KV, hd)
        _, NP, _, KV, _ = shape
        kv_ax = resolve_axis(mesh, "kv", KV, warn=False)
        page_ax = _page_axes(mesh, NP, kv_sharded=kv_ax is not None)
        if kv_ax is None and page_ax is None:
            resolve_axis(mesh, "kv", KV)        # end of chain: warn once
        return P(None, page_ax, None, kv_ax, None)
    return state_pspec(mesh, path, leaf)


def decode_state_shardings(mesh: Mesh, state, *, strict: bool = False) -> Any:
    """NamedSharding pytree for a ``DecodeState`` (or shape structs of one).

    Detects the paged layout from the state itself ("page_table" under
    ``model``), so callers pass the state they actually built.  ``strict``
    is forwarded to ``decode_state_pspec``.
    """
    paged = isinstance(getattr(state, "model", None), dict) \
        and "page_table" in state.model
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, decode_state_pspec(mesh, path, leaf, paged=paged,
                                     strict=strict)),
        state)


def spec_summary(shardings) -> Dict[str, str]:
    """{leaf path: partition spec} for a NamedSharding pytree — the
    human-readable half of ``ServingEngine.mesh_report()``."""
    flat = jax.tree_util.tree_flatten_with_path(shardings)[0]
    return {"/".join(_path_names(path)): str(tuple(sh.spec))
            for path, sh in flat}


def batch_sharding(mesh: Mesh, shape: Tuple[int, ...],
                   batch_dim: int = 0) -> NamedSharding:
    """Tokens / embeds / logits: batch over ("pod","data"), rest replicated.

    Exception: (3, B, T) M-RoPE positions -> batch_dim=1.
    """
    spec = [None] * len(shape)
    spec[batch_dim] = _batch_axes(mesh, shape[batch_dim])
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
