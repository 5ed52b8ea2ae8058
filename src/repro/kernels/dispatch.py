"""Kernel-dispatch layer: ONE switch between the Pallas fast path and XLA.

Every hot-path consumer (``models/attention.py:attn_verify``,
``core/drafters.py:context_ngram_draft``, the serving engine's buffer
sizing) routes through this module instead of importing kernels directly,
so backend selection, interpret-mode forcing and cache-length alignment are
decided in exactly one place.

Backend knob (``ModelConfig.backend`` for attention, ``SpecConfig.backend``
for drafting): ``"xla" | "pallas" | "auto"``.

  - ``"auto"``   — pallas on TPU, xla everywhere else (the production
                   default: the kernels are written for the TPU memory
                   hierarchy; on CPU the XLA paths are faster than
                   interpret-mode emulation).
  - ``"pallas"`` — always run the Pallas kernels.  Off-TPU this forces
                   ``interpret=True`` (how the parity tests prove the
                   kernels bit-compatible with the XLA paths on CPU).
  - ``"xla"``    — always run the pure-XLA paths.

Alignment: ``spec_attention_op`` streams the KV cache in ``block_s``-slot
VMEM blocks and pads the cache up to a block multiple per call when the
physical length does not divide — ``align_cache_len`` gives serving the
buffer length at which that per-step repad never happens.
"""
from __future__ import annotations

import re
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ops, ref

BACKENDS = ("xla", "pallas", "auto")
LANE = 128          # TPU lane width: last-dim tile of every VMEM block
SUBLANE = 8         # f32 sublane width: second-to-last-dim tile


def resolve_backend(backend: str) -> str:
    """Map the config knob to a concrete backend ("xla" or "pallas")."""
    if backend not in BACKENDS:
        raise ValueError(
            f"backend must be one of {BACKENDS}, got {backend!r}")
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return backend


def use_pallas(backend: str) -> bool:
    return resolve_backend(backend) == "pallas"


# every pallas_call names its kernel; the name survives into the op
# metadata of the compiled program ("<...>/<name>/pallas_call")
_KERNEL_OP = re.compile(r'op_name="[^"]*?(\w+)/pallas_call')


def kernels_in_hlo(hlo_text: str) -> Tuple[str, ...]:
    """Names of the Pallas kernels compiled for the TPU in an optimized HLO
    text, sorted: only ``tpu_custom_call`` ops count, so a kernel that ran
    in interpret mode (lowered to plain XLA ops) is not reported."""
    found = set()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = _KERNEL_OP.search(line)
            if m:
                found.add(m.group(1))
    return tuple(sorted(found))


def unique_sweep_widths(arms) -> Tuple[int, ...]:
    """Distinct positive speculation depths of an arm table, sorted.

    The adaptive spec_step (DESIGN.md §9) drafts once per depth returned
    here — each is one statically-shaped ``ngram_sweep`` baked into the SAME
    compiled step, because the sweep's continuation hash is a function of w.
    This is the dispatch layer's no-recompile contract for masking: the set
    of kernel instantiations one adaptive step contains is fixed by the arm
    TABLE (static), never by the arms slots happen to pick at runtime.
    w == 0 arms (plain greedy) need no sweep and contribute nothing.
    """
    return tuple(sorted({w for _, w in arms if w > 0}))


def default_interpret() -> bool:
    """Pallas kernels run in interpret mode off-TPU (tests force this by
    construction: CI has no TPU, so ``backend="pallas"`` == interpret)."""
    return jax.default_backend() != "tpu"


# ----------------------------------------------------------------------------
# buffer alignment (serving sizes its DecodeState through this)
# ----------------------------------------------------------------------------
def align_cache_len(n: int, block_s: int = 0) -> int:
    """Smallest cache length >= n that ``spec_attention_op`` never repads.

    A cache of S slots is streamed in blocks of ``min(block_s, S)``; padding
    happens iff S does not divide into whole blocks.  Below one block the
    kernel takes the cache as a single block, so only sublane alignment is
    applied there.  ``block_s=0`` means the kernel default.
    """
    bs = block_s or ops.DEFAULT_BLOCK_S
    if n >= bs:
        return -(-n // bs) * bs
    return -(-n // SUBLANE) * SUBLANE


# ----------------------------------------------------------------------------
# bifurcated verify attention
# ----------------------------------------------------------------------------
def pallas_verify_supported(cfg) -> bool:
    """Kernel-eligibility for a ModelConfig: the Pallas verify kernel
    implements the linear-cache, no-softcap contract; configs outside it
    (Gemma softcap, Mixtral sliding-window ring cache) keep the XLA path
    even under ``backend="pallas"``."""
    return (cfg.attn_logit_softcap is None
            and cfg.sliding_window is None)


def _static_mask(tail_mask) -> Optional[Tuple[Tuple[bool, ...], ...]]:
    """numpy (N, N) bool -> hashable tuple-of-tuples for the jitted ops.

    The tail mask is a compile-time tree-topology constant (DESIGN.md §11),
    so it belongs in the jit cache key: one kernel instantiation per
    topology, zero per-call operands.
    """
    if tail_mask is None:
        return None
    return tuple(map(tuple, np.asarray(tail_mask, bool).tolist()))


def verify_attention(q, k_cache, v_cache, k_tail, v_tail, cur_len, *,
                     w1: int, block_s: int = 0,
                     tail_mask=None) -> jnp.ndarray:
    """Pallas bifurcated verify attention in the engine layout.

    q: (B, K, W1, H, hd); caches (B, S, KV, hd); tails (B, K, W1, KV, hd);
    cur_len (B,).  Returns (B, K, W1, H, hd).

    ``tail_mask``: optional static (K*W1, K*W1) bool numpy array replacing
    the per-row causal tail mask — tree verification's ancestor-only
    visibility (DESIGN.md §11; K == 1 there, the tree is one "row").

    Masked-shape contract (adaptive arms, DESIGN.md §9): K/W1 are the
    compile-time maxima; a slot running a smaller (k, w) arm simply has its
    surplus rows/positions ignored downstream (attention is causal per row
    / ancestor-only per tree node, so the extra positions cannot influence
    the accepted prefix) — one compilation serves every arm.
    """
    bs = block_s if block_s else ops.DEFAULT_BLOCK_S
    return ops.spec_attention_op(q, k_cache, v_cache, k_tail, v_tail,
                                 cur_len, w1=w1, block_s=bs,
                                 interpret=default_interpret(),
                                 tail_mask=_static_mask(tail_mask))


def verify_attention_paged(q, k_pool, v_pool, page_table, k_tail, v_tail,
                           cur_len, *, w1: int, tail_mask=None) -> jnp.ndarray:
    """Pallas bifurcated verify attention over a paged KV pool.

    q: (B, K, W1, H, hd); pools (num_pages, page_size, KV, hd); page_table
    (B, pages_per_slot) int32 (-1 = unallocated); tails (B, K, W1, KV, hd);
    cur_len (B,); tail_mask as in ``verify_attention``.  Returns
    (B, K, W1, H, hd).  The kernel's cache-block grid walks the page table
    (one grid step per page), so page_size plays the role block_s has on
    the linear path.  The same masked-shape contract as
    ``verify_attention`` applies: K/W1 are arm-table maxima, one compile.
    """
    return ops.paged_spec_attention_op(q, k_pool, v_pool, page_table,
                                       k_tail, v_tail, cur_len, w1=w1,
                                       interpret=default_interpret(),
                                       tail_mask=_static_mask(tail_mask))


# ----------------------------------------------------------------------------
# context N-gram match/hash sweep
# ----------------------------------------------------------------------------
def ngram_sweep(buf: jnp.ndarray, query: jnp.ndarray, cur_len: jnp.ndarray,
                *, w: int, backend: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Backend-dispatched match/hash sweep over every context position.

    buf: (B, L) int32; query: (B, q); cur_len: (B,).
    Returns (match (B, L) int32, hash (B, L) uint32) where
      match[b, i] = all(buf[b, i:i+q] == query[b]) and i + q + w <= cur_len
      hash[b, i]  = hashing.hash_rows(buf[b, i+q : i+q+w])

    Both backends produce bit-identical integers (property the scoring
    stage in core/drafters.py relies on), so drafts cannot depend on the
    backend.

    Mesh seam (DESIGN.md §10): like ``attn_verify``, an installed
    activation sharder pins this to the XLA path — the Pallas sweep is a
    single-device ``pallas_call`` that the SPMD partitioner cannot split,
    so dispatching it over a data-sharded ``buf`` inside the sharded
    spec_step would fail to lower (or gather the buffer every step).
    """
    from ..distributed import act_sharding
    if use_pallas(backend) and not act_sharding.installed():
        return ops.ngram_match_op(buf, query, cur_len, w=w,
                                  interpret=default_interpret())
    B, L = buf.shape
    q = query.shape[1]
    pad = jnp.full((B, q + w), -1, jnp.int32)
    bufp = jnp.concatenate([buf.astype(jnp.int32), pad], axis=1)
    fn = lambda b, qq, c: ref.ngram_match_ref(b, qq, c[None], w=w)
    return jax.vmap(fn)(bufp, query.astype(jnp.int32),
                        cur_len.astype(jnp.int32))
