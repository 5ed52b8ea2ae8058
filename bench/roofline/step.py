"""Useful model operations of the served step, for ``step_mfu``.

Counted per token the model had to process for a user, not per position
the program computed: a prompt token once (prefill), a committed output
token once.  Rejected draft positions, padding and recomputation count
nothing.  Per token: 2 flops per non-embedding weight, attention over the
live context (2 flops per multiply-add for QK^T and for PV, in every head
and layer, capped by a sliding window), and for a committed token the
output head (2 * d * V).
"""
from __future__ import annotations

from typing import Optional


def attention_flops(ctx: float, *, layers: int, heads: int, head_dim: int,
                    window: Optional[int] = None) -> float:
    """One query attending ``ctx`` keys, over every layer."""
    if window is not None:
        ctx = min(ctx, window)
    return 4.0 * layers * heads * head_dim * ctx


def token_flops(ctx: float, *, non_embedding: int, d_model: int,
                vocab: int, layers: int, heads: int, head_dim: int,
                window: Optional[int] = None, head: bool = True) -> float:
    """One token at context ``ctx``; ``head`` for a token whose logits are
    needed (committed tokens, the last prompt token)."""
    f = 2.0 * non_embedding + attention_flops(
        ctx, layers=layers, heads=heads, head_dim=head_dim, window=window)
    return f + (2.0 * d_model * vocab if head else 0.0)


def prefill_flops(n: int, *, non_embedding: int, d_model: int, vocab: int,
                  layers: int, heads: int, head_dim: int,
                  window: Optional[int] = None) -> float:
    """A prompt of ``n`` tokens: token i attends i + 1 keys (at most
    ``window``); only the last position's logits are needed."""
    w = n if window is None else min(n, window)
    keys = w * (w + 1) / 2 + (n - w) * w
    return (2.0 * non_embedding * n + 4.0 * layers * heads * head_dim * keys
            + 2.0 * d_model * vocab)
