"""Operations and bytes the bifurcated verify attention needs, per call.

One call of the ``spec_attention`` kernel serves one layer of one
``spec_step``: every active slot scores k*(w+1) query rows against its
cache and against each row's own (w+1)-token tail.  The work the algorithm
needs is counted at the *live* cache length of each slot, whatever length
the kernel streams; the queries, tails and outputs are read or written
once.  Element size is that of the served type.
"""
from __future__ import annotations

from typing import Iterable, Tuple


def call_work(live: Iterable[int], *, heads: int, kv_heads: int,
              head_dim: int, rows: int, w1: int,
              elem_bytes: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of one call over slots whose caches hold ``live``
    positions each; ``rows`` = k*(w+1) query rows per slot."""
    flops = 0.0
    nbytes = 0.0
    for n in live:
        # QK^T and PV: 2 flops per multiply-add, each over head_dim; the
        # tail is causal within a row: (w1 + 1) / 2 keys per query on average
        keys = n + (w1 + 1) / 2
        flops += 4.0 * heads * head_dim * rows * keys
        cache = 2.0 * n * kv_heads * head_dim          # K and V, live part
        tails = 2.0 * rows * kv_heads * head_dim
        q_out = 2.0 * rows * heads * head_dim
        nbytes += elem_bytes * (cache + tails + q_out)
    return flops, nbytes


def seconds(flops: float, nbytes: float, peak_flops: float,
            peak_bytes_per_s: float) -> float:
    """The roofline: the least time the chip could take for the work."""
    return max(flops / peak_flops, nbytes / peak_bytes_per_s)
