"""Pallas TPU kernel: context N-gram matching (paper §4.2, Appendix B.2).

The O(L·(q+w)) part of the context drafter — comparing the last q tokens
against every context position and hashing every w-token continuation — is
a VPU job.  The (count, recency) scoring and top-k stay in plain XLA
(sort-based; O(L log L) but bandwidth-trivial).

Outputs per position p:
  match[p] = all(buf[p:p+q] == query) and p + q + w <= cur_len
  hash[p]  = polynomial uint32 hash of buf[p+q : p+q+w]

Layout: every load the TPU compiler sees is tile-aligned.  The padded token
buffer is viewed as (rows, 128) int32 — position p lives at (p // 128,
p % 128) — and the grid walks (slot, tile), one (8, 128) tile of
``TILE`` positions per step.  Each step gets its aligned tile plus the next
tile as a halo (a second BlockSpec on the same operand), so a window token at
offset s = 128·a + b of position p is

  lane c <  128 - b:  row r + a,     lane c + b
  lane c >= 128 - b:  row r + a + 1, lane c + b - 128

i.e. two static sublane slices of the (tile + halo) value, each rotated
left by b lanes (``pltpu.roll``), merged by a lane mask.  No dynamic
offset is ever unaligned, which is what Mosaic requires.  ``query`` and
``cur_len`` ride in SMEM as scalar-prefetch operands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .hashing import hash_step

LANE = 128
ROWS = 8                          # one (8, 128) int32 tile per grid step
TILE = ROWS * LANE                # positions per grid step
MAX_WINDOW = TILE                 # q + w must fit in the halo tile


def _window(x: jnp.ndarray, s: int) -> jnp.ndarray:
    """Tokens at offset ``s`` of every position of the tile:
    x (2 * ROWS, 128) -> (ROWS, 128), out[r, c] = flat[r*128+c+s]."""
    a, b = divmod(s, LANE)
    lo = x[a:a + ROWS]
    if b == 0:
        return lo
    hi = x[a + 1:a + 1 + ROWS]
    lane = jax.lax.broadcasted_iota(jnp.int32, lo.shape, 1)
    return jnp.where(lane < LANE - b, pltpu.roll(lo, LANE - b, 1),
                     pltpu.roll(hi, LANE - b, 1))


def _kernel(cur_len_ref, query_ref, main_ref, halo_ref, match_ref, hash_ref,
            *, q: int, w: int):
    b = pl.program_id(0)
    i = pl.program_id(1)
    x = jnp.concatenate([main_ref[...], halo_ref[...]], axis=0)
    shape = (ROWS, LANE)
    pos = (i * TILE
           + jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANE
           + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    # windows that would run past the committed context are invalid
    # (cur_len <= true L, so this also masks the padded region)
    match = pos + q + w <= cur_len_ref[b]
    for j in range(q):
        match = match & (_window(x, j) == query_ref[b, j])
    h = jnp.zeros(shape, jnp.uint32)
    for j in range(w):
        h = hash_step(h, _window(x, q + j))
    match_ref[...] = match.astype(jnp.int32)
    hash_ref[...] = h


def ngram_match_call(buf: jnp.ndarray, query: jnp.ndarray,
                     cur_len: jnp.ndarray, *, w: int,
                     interpret: bool = False):
    """buf: (B, R, 128) int32 — the token buffer in row-major (rows, lanes)
    view, -1-padded by the ops wrapper to ``n_tiles * TILE`` positions plus
    one halo tile.  query: (B, q) int32; cur_len: (B,) int32.
    Returns (match, hash), each (B, n_tiles * ROWS, 128).
    """
    B, R, lanes = buf.shape
    q = query.shape[1]
    assert lanes == LANE and R % ROWS == 0 and R > ROWS, (lanes, R)
    assert q + w <= MAX_WINDOW, (q, w)
    n_tiles = R // ROWS - 1
    kernel = functools.partial(_kernel, q=q, w=w)
    out_rows = n_tiles * ROWS
    block = pl.BlockSpec((None, ROWS, LANE), lambda b, i, c, qq: (b, i, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_tiles),
            in_specs=[
                block,
                pl.BlockSpec((None, ROWS, LANE),
                             lambda b, i, c, qq: (b, i + 1, 0)),
            ],
            out_specs=[block, block],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, out_rows, LANE), jnp.int32),
                   jax.ShapeDtypeStruct((B, out_rows, LANE), jnp.uint32)],
        interpret=interpret,
        name="ngram_match",
    )(cur_len, query, buf, buf)
