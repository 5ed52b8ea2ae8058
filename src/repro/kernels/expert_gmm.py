"""Pallas TPU kernel: grouped matmul over the experts an MoE layer holds.

The rows are (token, expert) pairs sorted by expert, each expert's group
padded to whole tiles of ``tm`` rows (``models/moe.expert_layout``), so a
row tile belongs to exactly one expert: ``tile_group[i]``.  Tile i's output
is its rows times that expert's weight matrix in layer ``layer``:

    out[i*tm:(i+1)*tm] = lhs[i*tm:(i+1)*tm] @ rhs[layer, tile_group[i]]

The weights come stacked over the layers, (R, E, K, N), and the kernel
reads the layer's blocks straight from them: a slice of one layer as the
operand would make XLA copy the layer's experts (277 MB at DeepSeek-V2-
Lite's 16 held experts) before every call.

The grid walks (row tile, output column block, contraction block), the
contraction innermost, accumulating in float32 scratch.  Each tile streams
its expert's matrix once, block by block; an expert whose rows fill one
tile -- the common case at verify batch sizes -- is read once a call.

The row count is static and dropless (the most any routing can need); the
tiles past ``live`` hold no rows.  Their grid steps compute nothing, and
their index maps repeat the last live step's blocks, so they move no data
and write back nothing new (the last live output block is written back
unchanged when the grid ends).  ``tile_group`` and (layer, live) ride in
SMEM as scalar-prefetch operands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 512          # column/contraction block where the width allows


def block(dim: int) -> int:
    """Block along a weight dim: ``BLOCK`` where it divides the dim, else
    the whole dim (DeepSeek's 1408-wide experts: 11 lane tiles, prime)."""
    return BLOCK if dim % BLOCK == 0 else dim


def _kernel(group_ref, meta_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *,
            n_k: int):
    i, kk = pl.program_id(0), pl.program_id(2)

    @pl.when(i < meta_ref[1])
    def _():
        @pl.when(kk == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...].astype(lhs_ref.dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        @pl.when(kk == n_k - 1)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def expert_gmm_call(lhs, rhs, layer, tile_group, live, *, tm: int,
                    name: str = "expert_gmm",
                    interpret: bool = False) -> jnp.ndarray:
    """lhs (M, K) rows sorted into tiles of ``tm``; rhs (R, E, K, N) the
    held experts' weights of every layer; layer () int32; tile_group
    (M // tm,) int32 the expert of each tile; live () int32 the tiles that
    hold rows (>= 1).  Returns (M, N) in lhs's dtype; rows of tiles past
    ``live`` are left unwritten.  ``name`` names the custom call in a
    device trace."""
    M, K = lhs.shape
    R, E, K2, N = rhs.shape
    assert K == K2 and M % tm == 0, (lhs.shape, rhs.shape, tm)
    tk, tn = block(K), block(N)
    n_m, n_n, n_k = M // tm, N // tn, K // tk

    def at(i, j, kk, meta):
        # tiles past the live ones repeat the last live step's blocks
        dead = i >= meta[1]
        return (jnp.where(dead, meta[1] - 1, i), jnp.where(dead, n_n - 1, j),
                jnp.where(dead, n_k - 1, kk))

    def lhs_map(i, j, kk, group, meta):
        i, _, kk = at(i, j, kk, meta)
        return i, kk

    def rhs_map(i, j, kk, group, meta):
        i, j, kk = at(i, j, kk, meta)
        return meta[0], group[i], kk, j

    def out_map(i, j, kk, group, meta):
        i, j, _ = at(i, j, kk, meta)
        return i, j

    meta = jnp.stack([jnp.asarray(layer, jnp.int32).reshape(()),
                      jnp.asarray(live, jnp.int32).reshape(())])
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_m, n_n, n_k),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((None, None, tk, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(tile_group.astype(jnp.int32), meta, lhs, rhs)
