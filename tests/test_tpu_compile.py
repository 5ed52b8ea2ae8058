"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler that ships with JAX compiles each kernel for
a chip that is described, not attached, and raises what the chip's own
compiler (Mosaic) would raise — unaligned loads, memory spaces a kernel may
not read, more VMEM than a kernel may use.  Interpret mode, which the rest
of the suite uses, checks none of that.

Shapes are StableLM-2-1.6B's at serving widths (bf16, 32 heads, 32 KV
heads, head_dim 64; 8 slots of 2048 positions; cache block 512).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import tree as T
from repro.kernels import dispatch, ops
from repro.kernels.ngram_match import LANE, TILE, ngram_match_call
from repro.kernels.spec_attention import (paged_spec_attention_call,
                                          spec_attention_call)

B, H, KV, HD, S, BLOCK_S = 8, 32, 32, 64, 2048, 512
DT = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    """AOT-compile ``fn`` for the described chip; return the HLO text."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


@pytest.mark.parametrize("k,w,dt", [(10, 10, DT), (4, 4, DT),
                                    (10, 10, jnp.float32)])
def test_spec_attention_compiles(one_chip, k, w, dt):
    """bf16 serving widths, and the float32 cache of the exactness runs."""
    s = _sds(one_chip)
    kw1 = k * (w + 1)
    fn = lambda q, kc, vc, kt, vt, cl: spec_attention_call(
        q, kc, vc, kt, vt, cl, w1=w + 1, block_s=BLOCK_S)
    hlo = _compile(fn, s((B, H, kw1, HD), dt), s((B, KV, S, HD), dt),
                   s((B, KV, S, HD), dt), s((B, KV, kw1, HD), dt),
                   s((B, KV, kw1, HD), dt), s((B,), jnp.int32))
    assert dispatch.kernels_in_hlo(hlo) == ("spec_attention",)


def test_spec_attention_tree_mask_compiles(one_chip):
    """The 81-node draft tree: one row, ancestor mask as a static operand."""
    s = _sds(one_chip)
    topo = T.topology(16, 5, 1)
    n = len(topo.pos_off)
    assert n == 81
    mask = np.asarray(topo.anc_mask, bool)
    fn = lambda q, kc, vc, kt, vt, cl: spec_attention_call(
        q, kc, vc, kt, vt, cl, w1=n, block_s=BLOCK_S, tail_mask=mask)
    hlo = _compile(fn, s((B, H, n, HD), DT), s((B, KV, S, HD), DT),
                   s((B, KV, S, HD), DT), s((B, KV, n, HD), DT),
                   s((B, KV, n, HD), DT), s((B,), jnp.int32))
    assert dispatch.kernels_in_hlo(hlo) == ("spec_attention",)


def test_paged_spec_attention_compiles(one_chip):
    s = _sds(one_chip)
    k, w = 10, 10
    kw1 = k * (w + 1)
    pps = S // BLOCK_S
    fn = lambda q, kp, vp, pt, kt, vt, cl: paged_spec_attention_call(
        q, kp, vp, pt, kt, vt, cl, w1=w + 1)
    hlo = _compile(fn, s((B, H, kw1, HD), DT),
                   s((B * pps, KV, BLOCK_S, HD), DT),
                   s((B * pps, KV, BLOCK_S, HD), DT),
                   s((B, pps), jnp.int32), s((B, KV, kw1, HD), DT),
                   s((B, KV, kw1, HD), DT), s((B,), jnp.int32))
    assert dispatch.kernels_in_hlo(hlo) == ("paged_spec_attention",)


@pytest.mark.parametrize("q,w,L", [(1, 10, S), (1, 4, 640), (2, 16, 1500)])
def test_ngram_match_op_compiles(one_chip, q, w, L):
    """The engine-facing wrapper: padding, (rows, 128) view, kernel."""
    s = _sds(one_chip)
    fn = lambda buf, qry, cl: ops.ngram_match_op(buf, qry, cl, w=w,
                                                 interpret=False)
    hlo = _compile(fn, s((B, L), jnp.int32), s((B, q), jnp.int32),
                   s((B,), jnp.int32))
    assert dispatch.kernels_in_hlo(hlo) == ("ngram_match",)


def test_ngram_match_call_compiles(one_chip):
    """The bare kernel over two tiles of 1024 positions plus the halo."""
    s = _sds(one_chip)
    rows = 3 * TILE // LANE
    fn = lambda buf, qry, cl: ngram_match_call(buf, qry, cl, w=10)
    hlo = _compile(fn, s((B, rows, LANE), jnp.int32), s((B, 1), jnp.int32),
                   s((B,), jnp.int32))
    assert dispatch.kernels_in_hlo(hlo) == ("ngram_match",)
