"""Paged decode attention: online softmax over block-table-gathered KV.

The kernel half of the paged serving story: one query token per sequence
(GQA groups expanded in-register) attends over K/V blocks scattered
through a shared pool — the VRF-chunk gather as a Pallas kernel.  The
block table and per-sequence lengths ride in as *scalar-prefetch*
operands (``pltpu.PrefetchScalarGridSpec``), so each grid step's index
map sends the DMA engine straight to pool block ``tables[b, j]``: the
dense (B, W) view is never materialised, which is the whole point — HBM
traffic is `lens[b]` tokens of K/V per sequence, not `max_seq`.

Layouts (chosen so a block is contiguous per kv head):
    q      (B, Hkv, G, D)      one decode token per sequence
    kpool  (Hkv, NB, bt, D)    the shared block pool (block 0 = zeros)
    vpool  (Hkv, NB, bt, D)
    tables (B, nblk) int32     block ids per sequence, 0 = unallocated
    lens   (B,) int32          valid tokens per sequence
    out    (B, Hkv, G, D)

Grid (B, Hkv, nblk) with the block axis innermost: m/l/acc scratch
carries the running softmax across a sequence's blocks exactly like
``flash_attention.py``'s kv loop.  `bt` (tokens per block) is the tuned
parameter.  A (1, 1, bt, D) block spans the pool's whole last two dims,
so the TPU tiling rule accepts any `bt`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale, bt):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                    # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)                    # (bt, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    k_pos = j * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
    visible = k_pos < lens_ref[b]                          # (1, bt)
    s = jnp.where(visible, s, NEG_INF)                     # (G, bt)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # mask p explicitly: on a fully-masked block m_new == NEG_INF and
    # exp(s - m_new) would be exp(0) == 1, not 0
    p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + \
        jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _flush():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)                 # fully-masked row
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, kpool, vpool, tables, lens, *, interpret=False):
    """q (B, Hkv, G, D) + pools/tables/lens -> (B, Hkv, G, D)."""
    B, Hkv, G, D = q.shape
    bt = kpool.shape[2]
    nblk = tables.shape[1]
    scale = 1.0 / math.sqrt(D)

    def q_map(b, h, j, tables, lens):
        del tables, lens, j
        return (b, h, 0, 0)

    def kv_map(b, h, j, tables, lens):
        del lens
        return (h, tables[b, j], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, nblk),
        in_specs=[pl.BlockSpec((1, 1, G, D), q_map),
                  pl.BlockSpec((1, 1, bt, D), kv_map),
                  pl.BlockSpec((1, 1, bt, D), kv_map)],
        out_specs=pl.BlockSpec((1, 1, G, D), q_map),
        scratch_shapes=[pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, D), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, bt=bt),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(tables, lens, q, kpool, vpool)
