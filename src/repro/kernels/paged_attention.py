"""Paged decode attention over the stacked KV pool, live blocks only.

One query token per slot attends over the keys and values its block table
points at, read straight from the serving engine's pool.  The pool leaf
(n, NB, bt, *row) of every layer stays in HBM; for each slot the kernel
copies in only the ``ceil(lens[b] / bt)`` blocks of layer ``i`` that hold
its live positions, ``pages`` blocks at a time, double-buffered across
groups and across slots, and carries an online softmax over them.  The
(B, max_seq) context is never materialised, and a slot with ``lens`` 0
issues no copy and gets zeros.

Inputs:
    q      (B, Hkv, G, hd)    one decode token per slot, G queries per
                              kv head
    kpool  (n, NB, bt, *row)  the stacked pool (``models.layers.kv_row``)
    vpool  (n, NB, bt, *row)
    i      ()  int32          the layer whose blocks are read
    tables (B, max_blocks)    block ids per slot
    lens   (B,) int32         live positions per slot (pos + 1, 0 if dead)
    out    (B, Hkv, G, hd)    float32

A row comes in one of two layouts, and the math follows the pool's shape:

* **whole heads** (Hkv, hd), hd a multiple of 128: the T positions'
  keys are T * Hkv rows of hd lanes, and each query row sees only the
  rows of its own head (a mask on the scores), so scores and the
  weighted sum are one matmul each.
* **one packed row** (R,) of all heads (heads of 96 share registers):
  the query becomes a block-diagonal (G * Hkv, R) matrix, one row per
  head holding that head's lanes, so the scores of every head are one
  matmul against the keys as stored, and the weighted sum one matmul of
  the probabilities against the values; the output keeps each row's own
  head's lanes.  No row is split into heads.

Keys and values are read as stored; scores, the running max and sum and
the weighted sum are float32, and the float32 probabilities meet bf16
values as three bf16 parts (hi, mid, lo) whose products are exact.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _dot(a, b, dims):
    """``dot_general`` with a float32 result, at full precision for
    float32 operands (bf16 operands multiply exactly in any case)."""
    prec = jax.lax.Precision.HIGHEST if a.dtype == F32 else None
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=prec,
                               preferred_element_type=F32)


def _weighted_sum(p, v):
    """p (M, T) float32 times v (T, R) as stored -> (M, R) float32.  A bf16
    v meets p as three bf16 parts stacked in one matmul, which together
    hold p's float32 value: the probabilities are not rounded."""
    if v.dtype == F32:
        return _dot(p, v, ((1,), (0,)))
    hi = p.astype(v.dtype)
    r = p - hi.astype(F32)
    mid = r.astype(v.dtype)
    lo = (r - mid.astype(F32)).astype(v.dtype)
    M = p.shape[0]
    out = _dot(jnp.concatenate([hi, mid, lo], axis=0), v, ((1,), (0,)))
    return out[:M] + out[M:2 * M] + out[2 * M:]


def _head_lanes(G, H, R, hd):
    """(G, H, R) bool: lane c of a packed row belongs to head h."""
    h = jax.lax.broadcasted_iota(jnp.int32, (G, H, R), 1)
    c = jax.lax.broadcasted_iota(jnp.int32, (G, H, R), 2)
    return (c >= h * hd) & (c < h * hd + hd)


class _Softmax:
    """Online softmax of the G * H query rows of one slot over its keys.

    Both row layouts become one (G * H, X) query matrix against keys of X
    lanes: a packed row keeps X = R lanes and makes the query
    block-diagonal (row (g, h) holds only head h's lanes), so each
    position is one key row; whole heads take X = hd, and the (T, H, hd)
    keys of T positions become T * H key rows, of which row (g, h) sees
    only those of head h.  Scores, max, sum and the weighted sum are
    float32."""

    def __init__(self, G, H, hd, row, dtype):
        self.G, self.H, self.hd, self.row = G, H, hd, row
        self.packed = len(row) == 1
        X = row[0] if self.packed else hd
        self.scratch = [pltpu.VMEM((G * H, 1), F32),          # running max
                        pltpu.VMEM((G * H, 1), F32),          # running sum
                        pltpu.VMEM((G * H, X), F32),          # weighted sum
                        pltpu.VMEM((G * H, X), dtype)]        # query rows

    def start(self, q, m_ref, l_ref, acc_ref, q_ref):
        """q (G, *row): the slot's queries."""
        G, H = self.G, self.H
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)
        if self.packed:
            R = self.row[0]
            q = jnp.where(_head_lanes(G, H, R, self.hd),
                          jnp.broadcast_to(q.astype(F32)[:, None, :],
                                           (G, H, R)), 0.0)
        q_ref[...] = q.reshape(q_ref.shape).astype(q_ref.dtype)

    def update(self, k, v, live, m_ref, l_ref, acc_ref, q_ref):
        """k, v (T, *row): a group's positions; ``live(pos)`` says which
        positions (int32, counted from the group's first) are live."""
        if self.packed:
            T = k.shape[0]
            keep = live(jax.lax.broadcasted_iota(jnp.int32, (1, T), 1))
        else:
            N = k.shape[0] * self.H
            k, v = k.reshape(N, self.hd), v.reshape(N, self.hd)
            shape = (self.G * self.H, N)
            col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            keep = (col % self.H == row % self.H) & live(col // self.H)
        s = _dot(q_ref[...], k, ((1,), (1,))) \
            / math.sqrt(self.hd)
        s = jnp.where(keep, s, -jnp.inf)
        m_prev = m_ref[...]
        m = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m)
        p = jnp.exp(s - m)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + _weighted_sum(p, v)
        m_ref[...] = m

    def finish(self, m_ref, l_ref, acc_ref, q_ref):
        """The slot's output (G, *row)."""
        G, H = self.G, self.H
        o = acc_ref[...] / l_ref[...]
        if not self.packed:
            return o.reshape(G, H, self.hd)
        R = self.row[0]
        return jnp.sum(jnp.where(_head_lanes(G, H, R, self.hd),
                                 o.reshape(G, H, R), 0.0), axis=1)


def _kernel(i_ref, tables_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, *state, math_, pages):
    B, max_blocks = tables_ref.shape
    bt = kbuf.shape[2]
    span = pages * bt
    layer = i_ref[0]

    def copies(b, g, slot):
        """(issued?, copies) of group g of slot b: its blocks that hold
        live positions, into buffer ``slot``."""
        nblk = (lens_ref[b] + bt - 1) // bt
        out = []
        for p in range(pages):
            j = g * pages + p
            blk = tables_ref[b, jnp.minimum(j, max_blocks - 1)]
            out.append((j < nblk, [
                pltpu.make_async_copy(k_hbm.at[layer, blk], kbuf.at[slot, p],
                                      sems.at[slot, 0]),
                pltpu.make_async_copy(v_hbm.at[layer, blk], vbuf.at[slot, p],
                                      sems.at[slot, 1])]))
        return out

    def start(b, g, slot):
        for p, (issued, cps) in enumerate(copies(b, g, slot)):
            @pl.when(issued)
            def _():
                for c in cps:
                    c.start()

            @pl.when(jnp.logical_not(issued))
            def _():
                # past the slot's last block: values that weigh 0 in the
                # weighted sum must not be NaN
                vbuf[slot, p] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)

    def wait(b, g, slot):
        for issued, cps in copies(b, g, slot):
            @pl.when(issued)
            def _():
                for c in cps:
                    c.wait()

    def next_live(b):
        """The first slot after b with live positions, else B."""
        return jax.lax.fori_loop(
            b + 1, B, lambda c, n: jnp.where((n == B) & (lens_ref[c] > 0),
                                             c, n), B)

    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    first = next_live(-1)

    @pl.when(first < B)
    def _():
        start(first, 0, 0)

    def slot_body(b, slot):
        n_groups = (lens_ref[b] + span - 1) // span
        after = next_live(b)

        @pl.when(n_groups > 0)
        def _():
            math_.start(q_ref[b], *state)

        def group_body(g, slot):
            last = g + 1 == n_groups
            nb, ng = jnp.where(last, after, b), jnp.where(last, 0, g + 1)

            @pl.when(nb < B)
            def _():
                start(nb, ng, 1 - slot)

            wait(b, g, slot)

            def live(pos):
                return g * span + pos < lens_ref[b]

            k = kbuf[slot].reshape((span,) + kbuf.shape[3:])
            v = vbuf[slot].reshape((span,) + vbuf.shape[3:])
            math_.update(k, v, live, *state)
            return 1 - slot

        slot = jax.lax.fori_loop(0, n_groups, group_body, slot)

        @pl.when(n_groups > 0)
        def _():
            o_ref[b] = math_.finish(*state).astype(o_ref.dtype)

        return slot

    jax.lax.fori_loop(0, B, slot_body, 0)


@functools.partial(jax.jit, static_argnames=("pages", "interpret"))
def paged_attention(q, kpool, vpool, i, tables, lens, *, pages=8,
                    interpret=False):
    """q (B, Hkv, G, hd) against layer ``i`` of the stacked pools through
    ``tables``, over each slot's first ``lens`` positions ->
    (B, Hkv, G, hd) float32."""
    B, H, G, hd = q.shape
    row = kpool.shape[3:]
    bt = kpool.shape[2]
    pages = max(1, min(pages, tables.shape[1]))
    math_ = _Softmax(G, H, hd, row, q.dtype)
    qr = q.transpose(0, 2, 1, 3)                             # (B, G, H, hd)
    if len(row) == 1:
        qr = jnp.pad(qr.reshape(B, G, H * hd),
                     ((0, 0), (0, 0), (0, row[0] - H * hd)))
    full = lambda shape: pl.BlockSpec(shape, lambda *_: (0,) * len(shape))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[full(qr.shape),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=full(qr.shape),
        scratch_shapes=[pltpu.VMEM((2, pages, bt) + row, kpool.dtype),
                        pltpu.VMEM((2, pages, bt) + row, vpool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2))] + math_.scratch)
    out = pl.pallas_call(
        functools.partial(_kernel, math_=math_, pages=pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qr.shape, F32),
        interpret=interpret,
    )(jnp.reshape(i, (1,)).astype(jnp.int32), tables.astype(jnp.int32),
      lens.astype(jnp.int32), qr, kpool, vpool)
    if len(row) == 1:
        out = out[..., :H * hd].reshape(B, G, H, hd)
    return out.transpose(0, 2, 1, 3)
