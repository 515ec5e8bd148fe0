"""Fused RMSNorm — the per-token normalization hot-spot of every LM layer.

One VMEM sweep per row block: mean-square reduce (the intra-lane reduction
stage), rsqrt, scale — no HBM round-trip for the intermediate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def block_rows(R: int, bm: int, dtype) -> int:
    """Rows per block under the TPU tiling rule (a block's second-minor dim
    is a multiple of 8 or the whole array): all of R when it fits in
    ``bm``, else ``bm`` rounded up to the dtype's sublane tile — 8 rows of
    32-bit, 16 of 16-bit data."""
    if R <= bm:
        return R
    tile = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    return -(-bm // tile) * tile


def _rms_kernel(x_ref, g_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(ms + eps) * g_ref[...].astype(jnp.float32)
                  ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "eps", "interpret"))
def rmsnorm(x: jax.Array, gamma: jax.Array, *, bm: int = 8,
            eps: float = 1e-6, interpret: bool = False) -> jax.Array:
    """x (R, D), gamma (D,) -> (R, D).

    ``bm`` is adjusted by :func:`block_rows`; R is zero-padded up to a
    multiple of the block (a zero row normalises to zero) and the padding
    sliced off, so any row count is legal.
    """
    R, D = x.shape
    assert gamma.shape == (D,)
    bm = block_rows(R, bm, x.dtype)
    pad = -R % bm
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    kernel = functools.partial(_rms_kernel, eps=eps)
    out = pl.pallas_call(
        kernel,
        grid=((R + pad) // bm,),
        in_specs=[pl.BlockSpec((bm, D), lambda i: (i, 0)),
                  pl.BlockSpec((D,), lambda i: (0,))],
        out_specs=pl.BlockSpec((bm, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R + pad, D), x.dtype),
        interpret=interpret,
    )(xp, gamma)
    return out[:R] if pad else out
