"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def matmul(a, b):
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32)
                   ).astype(a.dtype)


def jacobi2d(x_padded):
    x = x_padded.astype(jnp.float32)
    out = 0.25 * (x[:-2, 1:-1] + x[2:, 1:-1] + x[1:-1, :-2] + x[1:-1, 2:])
    return out.astype(x_padded.dtype)


def fconv2d(x_padded, filt):
    x = x_padded.astype(jnp.float32)[None, :, :, None]
    f = filt.astype(jnp.float32)[:, :, None, None]
    out = jax.lax.conv_general_dilated(
        x, f, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return out[0, :, :, 0].astype(x_padded.dtype)


def dotprod(a, b):
    return jnp.sum(a.astype(jnp.float32) * b.astype(jnp.float32))


def expv(x):
    return jnp.exp(jnp.clip(x.astype(jnp.float32), -80.0, 80.0)).astype(x.dtype)


def softmax_rows(x):
    return jax.nn.softmax(x.astype(jnp.float32), axis=-1).astype(x.dtype)


def attention(q, k, v, *, causal=True, window=None):
    """q (B,Hq,S,D), k/v (B,Hkv,Sk,D) with GQA head grouping."""
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    kq = jnp.repeat(k, group, axis=1).astype(jnp.float32)
    vq = jnp.repeat(v, group, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32), kq)
    s = s / math.sqrt(D)
    Sk = k.shape[2]
    q_pos = jnp.arange(S)[:, None]
    k_pos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((S, Sk), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # rows with no visible keys (all -inf) -> zero output
    any_visible = mask.any(axis=-1)[None, None, :, None]
    out = jnp.einsum("bhst,bhtd->bhsd", p, vq)
    out = jnp.where(any_visible, out, 0.0)
    return out.astype(q.dtype)


def rmsnorm(x, gamma, eps=1e-6):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * gamma.astype(jnp.float32)
            ).astype(x.dtype)
