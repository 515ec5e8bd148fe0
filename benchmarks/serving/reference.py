"""The plain reference: a float32 ``jax.numpy`` forward of the model,
written from the published architecture and nothing of the program.

Each family (``families/<family>.py``) writes its own forward,
``forward_rows``, over its own weights layout, from the pieces here:
every matrix product at ``highest`` precision (a TPU otherwise
multiplies float32 in bfloat16), RMSNorm, and rotary embedding in the
half-split form of the published code (``rotate_half``, inverse
frequencies ``theta ** (-2i / head_dim)``).  It runs one layer at a
time, so that it fits on the chip beside the weights.  What is shared
here batches the sampled requests and scores the served tokens.

``fp8=True`` is the control: every projection and the head multiply
float8 (e4m3, one scale per tensor) operands, the precision below the
configuration's bfloat16.  A comparison that passes it is too loose.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import spec

#: sequences in one forward: the (batch, heads, L, L) float32 scores of
#: one layer stay near 1 GB at any length
BATCH_TOKENS = 4096
E4M3_MAX = 448.0


def _fp8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / E4M3_MAX
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, fp8: bool):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (B, L, heads, hd) at positions 0..L-1."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[None, :, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def forward_rows(w: dict, c: dict, tokens: np.ndarray, rows: np.ndarray,
                 *, fp8: bool = False) -> jax.Array:
    """Teacher-forced logits by the family of ``c``: ``tokens`` (B, L)
    int32 (padding after each sequence is harmless under the causal
    mask), ``rows`` (B, R) the positions whose next-token logits are
    wanted -> (B, R, vocab) float32."""
    return spec.family(c).forward_rows(w, c, tokens, rows, fp8=fp8)


def gaps(w: dict, c: dict, prompts: list, served: list, *, length: int,
         rows_max: int, control: bool = False) -> dict:
    """For each served token, how far its logit lies below the
    reference's best in that row (0 where the program chose the
    reference's top token).  With ``control``, the same for the token
    the fp8 control puts first.  Sequences are padded to ``length``
    positions and ``rows_max`` rows, so one program serves every batch.
    Returns {"served": [array per request], "control": [...] or None}."""
    batch = max(1, BATCH_TOKENS // length)
    out = {"served": [], "control": [] if control else None}
    for b0 in range(0, len(prompts), batch):
        idx = list(range(b0, min(b0 + batch, len(prompts))))
        tokens = np.zeros((batch, length), np.int32)
        rows = np.zeros((batch, rows_max), np.int32)
        want = np.zeros((batch, rows_max), np.int32)
        for j, r in enumerate(idx):
            p, s = np.asarray(prompts[r]), np.asarray(served[r])
            seq = np.concatenate([p, s[:-1]])
            if len(seq) > length or len(s) > rows_max:
                raise ValueError(f"request of {len(p)} + {len(s)} tokens "
                                 f"exceeds {length} positions or "
                                 f"{rows_max} rows")
            tokens[j, :len(seq)] = seq
            rows[j, :len(s)] = len(p) - 1 + np.arange(len(s))
            want[j, :len(s)] = s
        ref = forward_rows(w, c, tokens, rows)
        top = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, jnp.asarray(want)[..., None],
                                  axis=-1)[..., 0]
        served_gap = np.asarray(top - got)
        if control:
            ctl = forward_rows(w, c, tokens, rows, fp8=True)
            pick = jnp.argmax(ctl, axis=-1)
            ctl_gap = np.asarray(top - jnp.take_along_axis(
                ref, pick[..., None], axis=-1)[..., 0])
            del ctl
        del ref
        for j, r in enumerate(idx):
            n = len(served[r])
            out["served"].append(served_gap[j, :n])
            if control:
                out["control"].append(ctl_gap[j, :n])
    return out
