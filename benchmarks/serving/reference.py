"""The plain reference: a float32 ``jax.numpy`` forward of a dense
decoder (pre-norm RMSNorm, rotary multi-head attention, SwiGLU MLP),
written from the published architecture and nothing of the program.

It reads the weights in ``weights.py``'s layout and runs one layer at a
time, so that it fits on the chip beside them, with every matrix product
at ``highest`` precision (a TPU otherwise multiplies float32 in bfloat16).
Rotary embedding is the half-split form of the published code
(``rotate_half``, inverse frequencies ``theta ** (-2i / head_dim)``).

``fp8=True`` is the control: every projection and the head multiply
float8 (e4m3, one scale per tensor) operands, the precision below the
configuration's bfloat16.  A comparison that passes it is too loose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
              "w_up", "w_down")
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


@functools.partial(jax.jit, static_argnames=("c", "fp8"))
def _layer(x, w, i, c, fp8):
    """One decoder layer on x (B, L, d) float32."""
    B, L, d = x.shape
    H, Hkv = c.heads, c.kv_heads
    hd = d // H
    lw = {k: jax.lax.dynamic_index_in_dim(w[k], i, keepdims=False)
          for k in LAYER_KEYS}
    h = _rms(x, lw["attn_norm"], c.eps)
    q = _rope(_mm(h, lw["wq"], fp8).reshape(B, L, H, hd), c.theta)
    k = _rope(_mm(h, lw["wk"], fp8).reshape(B, L, Hkv, hd), c.theta)
    v = _mm(h, lw["wv"], fp8).reshape(B, L, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((L, L), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(B, L, H * hd)
    x = x + _mm(o, lw["wo"], fp8)
    h = _rms(x, lw["mlp_norm"], c.eps)
    g = _mm(h, lw["w_gate"], fp8)
    u = _mm(h, lw["w_up"], fp8)
    return x + _mm(g * jax.nn.sigmoid(g) * u, lw["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("c", "fp8"))
def _logits(x, rows, w, c, fp8):
    """Logits (B, R, vocab) float32 at positions ``rows`` (B, R)."""
    x = _rms(x, w["final_norm"], c.eps)
    x = jnp.take_along_axis(x, rows[..., None], axis=1)
    return _mm(x, w["head"][:, :c.vocab], fp8)


@functools.partial(jax.jit, static_argnames=("c",))
def _embed(tokens, w, c):
    return w["embed"][tokens].astype(jnp.float32)


class Dims:
    """The hashable sizes the jitted pieces specialise on."""

    def __init__(self, c: dict):
        self.heads = c["num_attention_heads"]
        self.kv_heads = c["num_key_value_heads"]
        self.layers = c["num_hidden_layers"]
        self.vocab = c["vocab_size"]
        self.eps = float(c["rms_norm_eps"])
        self.theta = float(c["rope_theta"])

    def _key(self):
        return (self.heads, self.kv_heads, self.layers, self.vocab,
                self.eps, self.theta)

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, Dims) and self._key() == other._key()


def forward_rows(w: dict, c: dict, tokens: np.ndarray, rows: np.ndarray,
                 *, fp8: bool = False) -> jax.Array:
    """Teacher-forced logits: ``tokens`` (B, L) int32 (padding after each
    sequence is harmless under the causal mask), ``rows`` (B, R) the
    positions whose next-token logits are wanted -> (B, R, vocab)."""
    dims = Dims(c)
    with jax.default_matmul_precision("highest"):
        x = _embed(jnp.asarray(tokens), w, dims)
        for i in range(dims.layers):
            x = _layer(x, w, jnp.int32(i), dims, fp8)
        return _logits(x, jnp.asarray(rows), w, dims, fp8)


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
