"""Jit'd public wrappers: pick the Pallas kernel on TPU, the jnp reference
elsewhere (the CPU dry-run lowers the jnp path; interpret=True is for tests).
The models reach Pallas through ``rmsnorm`` and, for routed experts,
``ragged_dot_f32`` (megablox's grouped matmul); their projections go
through ``dense``, which is XLA's dot everywhere.
A call traced for a multi-device mesh also takes the reference: XLA cannot
partition a Mosaic kernel across devices, and GSPMD partitions the jnp
expression natively.

Wrappers also normalise shapes (padding to block multiples) so callers never
see tiling constraints, and resolve block shapes against the ambient
autotune winner table (`kernels.autotune`): an explicit caller arg wins,
then the tuned config for the problem signature, then the static default —
so `launch.train` / `launch.perf` / `serve` pick up tuned blocks with zero
call-site churn.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm as _gmm_fn

from . import autotune as _at
from . import flash_attention as _fa
from . import matmul as _mm
from . import paged_attention as _pa
from . import reduction as _red
from . import ref
from . import rmsnorm as _rms
from . import stencil as _st


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


#: every block choice made in this process: (kernel, shape, dtype) ->
#: (blocks, source), source one of "explicit", "tuned" (the ambient
#: autotune table) or "default" — what a chip run reports it compiled
resolved: dict[tuple, tuple[dict, str]] = {}


def _resolve(kernel, shape, dtype, **given):
    """Block-arg resolution: explicit args win, then the ambient autotune
    table, then `autotune.DEFAULTS`."""
    defaults = _at.DEFAULTS[kernel]
    source = "explicit"
    if any(v is None for v in given.values()):
        cfg = _at.tuned_config(kernel, shape, str(dtype)) or {}
        source = "tuned" if cfg else "default"
        given = {k: (v if v is not None else cfg.get(k, defaults[k]))
                 for k, v in given.items()}
    out = {k: int(v) for k, v in given.items()}
    resolved[(kernel, tuple(int(s) for s in shape), str(dtype))] = (out,
                                                                    source)
    return out


def _spans_devices(*operands) -> bool:
    """True when an operand is traced for (or placed on) a mesh of several
    devices — read off its abstract value, so no caller passes a flag."""
    return any(jax.typeof(x).sharding.mesh.size > 1 for x in operands)


def _mode(use_pallas, *operands):
    """use_pallas: None=auto (TPU, one device), True=pallas (interpret
    off-TPU), False=reference."""
    if use_pallas is None:
        return "pallas" if _on_tpu() and not _spans_devices(*operands) \
            else "ref"
    if use_pallas and not _on_tpu():
        return "interpret"
    return "pallas" if use_pallas else "ref"


def _pad_to(x, mult, axis):
    r = (-x.shape[axis]) % mult
    if r == 0:
        return x, 0
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, r)
    return jnp.pad(x, pad), r


def matmul(a, b, *, use_pallas=None, bm=None, bn=None, bk=None):
    m = _mode(use_pallas, a, b)
    if m == "ref":
        return ref.matmul(a, b)
    cfg = _resolve("matmul", (a.shape[0], a.shape[1], b.shape[1]), a.dtype,
                   bm=bm, bn=bn, bk=bk)
    bm, bn, bk = cfg["bm"], cfg["bn"], cfg["bk"]
    a, pm = _pad_to(a, bm, 0)
    a, pk = _pad_to(a, bk, 1)
    b, _ = _pad_to(b, bk, 0)
    b, pn = _pad_to(b, bn, 1)
    out = _mm.matmul(a, b, bm=bm, bn=bn, bk=bk, interpret=(m == "interpret"))
    return out[:out.shape[0] - pm or None, :out.shape[1] - pn or None] \
        if (pm or pn) else out


def jacobi2d(x, *, use_pallas=None, bh=None, bw=None):
    """x (H, W) unpadded; zero boundary (one sweep over the interior grid)."""
    xp = jnp.pad(x, 1)
    m = _mode(use_pallas)
    if m == "ref":
        return ref.jacobi2d(xp)
    H, W = x.shape
    cfg = _resolve("stencil", (H, W), x.dtype, bh=bh, bw=bw)
    bh, bw = cfg["bh"], cfg["bw"]
    bh = min(bh, H) if H % bh else bh
    while H % bh:
        bh -= 1
    bw_ = bw
    while W % bw_:
        bw_ //= 2
    bw_ = max(bw_, 1)
    return _st.jacobi2d(xp, bh=bh, bw=bw_, interpret=(m == "interpret"))


def fconv2d(x, filt, *, use_pallas=None, bh=None, bw=None):
    """valid conv: x (H, W), filt (fr, fc) -> (H-fr+1, W-fc+1)."""
    fr, fc = filt.shape
    m = _mode(use_pallas)
    if m == "ref":
        return ref.fconv2d(x, filt)
    H, W = x.shape[0] - fr + 1, x.shape[1] - fc + 1
    cfg = _resolve("stencil", (H, W), x.dtype, bh=bh, bw=bw)
    bh, bw = cfg["bh"], cfg["bw"]
    while H % bh:
        bh -= 1
    bw_ = bw
    while W % bw_ and bw_ > 1:
        bw_ -= 1
    return _st.fconv2d(x, filt, fr=fr, fc=fc, bh=bh, bw=bw_,
                       interpret=(m == "interpret"))


def dotprod(a, b, *, use_pallas=None, block=None):
    m = _mode(use_pallas)
    if m == "ref":
        return ref.dotprod(a, b)
    block = _resolve("reduction", (a.shape[0],), a.dtype,
                     block=block)["block"]
    quantum = 8 * block
    a, _ = _pad_to(a, quantum, 0)
    b, _ = _pad_to(b, quantum, 0)
    return _red.dotprod(a, b, block=block, interpret=(m == "interpret"))


def dotprod_hier(a, b, *, C, L, hierarchy="two-level", use_pallas=None,
                 block=256):
    """fdotproduct through the machine-level log-tree: per-lane Pallas
    partials combined intra-cluster then inter-cluster (or over the
    flattened ring with hierarchy="flat")."""
    m = _mode(use_pallas)
    if m == "ref":
        return ref.dotprod(a, b)
    quantum = C * L * 8 * block
    a, _ = _pad_to(a, quantum, 0)
    b, _ = _pad_to(b, quantum, 0)
    return _red.dotprod_hier(a, b, C=C, L=L, block=block, hierarchy=hierarchy,
                             interpret=(m == "interpret"))


def expv(x, *, use_pallas=None, block=2048):
    m = _mode(use_pallas)
    if m == "ref":
        return ref.expv(x)
    n = x.shape[0]
    quantum = 8 * block
    xp, r = _pad_to(x, quantum, 0)
    out = _red.expv(xp, block=block, interpret=(m == "interpret"))
    return out[:n]


def softmax_rows(x, *, use_pallas=None, bm=8):
    m = _mode(use_pallas)
    if m == "ref":
        return ref.softmax_rows(x)
    R = x.shape[0]
    while R % bm:
        bm -= 1
    return _red.softmax_rows(x, bm=bm, interpret=(m == "interpret"))


def attention(q, k, v, *, causal=True, window=None, use_pallas=None,
              bq=None, bk=None):
    m = _mode(use_pallas, q, k, v)
    if m == "ref":
        return ref.attention(q, k, v, causal=causal, window=window)
    B, Hq, S, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    cfg = _resolve("flash_attention", (B, Hq, Hkv, S, Sk, D), q.dtype,
                   bq=bq, bk=bk)
    bq, bk = cfg["bq"], cfg["bk"]
    bq = min(bq, S)
    while S % bq:
        bq //= 2
    bk_ = min(bk, Sk)
    while Sk % bk_:
        bk_ //= 2
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               bq=max(bq, 1), bk=max(bk_, 1),
                               interpret=(m == "interpret"))


def rmsnorm(x, gamma, *, eps=1e-6, use_pallas=None, bm=None):
    m = _mode(use_pallas, x, gamma)
    if m == "ref":
        return ref.rmsnorm(x, gamma, eps)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    R = x2.shape[0]
    bm = _resolve("rmsnorm", (R, shape[-1]), x.dtype, bm=bm)["bm"]
    out = _rms.rmsnorm(x2, gamma, bm=bm, eps=eps, interpret=(m == "interpret"))
    return out.reshape(shape)


def dense(x, w):
    """The models' projection seam: ``x @ w`` contracting the last dim, on
    every backend.  On one TPU XLA's dot beats the Pallas ``matmul`` at
    every projection shape the served models run, and inside the layer
    scan it reads the stacked weight in place, where a custom call takes a
    copy of each layer's slice (PERF.md §6)."""
    return x @ w


def einsum_f32(subscripts: str, a, b):
    """``jnp.einsum`` of two operands with a float32 result.  On a TPU the
    operands enter the MXU in their own type and the products accumulate
    in float32; elsewhere they are cast to float32 first, as XLA's CPU
    dot has no bfloat16 x bfloat16 = float32 form."""
    if _on_tpu():
        return jnp.einsum(subscripts, a, b,
                          preferred_element_type=jnp.float32)
    return jnp.einsum(subscripts, a.astype(jnp.float32),
                      b.astype(jnp.float32))


def ragged_dot_f32(x, w, group_sizes):
    """Grouped matmul with a float32 result: rows of ``x`` (M, K), sorted
    by group, times their group's ``w`` (G, K, N); ``group_sizes`` (G,)
    counts each group's rows.  Rows past their sum are undefined: the
    caller masks them.

    On one TPU it is the Pallas grouped matmul (megablox ``gmm``) with a
    whole group's (K, N) weight per tile, which visits only the groups
    that have rows: 2.8-3.1x XLA's ragged dot at a 512-row chunk's 3,072
    routed rows on a v5e (PERF.md §6).  Elsewhere XLA's ragged dot, its
    operands cast to float32 (:func:`einsum_f32`)."""
    if _on_tpu() and not _spans_devices(x, w):
        M, K = x.shape
        tm = 256 if M >= 2048 else 128
        xp = jnp.pad(x, ((0, -M % tm), (0, 0)))
        return _gmm_fn(xp, w, group_sizes, preferred_element_type=jnp.float32,
                       tiling=(tm, K, w.shape[-1]))[:M]
    return jax.lax.ragged_dot(x.astype(jnp.float32), w.astype(jnp.float32),
                              group_sizes)


def paged_attention(q, kpool, vpool, tables, lens, *, use_pallas=None):
    """Paged decode attention: q (B, Hkv, G, D) against a block pool
    (Hkv, NB, bt, D) through per-sequence block tables.  The ref path is
    the gather + masked-softmax expression the serving engine's decode
    layers inline; the Pallas path never materialises the gathered view
    (scalar-prefetched tables drive the DMA).  The block size is baked
    into the pool layout, so tuning happens where the pool is *sized*
    (``serve.paged`` / :func:`paged_block_tokens`), not per call."""
    m = _mode(use_pallas, q, kpool, vpool)
    if m == "ref":
        return ref.paged_attention(q, kpool, vpool, tables, lens)
    return _pa.paged_attention(q, kpool, vpool, tables, lens,
                               interpret=(m == "interpret"))


def paged_block_tokens(B, Hq, Hkv, T, D, dtype, *, default=16):
    """Tokens-per-block for a paged KV pool serving this decode signature:
    the tuned ``paged_attention`` bt when the autotune table has one, else
    ``default`` — lowered to a power-of-two divisor of T so the pool tiles
    ``max_seq`` exactly."""
    cfg = _at.tuned_config("paged_attention", (B, Hq, Hkv, T, D),
                           str(dtype)) or {}
    bt = max(1, min(int(cfg.get("bt", default)), T))
    while T % bt:
        bt //= 2
    return max(bt, 1)


def attention_q_chunk(S, T, H, Dh, dtype, *, default=512):
    """The q-block for the chunked-attention seam in `models.layers`: the
    tuned ``flash_attention`` bq for this problem signature when recorded,
    else ``default`` — lowered to a divisor of S (the chunked math is
    per-q-row independent, so any chunk size is bit-identical)."""
    cfg = _at.tuned_config("flash_attention", (1, H, H, S, T, Dh),
                           str(dtype)) or {}
    cq = max(1, min(int(cfg.get("bq", default)), S))
    while S % cq:
        cq -= 1
    return cq
