"""Model sublayers: GQA/SWA/cross attention, SwiGLU, MoE (EP), Mamba2 SSD.

All pure functions over param pytrees built from `PV` definitions
(`repro.parallel.sharding`).  Math in f32, storage in cfg.dtype.  Every
function has a train/prefill form and, where stateful, a decode form.

Sharding is by logical axes: batch -> (pod,data), heads/ff/experts/vocab ->
model (TP/EP), params FSDP over (pod,data).  Communication patterns map onto
the AraXL interconnects as described in DESIGN.md §2.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import substrate
from repro.configs.base import ATTN, MAMBA, MLA, MLP, MOE, XATTN, ModelConfig
from repro.kernels import ops as kops
from repro.parallel.sharding import PV, ShardingRules, constraint


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    # routed through kernels.ops so tuned block configs apply on TPU; the
    # off-TPU ref path is the same f32 rsqrt expression, bit for bit
    return kops.rmsnorm(x, g, eps=eps)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (..., S, H, Dh), positions (..., S) or (S,)."""
    half = x.shape[-1] // 2
    return rotate(x, positions,
                  theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half))


def rotate(x: jax.Array, positions: jax.Array, inv_freq,
           scale: float = 1.0) -> jax.Array:
    """Rotary embedding in the half-split layout at inverse frequencies
    ``inv_freq`` (Dh / 2,), cos and sin scaled by ``scale``."""
    half = x.shape[-1] // 2
    ang = positions[..., :, None].astype(jnp.float32) * inv_freq   # (..., S, half)
    ang = ang[..., :, None, :]                                     # broadcast heads
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term (``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: ModelConfig, dim: int) -> np.ndarray:
    """Inverse frequencies of a ``dim``-wide rotary part under YaRN
    (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``): pairs that turn
    more than ``yarn_beta_fast`` times over the original context keep
    their frequency, those that turn fewer than ``yarn_beta_slow`` times
    are interpolated by ``yarn_factor``, and a linear ramp blends the
    pairs between.  Plain rotary frequencies where the factor is 1."""
    theta = cfg.rope_theta
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.yarn_factor <= 1:
        return extra.astype(np.float32)

    def turns_dim(turns):
        return dim * math.log(cfg.yarn_original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(turns_dim(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp
    return (extra / cfg.yarn_factor * (1 - keep) + extra * keep
            ).astype(np.float32)


def yarn_cos_scale(cfg: ModelConfig) -> float:
    """The factor on cos and sin under YaRN (1 where mscale and
    mscale_all_dim agree, as in DeepSeek-V2)."""
    return yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale) \
        / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(q-k width), times YaRN's temperature squared where
    ``yarn_mscale_all_dim`` is set."""
    scale = cfg.qk_head_dim ** -0.5
    if cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def silu(x):
    return x * jax.nn.sigmoid(x)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def attn_defs(cfg: ModelConfig, cross: bool = False) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    dt = cfg.dtype
    return {
        "norm": PV((d,), jnp.float32, ("",), "ones"),
        "wq": PV((d, cfg.n_heads * hd), dt, ("fsdp", "model")),
        "wk": PV((d, cfg.n_kv_heads * hd), dt, ("fsdp", "model")),
        "wv": PV((d, cfg.n_kv_heads * hd), dt, ("fsdp", "model")),
        "wo": PV((cfg.n_heads * hd, d), dt, ("model", "fsdp")),
    }


@jax.named_scope("attn.qkv")
def _qkv(p, x, cfg: ModelConfig, rules, positions, rotate: bool):
    B, S, _ = x.shape
    hd = cfg.head_dim
    xn = rmsnorm(x, p["norm"], cfg.norm_eps)
    # constrain the flat projections (always divisible by |model|), then
    # reshape to heads — kv-head counts below |model| (glm4: kv=2) stay
    # shardable on the fused dim.
    qf = constraint(kops.dense(xn, p["wq"]), rules, "batch", None, "model")
    kf = constraint(kops.dense(xn, p["wk"]), rules, "batch", None, "model")
    vf = constraint(kops.dense(xn, p["wv"]), rules, "batch", None, "model")
    # On TPU the split into heads is no bitcast of the tiled (rows, N)
    # output, and XLA would fold it into the dots by copying each layer's
    # weight transposed; the barrier keeps the dots plain and moves the
    # activations instead (PERF.md §6).
    qf, kf, vf = jax.lax.optimization_barrier((qf, kf, vf))
    q = qf.reshape(B, S, cfg.n_heads, hd)
    k = kf.reshape(B, S, cfg.n_kv_heads, hd)
    v = vf.reshape(B, S, cfg.n_kv_heads, hd)
    if rotate:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _expand_kv(k, H: int, rules: ShardingRules):
    """Repeat kv heads up to H so the head dim shards cleanly over `model`
    even for sub-|model| kv counts (glm4: kv=2)."""
    Hkv = k.shape[2]
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=2)
    return constraint(k, rules, "batch", None, "model", None)


def _sdpa_chunked(q, k, v, cfg: ModelConfig, rules: ShardingRules, *,
                  causal: bool, q_offset: int = 0,
                  q_chunk: int | None = None) -> jax.Array:
    """Exact chunked attention: scan over q blocks against full K/V.

    f32 softmax; causal + sliding-window masks; the chunk body is
    checkpointed so backward recomputes score blocks instead of saving
    every softmax matrix (flash-style memory behaviour in pure XLA).
    The q-block size comes from the autotune table via
    `kernels.ops.attention_q_chunk` (chunking is per-q-row independent, so
    any block size is bit-identical).
    q (B,S,H,Dh), k/v (B,T,Hkv,Dh) -> (B,S,H,Dh)."""
    B, S, H, Dh = q.shape
    T = k.shape[1]
    scale = 1.0 / math.sqrt(Dh)
    q = constraint(q, rules, "batch", None, "model", None)
    k = _expand_kv(k, H, rules)
    v = _expand_kv(v, H, rules)
    if q_chunk is not None:                   # explicit caller choice wins
        cq = min(q_chunk, S)
        while S % cq:
            cq -= 1
    else:
        cq = kops.attention_q_chunk(S, T, H, Dh, q.dtype)
    n_chunks = S // cq
    k_pos = jnp.arange(T)

    def block(carry, qc_off):
        qc, off = qc_off
        s = jnp.einsum("bqhd,bthd->bhqt", qc.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        q_pos = off + q_offset + jnp.arange(cq)
        mask = jnp.ones((cq, T), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if cfg.window is not None:
            mask &= (q_pos[:, None] - k_pos[None, :]) < cfg.window
        s = jnp.where(mask[None, None], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqt,bthd->bqhd", pr, v.astype(jnp.float32))
        return carry, o.astype(q.dtype)

    qs = q.reshape(B, n_chunks, cq, H, Dh).transpose(1, 0, 2, 3, 4)
    offs = jnp.arange(n_chunks) * cq
    _, outs = jax.lax.scan(jax.checkpoint(block), None, (qs, offs))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, S, H, Dh)
    return out


def attn_layer(p, x, cfg: ModelConfig, rules: ShardingRules, positions,
               *, causal: bool = True) -> jax.Array:
    """Training / prefill self-attention (residual included)."""
    B, S, d = x.shape
    q, k, v = _qkv(p, x, cfg, rules, positions, rotate=True)
    with jax.named_scope("attn.core"):
        o = _sdpa_chunked(q, k, v, cfg, rules, causal=causal)
    with jax.named_scope("attn.out"):
        o = kops.dense(o.reshape(B, S, cfg.n_heads * cfg.head_dim), p["wo"])
        o = constraint(o, rules, "batch", None, None)
        return x + o.astype(x.dtype)


class AttnCache(NamedTuple):
    k: jax.Array          # (B, W, Hkv, Dh) — pre-rotated keys
    v: jax.Array


def attn_cache_len(cfg: ModelConfig, seq_len: int) -> int:
    return min(seq_len, cfg.window) if cfg.window else seq_len


def attn_cache_defs(cfg: ModelConfig, batch: int, seq_len: int) -> AttnCache:
    W = attn_cache_len(cfg, seq_len)
    shp = (batch, W, cfg.n_kv_heads, cfg.head_dim)
    return AttnCache(
        PV(shp, cfg.dtype, ("batch", "cache_seq", "kv", ""), "zeros"),
        PV(shp, cfg.dtype, ("batch", "cache_seq", "kv", ""), "zeros"))


def attn_layer_decode(p, x, cache: AttnCache, pos, cfg: ModelConfig,
                      rules: ShardingRules):
    """One-token step. pos: scalar int32 (shared position) or (B,) int32
    (per-slot true positions — the serving engine's continuous batch, where
    slots sit at different depths).

    Full-attention caches index directly; SWA caches are ring buffers of
    length `window` (entry i holds the newest position ≡ i mod W).  For a
    batch whose per-slot positions are all equal, the vector path is
    bit-identical to the scalar path (same writes, same masks, same
    reduction order)."""
    B, S1, d = x.shape                      # S1 == 1
    W = cache.k.shape[1]
    hd = cfg.head_dim
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1
    if per_slot:
        positions = pos[:, None]            # (B, 1) — rope broadcasts
    else:
        positions = (jnp.full((S1,), 0) + pos)[None, :]
    q, k, v = _qkv(p, x, cfg, rules, positions, rotate=True)
    slot = pos % W
    mesh = rules.mesh
    dist_cache = mesh is not None and rules.axis("cache_seq") == "model"
    if dist_cache and per_slot:
        raise NotImplementedError(
            "per-slot decode positions are not supported with the "
            "model-sharded (cache_seq) distributed cache path")
    if not dist_cache:
        if per_slot:
            # scatter each batch row at its own ring slot (rows distinct
            # by construction: one write per batch element)
            ck = cache.k.at[jnp.arange(B), slot].set(
                k[:, 0].astype(cache.k.dtype))
            cv = cache.v.at[jnp.arange(B), slot].set(
                v[:, 0].astype(cache.v.dtype))
        else:
            ck = jax.lax.dynamic_update_slice(cache.k, k, (0, slot, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache.v, v, (0, slot, 0, 0))
        ck = constraint(ck, rules, "batch", "cache_seq", "kv", None)
        cv = constraint(cv, rules, "batch", "cache_seq", "kv", None)

    def _scores_out(qg, ckb, cvb, idx, pos_):
        """Local masked scores + (m, l, o) partials for index slice idx.

        pos_ may be a scalar (mask over (W,)) or a (B,) vector (per-slot
        mask over (B, W))."""
        pos_c = pos_[:, None] if pos_.ndim == 1 else pos_
        if cfg.window:
            k_pos = pos_c - ((pos_c - idx) % W)  # newest position ≡ i (mod W)
            valid = k_pos >= 0
        else:
            k_pos = idx
            valid = k_pos <= pos_c
        s = jnp.einsum("bqhgd,bthd->bhgqt", qg.astype(jnp.float32),
                       ckb.astype(jnp.float32)) / math.sqrt(hd)
        mask = valid & (k_pos <= pos_c)
        if cfg.window:
            mask &= (pos_c - k_pos) < cfg.window
        if mask.ndim == 2:                  # (B, W) per-slot mask
            s = jnp.where(mask[:, None, None, None, :], s, -1e30)
        else:
            s = jnp.where(mask[None, None, None, None, :], s, -1e30)
        return s, cvb.astype(jnp.float32)

    G = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(B, S1, cfg.n_kv_heads, G, hd)
    if dist_cache:
        # distributed decode attention: each model shard WRITES the new
        # token into its cache slice if the slot falls in range (no
        # replicate-and-reshard of the cache), scores its slice, and the
        # softmax is merged with tiny pmax/psum collectives — AraXL's
        # inter-cluster log-tree reduction (never gather the cache).
        W_loc = W // mesh.shape["model"]
        cspec = rules.spec(("batch", "cache_seq", "kv", ""))

        def body(qg_, ckb, cvb, kb, vb, pos_):
            base = substrate.axis_index("model") * W_loc
            sl = pos_ % W
            ls = jnp.clip(sl - base, 0, W_loc - 1)
            inrange = (sl >= base) & (sl < base + W_loc)
            ck_new = jnp.where(
                inrange,
                jax.lax.dynamic_update_slice(ckb, kb, (0, ls, 0, 0)), ckb)
            cv_new = jnp.where(
                inrange,
                jax.lax.dynamic_update_slice(cvb, vb, (0, ls, 0, 0)), cvb)
            idx = base + jnp.arange(W_loc)
            s, cvf = _scores_out(qg_, ck_new, cv_new, idx, pos_)
            m = jax.lax.pmax(jnp.max(s, axis=-1, keepdims=True), "model")
            pr = jnp.exp(s - m)
            l = jax.lax.psum(jnp.sum(pr, axis=-1, keepdims=True), "model")
            o = jax.lax.psum(
                jnp.einsum("bhgqt,bthd->bqhgd", pr, cvf), "model")
            ln = jnp.maximum(l, 1e-20).squeeze(-1).transpose(0, 3, 1, 2)
            return o / ln[..., None], ck_new, cv_new

        bq = rules.spec(("batch", "", "", "", ""))
        bk = rules.spec(("batch", "", "", ""))
        o, ck, cv = substrate.shard_map(
            body, mesh=mesh,
            in_specs=(bq, cspec, cspec, bk, bk, P()),
            out_specs=(bq, cspec, cspec))(
                qg, cache.k, cache.v, k.astype(cache.k.dtype),
                v.astype(cache.v.dtype), jnp.asarray(pos, jnp.int32))
    else:
        s, cvf = _scores_out(qg, ck, cv, jnp.arange(W), pos)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqt,bthd->bqhgd", pr, cvf)
    o = kops.dense(o.reshape(B, S1, cfg.n_heads * hd).astype(x.dtype),
                   p["wo"])
    return x + o.astype(x.dtype), AttnCache(ck, cv)


def attn_layer_prefill(p, x, cfg: ModelConfig, rules, positions, cache_len):
    """Prefill: run attention AND return the populated cache."""
    B, S, _ = x.shape
    q, k, v = _qkv(p, x, cfg, rules, positions, rotate=True)
    with jax.named_scope("attn.core"):
        o = _sdpa_chunked(q, k, v, cfg, rules, causal=True)
    with jax.named_scope("attn.out"):
        o = kops.dense(o.reshape(B, S, cfg.n_heads * cfg.head_dim), p["wo"])
    W = cache_len
    with jax.named_scope("attn.kv_write"):
        if W >= S:
            pad = [(0, 0), (0, W - S), (0, 0), (0, 0)]
            ck, cv = jnp.pad(k, pad), jnp.pad(v, pad)
        else:                               # SWA ring buffer: last W tokens,
            tail_k, tail_v = k[:, S - W:], v[:, S - W:]  # at slot pos % W
            roll = (S - W) % W
            ck = jnp.roll(tail_k, shift=roll, axis=1)
            cv = jnp.roll(tail_v, shift=roll, axis=1)
    with jax.named_scope("attn.out"):
        return x + o.astype(x.dtype), AttnCache(ck, cv)


# -- paged attention (block-table KV pool) -----------------------------------
#
# The serving analogue of AraXL's VRF chunk map: K/V live in a shared pool
# of fixed-size token blocks, each request holds a table of block ids, and
# attention gathers through the table.  Block 0 is a permanent zero block —
# unallocated table entries gather exact zeros, which is what the dense
# cache's unwritten rows hold, so paged decode is bit-identical to the
# dense engine.  Full attention only (no SWA ring) — the paged engine
# rejects windowed configs.
#
# Each pool leaf is (n, NB, bt, *row): a stack's n layers, and in each
# block bt token rows, each a token's keys (or values, or latent) in
# whole 128-lane vector registers (:func:`kv_row`, :func:`pool_row`).
# Given a minor dimension that is not whole registers, the TPU keeps an
# array with its block axis minor-most, and each program would copy the
# whole pool into row-major order and back.  A program reads and writes
# a layer's rows in place, by its index in the stack, and whole: the
# TPU's gather and scatter of part of a row are loops, and the lanes
# past a token's values are zero.

#: lanes of a TPU vector register
LANES = 128


def pool_row(width: int) -> int:
    """Lanes of a pool row that holds ``width`` values."""
    return -(-width // LANES) * LANES


def kv_row(cfg: ModelConfig) -> tuple[int, ...]:
    """Shape of one token's keys (or values) in the paged pool: its heads
    as they are where a head fills whole registers (the gathered context
    is then laid out for attention as it comes), else all heads in one
    row of whole registers (phi3's 32 heads of 96 in 3,072 lanes, not
    padded to 128 each: on the TPU the padded heads compile to float32
    casts of the whole context, the one row to a cheaper bf16 relayout)."""
    if cfg.head_dim % LANES == 0:
        return (cfg.n_kv_heads, cfg.head_dim)
    return (pool_row(cfg.n_kv_heads * cfg.head_dim),)


def whole_rows(x, pool, lead: int):
    """Values x (*lead, ...) as whole rows of the pool leaf ``pool``
    (n, NB, bt, *row): (*lead, *row), zeros in the lanes past x's."""
    row = pool.shape[3:]
    if x.shape[lead:] == row:
        return x
    flat = x.reshape(x.shape[:lead] + (-1,))
    pad = math.prod(row) - flat.shape[-1]
    return jnp.pad(flat, [(0, 0)] * lead + [(0, pad)]).reshape(
        x.shape[:lead] + row)


def _context(rows, lead: tuple[int, ...], values: tuple[int, ...]):
    """Gathered pool rows (..., *row) -> (*lead, *values): each token's
    values.  A row of whole registers loses the lanes past them before
    its blocks merge into positions, where the TPU keeps the slice in
    place (a bitcast) for the ops that read the context."""
    if rows.shape[-len(values):] != values:
        rows = rows[..., :math.prod(values)]
    return rows.reshape(lead + values)


def attn_layer_decode_paged(p, x, pk, pv, i, tables, pos, live,
                            cfg: ModelConfig, rules: ShardingRules):
    """One-token decode against a block-table paged KV pool.

    pk/pv (n, NB, bt, *row) — the stack's shared block pool, of which
    this layer owns ``pk[i]``, a token's Hkv x Dh keys in its row
    (:func:`kv_row`; block 0 is the reserved zero block, never written by
    a live slot); tables (B, max_blocks) int32; pos (B,) per-slot
    positions; live (B,) bool.  The new rows go in with one scatter into
    the stacked leaf and the context comes out with one gather that
    carries ``i``, so no layer's pool is sliced out; rows are written and
    read whole.  Dead slots write a predicated no-op (they re-write the
    zero block's current value) so the batched step stays shape-stable.
    The gathered view ``_context(pk[i, tables], ...)`` is elementwise
    identical to the dense cache rows, and the math below is the same
    expression as :func:`attn_layer_decode`'s vector-pos path —
    bit-identical streams."""
    B, S1, d = x.shape                      # S1 == 1
    bt = pk.shape[2]
    Hkv, hd = cfg.n_kv_heads, cfg.head_dim
    W = tables.shape[1] * bt
    q, k, v = _qkv(p, x, cfg, rules, pos[:, None], rotate=True)
    with jax.named_scope("attn.kv_write"):
        blk = jnp.take_along_axis(tables, (pos // bt)[:, None], axis=1)[:, 0]
        off = pos % bt
        live_rows = live.reshape((B,) + (1,) * (pk.ndim - 3))
        nk = jnp.where(live_rows,
                       whole_rows(k[:, 0].astype(pk.dtype), pk, 1),
                       pk[i, blk, off])
        nv = jnp.where(live_rows,
                       whole_rows(v[:, 0].astype(pv.dtype), pv, 1),
                       pv[i, blk, off])
        pk = pk.at[i, blk, off].set(nk)
        pv = pv.at[i, blk, off].set(nv)
    with jax.named_scope("attn.core"):
        ck = _context(pk[i, tables], (B, W), (Hkv, hd))
        cv = _context(pv[i, tables], (B, W), (Hkv, hd))
        ck = constraint(ck, rules, "batch", None, "kv", None)
        cv = constraint(cv, rules, "batch", None, "kv", None)
        G = cfg.n_heads // cfg.n_kv_heads
        qg = q.reshape(B, S1, Hkv, G, hd)
        idx = jnp.arange(W)
        s = jnp.einsum("bqhgd,bthd->bhgqt", qg.astype(jnp.float32),
                       ck.astype(jnp.float32)) / math.sqrt(hd)
        mask = idx <= pos[:, None]                     # (B, W) causal
        s = jnp.where(mask[:, None, None, None, :], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqt,bthd->bqhgd", pr, cv.astype(jnp.float32))
    with jax.named_scope("attn.out"):
        o = kops.dense(o.reshape(B, S1, cfg.n_heads * hd).astype(x.dtype),
                       p["wo"])
        return x + o.astype(x.dtype), pk, pv


def attn_layer_prefill_paged(p, x, pk, pv, i, table_row, start, valid,
                             cfg: ModelConfig, rules: ShardingRules):
    """One prefill *chunk* (B == 1) against the paged pool.

    x (1, c, d) is the embedded chunk, padded to the fixed chunk length c;
    pk/pv the stacked pool leaves and ``i`` this layer's index in them, as
    in :func:`attn_layer_decode_paged`; ``valid`` counts real tokens,
    ``start`` is the chunk's base position (a multiple of the block
    size).  The chunk's K/V are scattered whole blocks at a time into the
    pre-allocated blocks of ``table_row`` (padding rows zeroed first, so
    the zero block stays zero even when the tail of the slice lands on
    unallocated entries), then the chunk attends causally over the full
    gathered view — earlier chunks' blocks are already resident, which is
    what makes chunked prefill exact."""
    B, c, d = x.shape                       # B == 1
    bt = pk.shape[2]
    Hkv, hd = cfg.n_kv_heads, cfg.head_dim
    W = table_row.shape[0] * bt
    positions = start + jnp.arange(c)
    q, k, v = _qkv(p, x, cfg, rules, positions[None, :], rotate=True)
    with jax.named_scope("attn.kv_write"):
        ok = (jnp.arange(c) < valid)[None, :, None, None]
        kz = jnp.where(ok, k, 0).astype(pk.dtype)
        vz = jnp.where(ok, v, 0).astype(pv.dtype)
        nblk = c // bt
        bids = jax.lax.dynamic_slice(table_row, (start // bt,), (nblk,))
        pk = pk.at[i, bids].set(
            whole_rows(kz[0].reshape(nblk, bt, Hkv, hd), pk, 2))
        pv = pv.at[i, bids].set(
            whole_rows(vz[0].reshape(nblk, bt, Hkv, hd), pv, 2))
    with jax.named_scope("attn.core"):
        ck = _context(pk[i, table_row], (1, W), (Hkv, hd))
        cv = _context(pv[i, table_row], (1, W), (Hkv, hd))
        ck = constraint(ck, rules, "batch", None, "kv", None)
        cv = constraint(cv, rules, "batch", None, "kv", None)
        G = cfg.n_heads // cfg.n_kv_heads
        qg = q.reshape(B, c, Hkv, G, hd)
        mask = jnp.arange(W)[None, :] <= positions[:, None]  # (c, W) causal
        s = jnp.einsum("bqhgd,bthd->bhgqt", qg.astype(jnp.float32),
                       ck.astype(jnp.float32)) / math.sqrt(hd)
        s = jnp.where(mask[None, None, None], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqt,bthd->bqhgd", pr, cv.astype(jnp.float32))
    with jax.named_scope("attn.out"):
        o = kops.dense(o.reshape(B, c, cfg.n_heads * hd).astype(x.dtype),
                       p["wo"])
        return x + o.astype(x.dtype), pk, pv


# -- multi-head latent attention (DeepSeek-V2) -------------------------------
#
# Keys and values come from one normalised latent of kv_lora_rank per token
# (kv_b expands it to every head's key and value) plus a roped key of
# qk_rope_head_dim shared by every head.  The cache holds those 576 values
# per token (kv_lora_rank 512 + 64), not the heads' keys and values.  The
# program attends *absorbed*: kv_b's key half is folded into each query, so
# scores are taken against the cached rows directly, and kv_b's value half
# is applied once to the weighted sum of latents.  The plain reference
# (benchmarks/serving/families/deepseek_v2.py) expands keys and values as
# published; the two agree up to rounding.

def mla_defs(cfg: ModelConfig) -> dict:
    d, H, r, dt = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank, cfg.dtype
    return {
        "norm": PV((d,), jnp.float32, ("",), "ones"),
        "wq": PV((d, H * cfg.qk_head_dim), dt, ("fsdp", "model")),
        "wkv_a": PV((d, cfg.latent_dim), dt, ("fsdp", "")),
        "kv_norm": PV((r,), jnp.float32, ("",), "ones"),
        "wkv_b": PV((r, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), dt,
                    ("", "model")),
        "wo": PV((H * cfg.v_head_dim, d), dt, ("model", "fsdp")),
    }


def _kv_b(p, cfg: ModelConfig):
    """kv_b as (rank, heads, nope + v): its key half and value half."""
    w = p["wkv_b"].reshape(cfg.kv_lora_rank, cfg.n_heads,
                           cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


@jax.named_scope("attn.qkv")
def _mla_qkv(p, x, cfg: ModelConfig, positions):
    """-> absorbed queries (B, S, H, latent_dim) float32 and the rows the
    cache holds, (B, S, latent_dim) in cfg.dtype: the normalised latent
    and the roped shared key."""
    B, S, _ = x.shape
    H, nope, r = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    xn = rmsnorm(x, p["norm"], cfg.norm_eps)
    q = kops.dense(xn, p["wq"])
    kv = kops.dense(xn, p["wkv_a"])
    q, kv = jax.lax.optimization_barrier((q, kv))
    q = q.reshape(B, S, H, cfg.qk_head_dim)
    inv = yarn_inv_freq(cfg, cfg.qk_rope_head_dim)
    scale = yarn_cos_scale(cfg)
    q_pe = rotate(q[..., nope:], positions, inv, scale)
    k_pe = rotate(kv[..., None, r:], positions, inv, scale)[..., 0, :]
    c = rmsnorm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    w_k, _ = _kv_b(p, cfg)
    q_lat = kops.einsum_f32("bshn,rhn->bshr", q[..., :nope], w_k)
    qa = jnp.concatenate([q_lat, q_pe.astype(jnp.float32)], axis=-1)
    rows = jnp.concatenate([c.astype(cfg.dtype), k_pe.astype(cfg.dtype)],
                           axis=-1)
    return qa, rows


def _mla_attend(qa, ctx, mask, cfg: ModelConfig):
    """Absorbed attention of queries qa (B, S, H, C) over cached rows ctx
    (B, T, C) under mask (B, S, T) -> latents (B, S, H, rank) float32."""
    s = kops.einsum_f32("bshc,btc->bhst", qa.astype(ctx.dtype), ctx) \
        * mla_softmax_scale(cfg)
    s = jnp.where(mask[:, None], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    return kops.einsum_f32("bhst,btr->bshr", pr.astype(ctx.dtype),
                           ctx[..., :cfg.kv_lora_rank])


@jax.named_scope("attn.out")
def _mla_out(p, x, o_lat, cfg: ModelConfig):
    """kv_b's value half, then o, then the residual."""
    B, S = o_lat.shape[:2]
    _, w_v = _kv_b(p, cfg)
    o = kops.einsum_f32("bshr,rhv->bshv", o_lat.astype(w_v.dtype), w_v)
    o = kops.dense(o.reshape(B, S, -1).astype(x.dtype), p["wo"])
    return x + o.astype(x.dtype)


def mla_layer(p, x, cfg: ModelConfig, rules: ShardingRules, positions):
    """Training / whole-sequence latent attention (causal, residual
    included)."""
    qa, rows = _mla_qkv(p, x, cfg, positions)
    with jax.named_scope("attn.core"):
        S = x.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))[None]
        o = _mla_attend(qa, rows, mask, cfg)
    return _mla_out(p, x, o, cfg)


def mla_cache_defs(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    return {"c": PV((batch, seq_len, cfg.latent_dim), cfg.dtype,
                    ("batch", "cache_seq", ""), "zeros")}


def mla_layer_prefill(p, x, cfg: ModelConfig, rules, positions,
                      cache_len: int):
    """Prefill: latent attention and the cache (B, cache_len, C)."""
    S = x.shape[1]
    qa, rows = _mla_qkv(p, x, cfg, positions)
    with jax.named_scope("attn.core"):
        o = _mla_attend(qa, rows, jnp.tril(jnp.ones((S, S), bool))[None],
                        cfg)
    with jax.named_scope("attn.kv_write"):
        c = jnp.pad(rows, [(0, 0), (0, cache_len - S), (0, 0)])
    return _mla_out(p, x, o, cfg), {"c": c}


def mla_layer_decode(p, x, cache: dict, pos, cfg: ModelConfig,
                     rules: ShardingRules):
    """One-token step over a dense latent cache; pos scalar or (B,)."""
    B = x.shape[0]
    W = cache["c"].shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    qa, rows = _mla_qkv(p, x, cfg, pos[:, None])
    with jax.named_scope("attn.kv_write"):
        c = cache["c"].at[jnp.arange(B), pos].set(rows[:, 0])
    with jax.named_scope("attn.core"):
        mask = (jnp.arange(W)[None, :] <= pos[:, None])[:, None, :]
        o = _mla_attend(qa, c, mask, cfg)
    return _mla_out(p, x, o, cfg), {"c": c}


def mla_layer_decode_paged(p, x, pool, i, tables, pos, live,
                           cfg: ModelConfig, rules: ShardingRules):
    """One-token decode against a paged latent pool (n, NB, bt, R), of
    which this layer owns ``pool[i]``, a token's C latent values in the
    first lanes of its row: the counterpart of
    :func:`attn_layer_decode_paged` (one scatter and one gather into the
    stacked leaf, block 0 the zero block, dead slots rewrite what they
    read)."""
    B = x.shape[0]
    bt, C = pool.shape[2], cfg.latent_dim
    W = tables.shape[1] * bt
    qa, rows = _mla_qkv(p, x, cfg, pos[:, None])
    with jax.named_scope("attn.kv_write"):
        blk = jnp.take_along_axis(tables, (pos // bt)[:, None], axis=1)[:, 0]
        off = pos % bt
        new = jnp.where(live[:, None],
                        whole_rows(rows[:, 0].astype(pool.dtype), pool, 1),
                        pool[i, blk, off])
        pool = pool.at[i, blk, off].set(new)
    with jax.named_scope("attn.core"):
        ctx = _context(pool[i, tables], (B, W), (C,))
        mask = (jnp.arange(W)[None, :] <= pos[:, None])[:, None, :]
        o = _mla_attend(qa, ctx, mask, cfg)
    return _mla_out(p, x, o, cfg), pool


def mla_layer_prefill_paged(p, x, pool, i, table_row, start, valid,
                            cfg: ModelConfig, rules: ShardingRules):
    """One prefill chunk (B == 1) against the stacked paged latent pool
    at layer ``i``: the counterpart of :func:`attn_layer_prefill_paged`."""
    _, c, _ = x.shape
    bt, C = pool.shape[2], cfg.latent_dim
    W = table_row.shape[0] * bt
    positions = start + jnp.arange(c)
    qa, rows = _mla_qkv(p, x, cfg, positions[None, :])
    with jax.named_scope("attn.kv_write"):
        ok = (jnp.arange(c) < valid)[:, None]
        rz = jnp.where(ok, rows[0], 0).astype(pool.dtype)
        nblk = c // bt
        bids = jax.lax.dynamic_slice(table_row, (start // bt,), (nblk,))
        pool = pool.at[i, bids].set(
            whole_rows(rz.reshape(nblk, bt, C), pool, 2))
    with jax.named_scope("attn.core"):
        ctx = _context(pool[i, table_row], (1, W), (C,))
        mask = (jnp.arange(W)[None, :] <= positions[:, None])[None]
        o = _mla_attend(qa, ctx, mask, cfg)
    return _mla_out(p, x, o, cfg), pool


# -- cross attention ---------------------------------------------------------

def xattn_defs(cfg: ModelConfig) -> dict:
    return attn_defs(cfg, cross=True)


def xattn_layer(p, x, ctx, cfg: ModelConfig, rules: ShardingRules):
    """Cross-attention to a context (encoder output / image embeddings).
    ctx (B, T, d); no positional rotation (learned content addressing)."""
    B, S, d = x.shape
    hd = cfg.head_dim
    xn = rmsnorm(x, p["norm"], cfg.norm_eps)
    q = kops.dense(xn, p["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = kops.dense(ctx, p["wk"]).reshape(B, ctx.shape[1], cfg.n_kv_heads, hd)
    v = kops.dense(ctx, p["wv"]).reshape(B, ctx.shape[1], cfg.n_kv_heads, hd)
    o = _sdpa_chunked(q, k, v, cfg, rules, causal=False)
    o = kops.dense(o.reshape(B, S, cfg.n_heads * hd), p["wo"])
    return x + o.astype(x.dtype)


class XAttnCache(NamedTuple):
    k: jax.Array          # (B, T, Hkv, Dh) — projected context, fixed
    v: jax.Array


def xattn_cache_defs(cfg: ModelConfig, batch: int) -> XAttnCache:
    shp = (batch, cfg.n_ctx_tokens, cfg.n_kv_heads, cfg.head_dim)
    return XAttnCache(PV(shp, cfg.dtype, ("batch", "", "kv", ""), "zeros"),
                      PV(shp, cfg.dtype, ("batch", "", "kv", ""), "zeros"))


def xattn_prefill_cache(p, ctx, cfg: ModelConfig) -> XAttnCache:
    B, T, _ = ctx.shape
    hd = cfg.head_dim
    k = kops.dense(ctx, p["wk"]).reshape(B, T, cfg.n_kv_heads, hd)
    v = kops.dense(ctx, p["wv"]).reshape(B, T, cfg.n_kv_heads, hd)
    return XAttnCache(k, v)


def xattn_layer_decode(p, x, cache: XAttnCache, cfg: ModelConfig,
                       rules: ShardingRules):
    B, S1, d = x.shape
    hd = cfg.head_dim
    xn = rmsnorm(x, p["norm"], cfg.norm_eps)
    q = kops.dense(xn, p["wq"]).reshape(B, S1, cfg.n_heads, hd)
    G = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(B, S1, cfg.n_kv_heads, G, hd)
    s = jnp.einsum("bqhgd,bthd->bhgqt", qg.astype(jnp.float32),
                   cache.k.astype(jnp.float32)) / math.sqrt(hd)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqt,bthd->bqhgd", pr, cache.v.astype(jnp.float32))
    o = kops.dense(o.reshape(B, S1, cfg.n_heads * hd).astype(x.dtype),
                   p["wo"])
    return x + o.astype(x.dtype), cache


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------

def mlp_defs(cfg: ModelConfig) -> dict:
    d, f, dt = cfg.d_model, cfg.d_ff, cfg.dtype
    return {
        "norm": PV((d,), jnp.float32, ("",), "ones"),
        "wi": PV((d, f), dt, ("fsdp", "model")),
        "wg": PV((d, f), dt, ("fsdp", "model")),
        "wo": PV((f, d), dt, ("model", "fsdp")),
    }


@jax.named_scope("mlp")
def mlp_layer(p, x, cfg: ModelConfig, rules: ShardingRules):
    xn = rmsnorm(x, p["norm"], cfg.norm_eps)
    h = silu(kops.dense(xn, p["wg"])) * kops.dense(xn, p["wi"])
    h = constraint(h, rules, "batch", None, "model")
    o = kops.dense(h, p["wo"])
    return x + o.astype(x.dtype)


# ---------------------------------------------------------------------------
# MoE — top-k routing, capacity dispatch, expert parallelism over `model`
# ---------------------------------------------------------------------------

def _shared_defs(cfg: ModelConfig, defs: dict) -> dict:
    """Adds the shared experts, one SwiGLU of n_shared x d_ff_expert."""
    if cfg.n_shared_experts:
        f = cfg.n_shared_experts * (cfg.d_ff_expert or cfg.d_ff)
        d, dt = cfg.d_model, cfg.dtype
        defs["shared"] = {"wi": PV((d, f), dt, ("fsdp", "model")),
                          "wg": PV((d, f), dt, ("fsdp", "model")),
                          "wo": PV((f, d), dt, ("model", "fsdp"))}
    return defs


def moe_defs(cfg: ModelConfig) -> dict:
    d, dt = cfg.d_model, cfg.dtype
    E = cfg.n_experts
    ffe = cfg.d_ff_expert or cfg.d_ff
    # expert dim over `model` when divisible (EP), else ff dim (expert-TP)
    return _shared_defs(cfg, {
        "norm": PV((d,), jnp.float32, ("",), "ones"),
        "router": PV((d, E), jnp.float32, ("fsdp", "")),
        "wi": PV((E, d, ffe), dt, ("model", "fsdp", "")),
        "wg": PV((E, d, ffe), dt, ("model", "fsdp", "")),
        "wo": PV((E, ffe, d), dt, ("model", "", "fsdp")),
    })


def moe_defs_tp(cfg: ModelConfig) -> dict:
    d, dt = cfg.d_model, cfg.dtype
    E = cfg.n_experts
    ffe = cfg.d_ff_expert or cfg.d_ff
    return _shared_defs(cfg, {
        "norm": PV((d,), jnp.float32, ("",), "ones"),
        "router": PV((d, E), jnp.float32, ("fsdp", "")),
        "wi": PV((E, d, ffe), dt, ("", "fsdp", "model")),
        "wg": PV((E, d, ffe), dt, ("", "fsdp", "model")),
        "wo": PV((E, ffe, d), dt, ("", "model", "fsdp")),
    })


def _model_axes(rules: ShardingRules) -> tuple:
    """Mesh axes the logical `model` (TP/EP) axis maps to, flattened.  A
    plain production mesh gives ("model",); a Topology-driven hierarchical
    mesh may map `model` to several level axes (e.g. ("pod", "data",
    "model")) treated as one outer-major expert ring."""
    if rules.mesh is None:
        return ()
    ax = rules.axis("model")
    if ax is None:
        return ()
    axes = (ax,) if isinstance(ax, str) else tuple(ax)
    return tuple(a for a in axes if a in rules.mesh.shape)


def _model_size(rules: ShardingRules) -> int:
    return math.prod(rules.mesh.shape[a] for a in _model_axes(rules))


def moe_mode(cfg: ModelConfig, rules: ShardingRules) -> str:
    maxes = _model_axes(rules)
    if not maxes:
        return "local"
    if cfg.moe_tp:
        return "tp"
    msize = _model_size(rules)
    assert cfg.n_experts % msize == 0, \
        f"{cfg.name}: E={cfg.n_experts} not divisible by model={msize}; " \
        "set moe_tp=True"
    if cfg.moe_impl == "a2a" and rules.axis("act_seq"):
        return "ep_a2a"
    return "ep"


def _dispatch_ffn(xf, top_idx, top_gate, wi, wg, wo, e_base, E_loc, C):
    """Capacity-dispatch N tokens to E_loc local experts and combine.

    xf (N, d) f32; top_idx/top_gate (N, k); expert weights (E_loc, d, f) etc.
    Returns the local experts' combined contribution (N, d) f32.
    """
    N, d = xf.shape
    wdt = wi.dtype
    out = jnp.zeros((N, d), jnp.float32)
    for j in range(E_loc):                       # static, small (<= E/|model|)
        e = e_base + j
        sel = (top_idx == e)                     # (N, k)
        gate = jnp.sum(jnp.where(sel, top_gate, 0.0), axis=-1)    # (N,)
        chosen = sel.any(axis=-1)
        pos = jnp.cumsum(chosen.astype(jnp.int32)) - 1            # (N,)
        slot = jnp.where(chosen & (pos < C), pos, C)              # C = drop
        # FFN math stays fully in the weight dtype: any f32 operand (fwd OR
        # bwd cotangent) promotes the whole 94-layer expert stack to f32 via
        # XLA loop-invariant hoisting — 7 GiB of converts in the dry-run.
        buf = jnp.zeros((C + 1, d), wdt).at[slot].set(xf.astype(wdt))[:C]
        h = silu(buf @ wg[j]) * (buf @ wi[j])
        y = (h @ wo[j]).astype(jnp.float32)                       # (C, d)
        back = jnp.where(slot < C, slot, C)
        gathered = jnp.concatenate([y, jnp.zeros((1, d), y.dtype)])[back]
        out = out + gate[:, None] * gathered
    return out


@jax.named_scope("moe.router")
def moe_route(xn, router, cfg: ModelConfig):
    """(gates, expert ids), each (..., k).  With ``norm_topk_prob`` the
    gates are a softmax over the k highest router logits (Mixtral,
    Qwen3); without it they are the softmax over every expert's score,
    read at the k highest and not renormalised, with the scores in
    float32 at full precision as DeepSeek-V2's ``MoEGate`` takes them."""
    k = cfg.experts_per_token
    if cfg.norm_topk_prob:
        logits = (xn.astype(jnp.float32) @ router)
        top_gate, top_idx = jax.lax.top_k(logits, k)
        return jax.nn.softmax(top_gate, axis=-1), top_idx
    logits = jnp.matmul(xn.astype(jnp.float32), router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)


@jax.named_scope("moe.experts")
def routed_experts(xf, top_idx, top_gate, wg, wi, wo, e_lo=0, layer=None,
                   capacity=None):
    """Routed experts: every (row, chosen expert) pair whose expert this
    share holds (``e_lo`` .. ``e_lo + E_held - 1``) is computed, by one
    grouped matmul per projection over the pairs sorted by expert.  xf
    (N, d); top_idx / top_gate (N, k) -> (N, d) float32, each row's gated
    sum over its held experts.  With ``capacity``, an expert keeps its
    first ``capacity`` rows in row order and the rest add nothing, as
    :func:`_dispatch_ffn` drops them; else it keeps every row.

    Expert stacks are (E_held, d, f) etc., or, with ``layer``, every
    layer's (n_layers, E_held, d, f): the grouped matmul then takes the
    whole stack, with rows only in this layer's groups, so that it reads
    the layer's experts where they lie (a grouped kernel takes a copy of
    a slice it is given)."""
    N, d = xf.shape
    k = top_idx.shape[-1]
    E_held = wg.shape[-3]
    local = top_idx.reshape(N * k) - e_lo
    held = (local >= 0) & (local < E_held)
    key = jnp.where(held, local, E_held)            # the rest sort last
    order = jnp.argsort(key, stable=True)
    counts = jnp.bincount(key, length=E_held + 1).astype(jnp.int32)
    sizes = counts[:E_held]
    if capacity is not None:                        # rank within its expert
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(N * k) - starts[key[order]]
        held = held & jnp.zeros(N * k, bool).at[order].set(rank < capacity)
    if layer is not None:
        wg, wi, wo = (w.reshape((-1,) + w.shape[2:]) for w in (wg, wi, wo))
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros(wg.shape[0], jnp.int32), sizes, (layer * E_held,))
    tok = order // k
    xs = xf[tok].astype(wg.dtype)
    g = kops.ragged_dot_f32(xs, wg, sizes)
    u = kops.ragged_dot_f32(xs, wi, sizes)
    h = (silu(g) * u).astype(wo.dtype)
    y = kops.ragged_dot_f32(h, wo, sizes)
    gate = jnp.where(held, top_gate.reshape(N * k), 0.0)[order]
    y = jnp.where(held[order][:, None], y, 0.0)     # rows past the groups
    return jnp.zeros((N, d), jnp.float32).at[tok].add(gate[:, None] * y)


@jax.named_scope("moe.shared")
def shared_experts(sp, xn):
    """The always-on experts, one SwiGLU."""
    h = silu(kops.dense(xn, sp["wg"])) * kops.dense(xn, sp["wi"])
    return kops.dense(h, sp["wo"])


def moe_local(p, xn, cfg: ModelConfig, *, e_lo: int = 0,
              shared: bool = True, layer=None, count_rows=None):
    """One chip's share of a MoE layer, without the residual: routing
    over every expert (``p["router"]``), the routed experts that ``p``'s
    expert stacks hold (ids from ``e_lo``; stacks of every layer with
    ``layer``, :func:`routed_experts`; dropless unless the config sets a
    ``capacity_factor``, which then caps each expert's rows of this
    call), and, with ``shared``, the shared experts (a layer's shares
    count them once).  With ``count_rows``, a mask over xn's rows, it
    returns (y, how many of the held experts those rows chose)."""
    top_gate, top_idx = moe_route(xn, p["router"], cfg)
    xf = xn.reshape(-1, xn.shape[-1])
    k = cfg.experts_per_token
    cap = None if cfg.capacity_factor is None else max(1, int(math.ceil(
        xf.shape[0] * k / cfg.n_experts * cfg.capacity_factor)))
    y = routed_experts(xf, top_idx.reshape(-1, k), top_gate.reshape(-1, k),
                       p["wg"], p["wi"], p["wo"], e_lo, layer,
                       cap).reshape(xn.shape)
    if shared and "shared" in p:
        y = y + shared_experts(p["shared"], xn).astype(jnp.float32)
    if count_rows is None:
        return y
    E_held = p["wg"].shape[-3]
    local = top_idx.reshape(-1, k) - e_lo
    pick = count_rows.reshape(-1, 1) & (local >= 0) & (local < E_held)
    hits = jnp.zeros(E_held + 1, jnp.int32).at[
        jnp.where(pick, local, E_held)].add(1)
    return y, jnp.sum(hits[:E_held] > 0).astype(jnp.int32)


@jax.named_scope("mlp")
def moe_layer(p, x, cfg: ModelConfig, rules: ShardingRules, topology=None):
    """Top-k MoE with per-shard capacity.  EP mode: experts sharded over
    the `model` axes via shard_map (tokens replicated on the model axes —
    the GLSU "shuffle stage" becomes a local scatter + cross-lane psum
    combine).  TP mode (n_experts < |model|): all experts everywhere, ff
    dim sharded.  A config without a ``capacity_factor`` routes dropless
    (:func:`moe_local`), on one device only.

    ``topology`` (a :class:`repro.topology.Topology` whose level axes are
    the `model` mesh axes) makes the ep_a2a dispatch hierarchical: the
    token all-to-all runs level by level, intra-level ring first — see
    :func:`_moe_ep_a2a`.
    """
    B, S, d = x.shape
    xn = rmsnorm(x, p["norm"], cfg.norm_eps)
    k = cfg.experts_per_token
    E = cfg.n_experts
    mode = moe_mode(cfg, rules)
    if cfg.capacity_factor is None:
        if mode != "local":
            raise ValueError(f"{cfg.name}: dropless MoE runs on one device; "
                             f"MoE mode {mode!r} needs a capacity_factor")
        return x + moe_local(p, xn, cfg).astype(x.dtype)
    top_gate, top_idx = moe_route(xn, p["router"], cfg)
    if "shared" in p:
        x = x + shared_experts(p["shared"], xn).astype(x.dtype)

    def run_local(xn_, ti_, tg_, wi, wg, wo, e_base, E_loc):
        N = xn_.shape[0] * xn_.shape[1]
        C = max(1, int(math.ceil(N * k / E * cfg.capacity_factor)))
        xf = xn_.reshape(N, d).astype(jnp.float32)
        y = _dispatch_ffn(xf, ti_.reshape(N, k), tg_.reshape(N, k),
                          wi, wg, wo, e_base, E_loc, C)
        return y.reshape(xn_.shape)

    if mode == "local":
        y = run_local(xn, top_idx, top_gate, p["wi"], p["wg"], p["wo"], 0, E)
        return x + y.astype(x.dtype)

    mesh = rules.mesh
    maxes = _model_axes(rules)
    msize = _model_size(rules)
    mspec = maxes if len(maxes) > 1 else maxes[0]
    bspec = rules.spec(("batch", "", ""))   # respects batch divisibility

    if mode == "tp":
        # every shard runs all experts on its token shard, ff sharded
        def body(xn_, ti_, tg_, wi, wg, wo):
            y = run_local(xn_, ti_, tg_, wi, wg, wo, 0, E)
            return jax.lax.psum(y, maxes)

        y = substrate.shard_map(
            body, mesh=mesh,
            in_specs=(bspec, bspec, bspec,
                      P(None, None, mspec), P(None, None, mspec),
                      P(None, mspec, None)),
            out_specs=bspec)(xn, top_idx, top_gate, p["wi"], p["wg"], p["wo"])
        return x + y.astype(x.dtype)

    if mode == "ep_a2a" and S % msize == 0:
        return x + _moe_ep_a2a(p, xn, top_idx, top_gate, cfg, rules,
                               topology).astype(x.dtype)

    # EP (replicated-token variant): experts sharded over the model axes,
    # tokens replicated on them, combine via psum.  Simple but pays a
    # token-space all-reduce per layer — §Perf replaces it with ep_a2a.
    E_loc = E // msize

    def body(xn_, ti_, tg_, wi, wg, wo):
        e_base = substrate.axis_index(maxes) * E_loc
        # e_base is traced; shift indices so the static loop sees local ids
        ti_loc = ti_ - e_base
        y = run_local(xn_, ti_loc, tg_, wi, wg, wo, 0, E_loc)
        return jax.lax.psum(y, maxes)

    y = substrate.shard_map(
        body, mesh=mesh,
        in_specs=(bspec, bspec, bspec,
                  P(mspec, None, None), P(mspec, None, None),
                  P(mspec, None, None)),
        out_specs=bspec)(xn, top_idx, top_gate, p["wi"], p["wg"], p["wo"])
    return x + y.astype(x.dtype)


def _a2a_stages(rules: ShardingRules, topology) -> list:
    """The expert-dispatch exchange as (axes, size) stages, innermost
    first.

    Flat (``topology=None``): one all-to-all over every model axis at once.
    With a Topology whose level axes are the model axes, one stage per
    level — the intra-level (lane) exchange runs first and each outer
    (cluster / pod) stage only moves already-aggregated level blocks, so
    the physically long wires never carry intra-level traffic (the
    §III-B.3 Align pipeline applied to token buffers).  Both schedules are
    exact inverses of themselves stage by stage, so the combine path
    restores placement bit-identically to the flat exchange.
    """
    maxes = _model_axes(rules)
    if topology is None:
        return [(maxes, _model_size(rules))]
    from repro.topology import mesh_levels
    levels = mesh_levels(topology, rules.mesh.shape)
    flat = tuple(a for axes, _ in levels for a in axes)
    if flat != maxes:
        raise ValueError(f"topology level axes {flat} must flatten to the "
                         f"model axes {maxes}")
    return list(reversed(levels))                     # innermost first


def _a2a_dispatch(buf, stages, E_loc: int):
    """(E, C, d) expert-major capacity buffers -> (E_loc, C*msize, d): every
    stage peels off the expert index's innermost remaining level digit and
    exchanges along that level's ring."""
    for axes, s in stages:
        ED, Ccur, d = buf.shape
        buf = buf.reshape(ED // (s * E_loc), s, E_loc, Ccur, d)
        buf = jax.lax.all_to_all(buf, axes, split_axis=1, concat_axis=3,
                                 tiled=True)
        buf = buf.reshape(ED // s, Ccur * s, d)
    return buf


def _a2a_combine(y, stages, E_loc: int):
    """Exact inverse of :func:`_a2a_dispatch` (stages unwound outermost
    first), restoring (E, C, d) placement."""
    for axes, s in reversed(stages):
        ED, Ccur, d = y.shape
        y = y.reshape(ED // E_loc, 1, E_loc, Ccur, d)
        y = jax.lax.all_to_all(y, axes, split_axis=3, concat_axis=1,
                               tiled=True)
        y = y.reshape(ED * s, Ccur // s, d)
    return y


def _moe_ep_a2a(p, xn, top_idx, top_gate, cfg: ModelConfig,
                rules: ShardingRules, topology=None):
    """All-to-all expert parallelism — the GLSU discipline: shuffle the
    (small) token buffers between expert shards instead of replicating
    tokens / gathering weights.

    Each model shard dispatches its OWN sequence slice (act_seq sharding)
    into per-expert capacity buffers for all E experts, a2a's buffers so
    shard i holds its E/msize experts' tokens from every source, runs the
    FFN, a2a's back and combines.  Wire per layer ~= 4 x dispatched-token
    bytes — two orders of magnitude below the psum-combine variant at
    qwen3 scale (measured in §Perf).

    Communicates across: every `model` mesh axis.  Flat by default (one
    all-to-all spanning them); with ``topology`` the exchange walks the
    topology levels innermost-first (see :func:`_a2a_stages`) and — because
    the FFN is row-independent and the combine inverts the dispatch stage
    by stage — produces bit-identical results to the flat exchange.
    """
    mesh = rules.mesh
    maxes = _model_axes(rules)
    msize = _model_size(rules)
    mspec = maxes if len(maxes) > 1 else maxes[0]
    stages = _a2a_stages(rules, topology)
    B, S, d = xn.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    E_loc = E // msize
    S_loc = S // msize
    bspec_tok = rules.spec(("batch", "act_seq", ""))
    bspec_idx = rules.spec(("batch", "act_seq", ""))
    wdt = p["wi"].dtype

    def body(xn_, ti_, tg_, wi, wg, wo):
        B_loc = xn_.shape[0]
        N = B_loc * S_loc
        C = max(1, int(math.ceil(N * k / E * cfg.capacity_factor)))
        xf = xn_.reshape(N, d).astype(wdt)
        ti = ti_.reshape(N * k)
        tg = tg_.reshape(N * k).astype(jnp.float32)
        tok = jnp.repeat(jnp.arange(N), k)

        # rank of each (token, choice) within its expert (stable by token)
        order = jnp.argsort(ti, stable=True)
        sorted_e = ti[order]
        start = jnp.searchsorted(sorted_e, jnp.arange(E))
        ranks_sorted = jnp.arange(N * k) - start[sorted_e]
        ranks = jnp.zeros(N * k, jnp.int32).at[order].set(
            ranks_sorted.astype(jnp.int32))
        keep = ranks < C
        slot = jnp.where(keep, ti * C + ranks, E * C)             # OOB drops
        buf = jnp.zeros((E * C + 1, d), wdt).at[slot].set(xf[tok])[:-1]
        buf = buf.reshape(E, C, d)

        # GLSU shuffle: expert-major blocks to their owning shard,
        # level by level
        recv = _a2a_dispatch(buf, stages, E_loc)      # (E_loc, C*msize, d)
        h = silu(jnp.einsum("ecd,edf->ecf", recv, wg)) \
            * jnp.einsum("ecd,edf->ecf", recv, wi)
        y = jnp.einsum("ecf,efd->ecd", h.astype(wdt), wo)
        back = _a2a_combine(y, stages, E_loc)         # (E, C, d)
        flat = jnp.concatenate([back.reshape(E * C, d),
                                jnp.zeros((1, d), y.dtype)])
        picked = flat[slot].astype(jnp.float32)                   # (N*k, d)
        w = jnp.where(keep, tg, 0.0)[:, None]
        out = jnp.zeros((N, d), jnp.float32).at[tok].add(w * picked)
        return out.reshape(B_loc, S_loc, d)

    y = substrate.shard_map(
        body, mesh=mesh,
        in_specs=(bspec_tok, bspec_idx, bspec_idx,
                  P(mspec, None, None), P(mspec, None, None),
                  P(mspec, None, None)),
        out_specs=bspec_tok)(xn, top_idx, top_gate,
                             p["wi"], p["wg"], p["wo"])
    return y


# ---------------------------------------------------------------------------
# Mamba2 (SSD, chunked) — arXiv:2405.21060
# ---------------------------------------------------------------------------

def mamba_defs(cfg: ModelConfig) -> dict:
    d, dt = cfg.d_model, cfg.dtype
    di = cfg.d_inner_ssm
    N = cfg.ssm_state
    H = cfg.n_ssm_heads
    kc = cfg.ssm_conv
    return {
        "norm": PV((d,), jnp.float32, ("",), "ones"),
        "in_proj": PV((d, 2 * di + 2 * N + H), dt, ("fsdp", "model")),
        "conv_w": PV((kc, di + 2 * N), dt, ("", "model")),
        "conv_b": PV((di + 2 * N,), dt, ("model",), "zeros"),
        "A_log": PV((H,), jnp.float32, ("model",), "zeros"),
        "D": PV((H,), jnp.float32, ("model",), "ones"),
        "dt_bias": PV((H,), jnp.float32, ("model",), "zeros"),
        "gnorm": PV((di,), jnp.float32, ("model",), "ones"),
        "out_proj": PV((di, d), dt, ("model", "fsdp")),
    }


def _ssd_chunked(xh, dtv, Bm, Cm, A, chunk: int, state_in=None):
    """Chunked state-space dual form.

    xh (B,S,H,P) f32; dtv (B,S,H); Bm/Cm (B,S,N); A (H,) negative.
    Returns y (B,S,H,P), final state (B,H,P,N)."""
    Bsz, S, H, Pd = xh.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    while S % Q:
        Q -= 1
    nc = S // Q
    r = lambda t: t.reshape((Bsz, nc, Q) + t.shape[2:])
    xc, dtc, Bc, Cc = r(xh), r(dtv), r(Bm), r(Cm)

    dA = dtc * A[None, None, None, :]                 # (B,nc,Q,H) negative
    dA_cs = jnp.cumsum(dA, axis=2)                    # within-chunk cumsum
    # decay from q' to q (q >= q'): exp(dA_cs[q] - dA_cs[q'])
    seg = dA_cs[:, :, :, None, :] - dA_cs[:, :, None, :, :]      # (B,nc,Q,Q,H)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)

    xdt = xc * dtc[..., None]                         # (B,nc,Q,H,P)
    # intra-chunk (diagonal blocks)
    y_diag = jnp.einsum("bcqn,bckn,bcqkh,bckhp->bcqhp",
                        Cc, Bc, L.transpose(0, 1, 2, 3, 4), xdt)
    # chunk-final states
    decay_end = jnp.exp(dA_cs[:, :, -1:, :] - dA_cs)  # (B,nc,Q,H)
    S_c = jnp.einsum("bcqn,bcqh,bcqhp->bchpn", Bc, decay_end, xdt)
    # inter-chunk recurrence (the ring/slide stage when sequence-sharded)
    chunk_decay = jnp.exp(jnp.sum(dA, axis=2))        # (B,nc,H)

    def scan_fn(s_prev, inp):
        s_c, dec = inp                                # (B,H,P,N), (B,H)
        s_in = s_prev
        s_next = s_c + dec[:, :, None, None] * s_prev
        return s_next, s_in

    init = (jnp.zeros((Bsz, H, Pd, N), jnp.float32) if state_in is None
            else state_in)
    s_final, s_ins = jax.lax.scan(
        scan_fn, init,
        (S_c.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    s_ins = s_ins.transpose(1, 0, 2, 3, 4)            # (B,nc,H,P,N)
    y_off = jnp.einsum("bcqn,bchpn,bcqh->bcqhp", Cc, s_ins, jnp.exp(dA_cs))
    y = (y_diag + y_off).reshape(Bsz, S, H, Pd)
    return y, s_final


def _mamba_project(p, x, cfg: ModelConfig):
    di, N, H = cfg.d_inner_ssm, cfg.ssm_state, cfg.n_ssm_heads
    xn = rmsnorm(x, p["norm"], cfg.norm_eps)
    proj = kops.dense(xn, p["in_proj"])               # (B,S,2di+2N+H)
    z, xc, Bm, Cm, dtv = jnp.split(
        proj, [di, 2 * di, 2 * di + N, 2 * di + 2 * N], axis=-1)
    return z, jnp.concatenate([xc, Bm, Cm], -1), dtv


def mamba_layer(p, x, cfg: ModelConfig, rules: ShardingRules,
                conv_state=None, ssm_state=None, return_state: bool = False):
    """Train/prefill Mamba2 block (full sequence, chunked SSD)."""
    B, S, d = x.shape
    di, N, H = cfg.d_inner_ssm, cfg.ssm_state, cfg.n_ssm_heads
    kc = cfg.ssm_conv
    z, xbc, dtv = _mamba_project(p, x, cfg)
    # depthwise causal conv over (x, B, C)
    pad = jnp.zeros((B, kc - 1, xbc.shape[-1]), xbc.dtype) \
        if conv_state is None else conv_state
    xbc_p = jnp.concatenate([pad, xbc], axis=1)
    conv = sum(xbc_p[:, i:i + S] * p["conv_w"][i][None, None]
               for i in range(kc)) + p["conv_b"][None, None]
    conv = silu(conv)
    xc, Bm, Cm = jnp.split(conv, [di, di + N], axis=-1)
    xh = xc.reshape(B, S, H, cfg.ssm_head_dim).astype(jnp.float32)
    dtb = jax.nn.softplus(dtv.astype(jnp.float32) + p["dt_bias"][None, None])
    A = -jnp.exp(p["A_log"])
    y, s_final = _ssd_chunked(xh, dtb, Bm.astype(jnp.float32),
                              Cm.astype(jnp.float32), A, cfg.ssm_chunk,
                              ssm_state)
    y = y + p["D"][None, None, :, None] * xh          # skip
    y = y.reshape(B, S, di)
    y = rmsnorm(y.astype(x.dtype) * silu(z), p["gnorm"], cfg.norm_eps)
    out = kops.dense(y, p["out_proj"])
    res = x + out.astype(x.dtype)
    if return_state:
        new_conv = xbc_p[:, S:S + kc - 1] if kc > 1 else pad
        return res, (new_conv, s_final.astype(jnp.float32))
    return res


class MambaCache(NamedTuple):
    conv: jax.Array       # (B, kc-1, di+2N)
    state: jax.Array      # (B, H, P, N) f32


def mamba_cache_defs(cfg: ModelConfig, batch: int) -> MambaCache:
    di, N, H = cfg.d_inner_ssm, cfg.ssm_state, cfg.n_ssm_heads
    return MambaCache(
        PV((batch, cfg.ssm_conv - 1, di + 2 * N), cfg.dtype,
           ("batch", "", "model"), "zeros"),
        PV((batch, H, cfg.ssm_head_dim, N), jnp.float32,
           ("batch", "model", "", ""), "zeros"))


def mamba_layer_decode(p, x, cache: MambaCache, cfg: ModelConfig,
                       rules: ShardingRules):
    """Single-token recurrent step: state <- dA*state + dt*B (x) ; y = C.state."""
    B, S1, d = x.shape
    di, N, H = cfg.d_inner_ssm, cfg.ssm_state, cfg.n_ssm_heads
    kc = cfg.ssm_conv
    z, xbc, dtv = _mamba_project(p, x, cfg)
    window = jnp.concatenate([cache.conv, xbc], axis=1)       # (B, kc, ch)
    conv = jnp.einsum("bkc,kc->bc", window, p["conv_w"]) + p["conv_b"]
    conv = silu(conv)[:, None, :]
    xc, Bm, Cm = jnp.split(conv, [di, di + N], axis=-1)
    xh = xc.reshape(B, H, cfg.ssm_head_dim).astype(jnp.float32)
    dtb = jax.nn.softplus(dtv.astype(jnp.float32)[:, 0] + p["dt_bias"][None])
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dtb * A[None])                               # (B,H)
    Bv = Bm[:, 0].astype(jnp.float32)                         # (B,N)
    Cv = Cm[:, 0].astype(jnp.float32)
    upd = jnp.einsum("bh,bhp,bn->bhpn", dtb, xh, Bv)
    state = cache.state * dA[:, :, None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", state, Cv) + p["D"][None, :, None] * xh
    y = y.reshape(B, 1, di)
    y = rmsnorm(y.astype(x.dtype) * silu(z), p["gnorm"], cfg.norm_eps)
    out = kops.dense(y, p["out_proj"])
    new_conv = window[:, 1:] if kc > 1 else cache.conv
    return x + out.astype(x.dtype), MambaCache(new_conv, state)
