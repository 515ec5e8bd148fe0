"""Whole-model assembly: embeddings -> scan over layer periods -> head.

One code path serves all ten assigned architectures: the repeating layer
``period`` (a tuple of layers, each a tuple of sublayer kinds) drives both
parameter stacking (compile-time O(one period) via lax.scan) and execution.
Families:

    dense / moe      decoder-only periods of (attn, mlp|moe)
    ssm              (mamba,) periods
    hybrid (jamba)   8-layer periods mixing mamba/attn and moe/mlp
    encdec           + a bidirectional encoder; decoder layers carry xattn
    vlm              + a frontend projection; xattn layers attend image tokens

Three entry points per model: ``forward_train`` (loss), ``prefill``
(populate caches, return last logits), ``decode_step`` (one token).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro import substrate
from repro.configs.base import ATTN, MAMBA, MLA, MLP, MOE, XATTN, ModelConfig
from repro.parallel.sharding import PV, ShardingRules, constraint
from . import layers as L


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------

def _stack(defs, n: int):
    return jax.tree.map(
        lambda pv: PV((n,) + pv.shape, pv.dtype, ("",) + pv.logical, pv.init,
                      pv.scale),
        defs, is_leaf=lambda x: isinstance(x, PV))


def _sublayer_defs(kind: str, cfg: ModelConfig):
    if kind == ATTN:
        return L.attn_defs(cfg)
    if kind == MLA:
        return L.mla_defs(cfg)
    if kind == XATTN:
        return L.xattn_defs(cfg)
    if kind == MAMBA:
        return L.mamba_defs(cfg)
    if kind == MLP:
        return L.mlp_defs(cfg)
    if kind == MOE:
        return L.moe_defs_tp(cfg) if cfg.moe_tp else L.moe_defs(cfg)
    raise ValueError(kind)


def stacks(cfg: ModelConfig) -> list[tuple[str, tuple, int]]:
    """(parameter key, layer kinds, repeats) of each scanned stack of
    layers, in the order they run: the ``first_dense`` leading layers
    (DeepSeek's dense MLP layers before the MoE period), then the period.
    A cache or pool holds one subtree per stack (:func:`split_state`)."""
    lead = [("lead", cfg.lead_period, cfg.first_dense)] \
        if cfg.first_dense else []
    return lead + [("period", cfg.layer_period, cfg.n_periods)]


def split_state(cfg: ModelConfig, state) -> list:
    """A cache or pool tree -> its subtree per stack.  Without leading
    layers the tree is the period's own (the layout every dense model
    had)."""
    if cfg.first_dense:
        return [state[name] for name, _, _ in stacks(cfg)]
    return [state]


def join_state(cfg: ModelConfig, parts: list):
    if cfg.first_dense:
        return {name: part for (name, _, _), part in zip(stacks(cfg), parts)}
    return parts[0]


def _stack_defs(kinds, n: int, leaf_defs) -> dict:
    """{"l<i>": {"s<j>_<kind>": stacked defs}} over the kinds that
    ``leaf_defs(kind)`` gives defs for (None: no entry)."""
    out = {}
    for li, layer in enumerate(kinds):
        slots = {}
        for si, kind in enumerate(layer):
            d = leaf_defs(kind)
            if d is not None:
                slots[f"s{si}_{kind}"] = _stack(d, n)
        out[f"l{li}"] = slots
    return out


def model_defs(cfg: ModelConfig) -> dict:
    d, V, dt = cfg.d_model, cfg.vocab_size, cfg.dtype
    # embed/head are vocab-sharded over `model` ONLY: FSDP-sharding their
    # d_model dim makes every loss chunk / embed lookup all-gather the whole
    # table over `data` (measured 8x wire blow-up in the dry-run).
    Vp = cfg.padded_vocab
    defs: dict[str, Any] = {
        "embed": PV((Vp, d), dt, ("model", ""), "normal", 0.02),
        "final_norm": PV((d,), jnp.float32, ("",), "ones"),
    }
    if not cfg.tie_embeddings:
        defs["head"] = PV((d, Vp), dt, ("", "model"))
    for name, kinds, n in stacks(cfg):
        defs[name] = _stack_defs(kinds, n, lambda k: _sublayer_defs(k, cfg))
    if cfg.family == "encdec":
        enc_layer = {"attn": L.attn_defs(cfg), "mlp": L.mlp_defs(cfg)}
        defs["encoder"] = {"layers": _stack(enc_layer, cfg.n_enc_layers),
                           "norm": PV((d,), jnp.float32, ("",), "ones")}
    if cfg.d_ctx:
        defs["ctx_proj"] = PV((cfg.d_ctx, d), dt, ("", "fsdp"))
    return defs


# ---------------------------------------------------------------------------
# Cache definitions (decode)
# ---------------------------------------------------------------------------

def cache_defs(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    def leaf(kind):
        if kind == ATTN:
            return L.attn_cache_defs(cfg, batch, seq_len)._asdict()
        if kind == MLA:
            return L.mla_cache_defs(cfg, batch, seq_len)
        if kind == XATTN:
            return L.xattn_cache_defs(cfg, batch)._asdict()
        if kind == MAMBA:
            return L.mamba_cache_defs(cfg, batch)._asdict()
        return None

    return join_state(cfg, [_stack_defs(kinds, n, leaf)
                            for _, kinds, n in stacks(cfg)])


def pool_defs(cfg: ModelConfig, n_blocks: int, block_tokens: int) -> dict:
    """Paged-KV block pool defs: same tree shape as :func:`cache_defs` but
    each ATTN leaf is ``{"k", "v": (n, n_blocks, block_tokens, *row)}``,
    a token's Hkv x Dh keys or values in a row of
    :func:`repro.models.layers.kv_row`, and each MLA leaf ``{"c": (n,
    n_blocks, block_tokens, R)}``, its latent_dim values in the first of
    R lanes (:func:`repro.models.layers.pool_row`) — a
    shared pool of fixed-size token blocks indexed by per-request block
    tables (block 0 is the reserved zero block).  Paged serving supports
    pure-attention caches only (no SSM/xattn state) and full attention
    (no SWA ring), which the serving engine validates."""
    if cfg.window:
        raise ValueError("paged KV supports full attention only "
                         f"(cfg.window={cfg.window})")

    blocks = (n_blocks, block_tokens)

    def leaf(kind):
        if kind == ATTN:
            row = L.kv_row(cfg)
            axes = ("", "") + ("kv", "")[:len(row)]
            return {"k": PV(blocks + row, cfg.dtype, axes, "zeros"),
                    "v": PV(blocks + row, cfg.dtype, axes, "zeros")}
        if kind == MLA:
            return {"c": PV(blocks + (L.pool_row(cfg.latent_dim),),
                            cfg.dtype, ("", "", ""), "zeros")}
        if kind in (XATTN, MAMBA):
            raise ValueError(
                f"paged KV serving supports attention caches only, "
                f"layer period has {kind}")
        return None

    return join_state(cfg, [_stack_defs(kinds, n, leaf)
                            for _, kinds, n in stacks(cfg)])


def cache_into_pool(pool, cache, js, bids):
    """Copy blocks ``js`` of a batch-1 dense cache (the tree of
    :func:`cache_defs`, its sequence a whole number of the pool's blocks)
    into pool blocks ``bids``, each token as a row of the pool's format
    (:func:`pool_defs`)."""
    def put(pool_leaf, cache_leaf):
        n, _, bt = pool_leaf.shape[:3]
        blocks = cache_leaf[:, 0].reshape(
            (n, -1, bt) + cache_leaf.shape[3:])
        return pool_leaf.at[:, bids].set(
            L.whole_rows(blocks[:, js], pool_leaf, 3))

    return jax.tree.map(put, pool, cache)


# ---------------------------------------------------------------------------
# Context (encoder / image frontend)
# ---------------------------------------------------------------------------

def context_len(cfg: ModelConfig, seq_len: int) -> int:
    if cfg.family == "encdec":
        return max(cfg.ssm_chunk, seq_len // 4)      # speech frames downsampled
    return cfg.n_ctx_tokens


def encode_context(params, ctx_embeds, cfg: ModelConfig, rules: ShardingRules):
    """Frontend stub output -> d_model context for xattn (encoder if encdec)."""
    ctx = ctx_embeds.astype(cfg.dtype)
    if "ctx_proj" in params:
        ctx = ctx @ params["ctx_proj"]
    ctx = constraint(ctx, rules, "batch", None, None)
    if cfg.family != "encdec":
        return ctx

    enc = params["encoder"]
    S = ctx.shape[1]
    positions = jnp.arange(S)

    def body(x, lp):
        x = L.attn_layer(lp["attn"], x, cfg, rules, positions, causal=False)
        x = L.mlp_layer(lp["mlp"], x, cfg, rules)
        return x, None

    if cfg.remat:
        body = jax.checkpoint(body)
    ctx, _ = jax.lax.scan(body, ctx, enc["layers"])
    return L.rmsnorm(ctx, enc["norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Decoder trunk
# ---------------------------------------------------------------------------

def _apply_slot(kind, sp, x, cfg, rules, positions, ctx):
    if kind == ATTN:
        return L.attn_layer(sp, x, cfg, rules, positions, causal=True)
    if kind == MLA:
        return L.mla_layer(sp, x, cfg, rules, positions)
    if kind == XATTN:
        return L.xattn_layer(sp, x, ctx, cfg, rules)
    if kind == MAMBA:
        return L.mamba_layer(sp, x, cfg, rules)
    if kind == MLP:
        return L.mlp_layer(sp, x, cfg, rules)
    if kind == MOE:
        return L.moe_layer(sp, x, cfg, rules)
    raise ValueError(kind)


def trunk(params, x, cfg: ModelConfig, rules: ShardingRules, positions,
          ctx=None):
    for name, kinds, n in stacks(cfg):
        def body(xc, pp, kinds=kinds):
            for li, layer in enumerate(kinds):
                for si, kind in enumerate(layer):
                    sp = pp[f"l{li}"][f"s{si}_{kind}"]
                    xc = _apply_slot(kind, sp, xc, cfg, rules, positions, ctx)
                    xc = constraint(xc, rules, "batch", "act_seq", None)
            return xc, None

        if cfg.remat:
            body = jax.checkpoint(body)
        if cfg.unroll_layers:
            for i in range(n):
                x, _ = body(x, jax.tree.map(lambda t: t[i], params[name]))
            continue
        x, _ = jax.lax.scan(body, x, params[name])
    return x


@jax.named_scope("embed")
def embed_tokens(params, tokens, cfg: ModelConfig, rules: ShardingRules):
    mesh = rules.mesh
    if mesh is None or "model" not in mesh.shape or \
            cfg.padded_vocab % mesh.shape["model"]:
        x = jnp.take(params["embed"], tokens, axis=0)
        return constraint(x, rules, "batch", "act_seq", None)

    # Explicit vocab-sharded lookup: masked local gather + psum over `model`.
    # (The GSPMD gather fallback replicates the whole table per device —
    # >1 GiB for 150k vocabularies; this is the AraXL byte-map discipline:
    # touch only the locally-resident rows, reduce on the lane axis.)
    from jax.sharding import PartitionSpec as P
    V_loc = cfg.padded_vocab // mesh.shape["model"]
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    bspec = rules.spec(("batch", ""))

    def body(tok, emb):
        lo = substrate.axis_index("model") * V_loc
        ids = tok - lo
        ok = (ids >= 0) & (ids < V_loc)
        safe = jnp.where(ok, ids, 0)
        x = emb[safe]                          # emb local block (V_loc, d)
        x = jnp.where(ok[..., None], x, 0)
        return jax.lax.psum(x, "model")

    x = substrate.shard_map(body, mesh=mesh,
                            in_specs=(bspec, P("model", None)),
                            out_specs=bspec)(tokens, params["embed"])
    return constraint(x, rules, "batch", "act_seq", None)


def _mask_pad_vocab(logits, cfg: ModelConfig):
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    ids = jnp.arange(cfg.padded_vocab)
    return jnp.where(ids >= cfg.vocab_size, jnp.asarray(-1e30, logits.dtype),
                     logits)


@jax.named_scope("head")
def logits_fn(params, x, cfg: ModelConfig, rules: ShardingRules):
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = _mask_pad_vocab(x @ head, cfg)
    return constraint(logits, rules, "batch", None, "model")


def _ce_terms(logits, targets):
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    picked = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    return lse - picked


def ce_loss(params, x, targets, mask, cfg: ModelConfig,
            rules: ShardingRules):
    """Mean masked next-token CE.  With cfg.loss_chunk the sequence is
    processed in checkpointed blocks so the f32 logits (B, S, V) are never
    materialised whole — the decisive memory lever for 100k+ vocabularies."""
    B, S, _ = x.shape
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    chunk = cfg.loss_chunk
    if chunk <= 0 or S <= chunk or S % chunk:
        logits = constraint(_mask_pad_vocab(x @ head, cfg), rules,
                            "batch", None, "model")
        tok_loss = _ce_terms(logits, targets)
        return jnp.sum(tok_loss * mask) / jnp.sum(mask)

    nc = S // chunk
    xs = (x.reshape(B, nc, chunk, -1).transpose(1, 0, 2, 3),
          targets.reshape(B, nc, chunk).transpose(1, 0, 2),
          mask.reshape(B, nc, chunk).transpose(1, 0, 2))

    @jax.checkpoint
    def body(acc, blk):
        xc, tc, mc = blk
        logits = constraint(_mask_pad_vocab(xc @ head, cfg), rules,
                            "batch", None, "model")
        return acc + jnp.sum(_ce_terms(logits, tc) * mc), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), xs)
    return total / jnp.sum(mask)


def forward_train(params, tokens, cfg: ModelConfig, rules: ShardingRules,
                  ctx_embeds=None):
    """tokens (B, S) -> mean next-token cross-entropy loss."""
    B, S = tokens.shape
    positions = jnp.arange(S)
    ctx = None
    if cfg.family in ("encdec", "vlm"):
        ctx = encode_context(params, ctx_embeds, cfg, rules)
    x = embed_tokens(params, tokens, cfg, rules)
    x = trunk(params, x, cfg, rules, positions, ctx)
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    mask = jnp.ones((B, S), jnp.float32).at[:, -1].set(0.0)
    return ce_loss(params, x, targets, mask, cfg, rules)


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------

def prefill(params, tokens, cfg: ModelConfig, rules: ShardingRules,
            cache_seq_len: int, ctx_embeds=None):
    """tokens (B, S) -> (cache, last-token logits)."""
    B, S = tokens.shape
    positions = jnp.arange(S)
    ctx = None
    if cfg.family in ("encdec", "vlm"):
        ctx = encode_context(params, ctx_embeds, cfg, rules)
    x = embed_tokens(params, tokens, cfg, rules)
    W = L.attn_cache_len(cfg, cache_seq_len)

    def body(xc, pp, kinds):
        caches = {}
        for li, layer in enumerate(kinds):
            lcaches = {}
            for si, kind in enumerate(layer):
                key = f"s{si}_{kind}"
                sp = pp[f"l{li}"][key]
                if kind == ATTN:
                    xc, c = L.attn_layer_prefill(sp, xc, cfg, rules,
                                                 positions, W)
                    lcaches[key] = c._asdict()
                elif kind == MLA:
                    xc, lcaches[key] = L.mla_layer_prefill(
                        sp, xc, cfg, rules, positions, W)
                elif kind == XATTN:
                    xc = L.xattn_layer(sp, xc, ctx, cfg, rules)
                    lcaches[key] = L.xattn_prefill_cache(sp, ctx, cfg)._asdict()
                elif kind == MAMBA:
                    xc, (conv, state) = L.mamba_layer(sp, xc, cfg, rules,
                                                      return_state=True)
                    lcaches[key] = {"conv": conv.astype(cfg.dtype),
                                    "state": state}
                else:
                    xc = _apply_slot(kind, sp, xc, cfg, rules, positions, ctx)
            caches[f"l{li}"] = lcaches
        xc = constraint(xc, rules, "batch", None, None)
        return xc, caches

    parts = []
    for name, kinds, n in stacks(cfg):
        fn = functools.partial(body, kinds=kinds)
        if cfg.remat:
            fn = jax.checkpoint(fn)
        if cfg.unroll_layers:                  # cost-analysis variants
            caches = []
            for i in range(n):
                x, c = fn(x, jax.tree.map(lambda t: t[i], params[name]))
                caches.append(c)
            parts.append(jax.tree.map(lambda *xs: jnp.stack(xs), *caches))
        else:
            x, c = jax.lax.scan(fn, x, params[name])
            parts.append(c)
    logits = logits_fn(params, x[:, -1:], cfg, rules)
    return join_state(cfg, parts), logits


def decode_step(params, token, cache, pos, cfg: ModelConfig,
                rules: ShardingRules):
    """token (B, 1), pos scalar int32 or (B,) int32 per-slot positions
    -> (logits (B,1,V), new cache).  The vector form is the serving
    engine's continuous batch; it is bit-identical to the scalar form
    when every slot sits at the same position."""
    x = embed_tokens(params, token, cfg, rules)

    def body(xc, pc, kinds):
        pp, cc = pc
        new_caches = {}
        for li, layer in enumerate(kinds):
            lcaches = {}
            for si, kind in enumerate(layer):
                key = f"s{si}_{kind}"
                sp = pp[f"l{li}"][key]
                if kind == ATTN:
                    c = L.AttnCache(**cc[f"l{li}"][key])
                    xc, c = L.attn_layer_decode(sp, xc, c, pos, cfg, rules)
                    lcaches[key] = c._asdict()
                elif kind == MLA:
                    xc, lcaches[key] = L.mla_layer_decode(
                        sp, xc, cc[f"l{li}"][key], pos, cfg, rules)
                elif kind == XATTN:
                    c = L.XAttnCache(**cc[f"l{li}"][key])
                    xc, c = L.xattn_layer_decode(sp, xc, c, cfg, rules)
                    lcaches[key] = c._asdict()
                elif kind == MAMBA:
                    c = L.MambaCache(**cc[f"l{li}"][key])
                    xc, c = L.mamba_layer_decode(sp, xc, c, cfg, rules)
                    lcaches[key] = c._asdict()
                else:
                    xc = _apply_slot(kind, sp, xc, cfg, rules, None, None)
            new_caches[f"l{li}"] = lcaches
        return xc, new_caches

    parts = []
    for (name, kinds, n), part in zip(stacks(cfg), split_state(cfg, cache)):
        fn = functools.partial(body, kinds=kinds)
        if cfg.unroll_layers:                  # cost-analysis variants
            caches = []
            for i in range(n):
                x, c = fn(x, jax.tree.map(lambda t: t[i],
                                          (params[name], part)))
                caches.append(c)
            parts.append(jax.tree.map(lambda *xs: jnp.stack(xs), *caches))
        else:
            x, c = jax.lax.scan(fn, x, (params[name], part))
            parts.append(c)
    logits = logits_fn(params, x, cfg, rules)
    return logits, join_state(cfg, parts)


#: the MoE weights the paged programs pass whole, not per layer
EXPERT_STACKS = ("wg", "wi", "wo")


def _paged_layers(params, x, pool, cfg: ModelConfig, rules: ShardingRules,
                  attend, count_rows=None):
    """Scan every stack of layers with its share of the paged pool in the
    carry: ``attend(kind, sp, x, pool_leaf, i) -> (x, pool_leaf)`` runs
    each attention sublayer on the stack's whole pool leaf and the layer's
    index ``i``, so its rows are written in place (with the pool donated
    to the program, no layer's pool is sliced, copied or written back).
    MoE sublayers route through :func:`repro.models.layers.moe_local`, on
    one device only.  Their expert stacks stay out of the scan: each
    layer's grouped matmuls take the whole stack and ``i``, and read its
    experts in place.  Returns (x, new pool, reached): with
    ``count_rows``, a mask over x's rows, ``reached`` holds per MoE layer,
    in order, how many experts those rows chose; else None."""
    if cfg.n_experts and L.moe_mode(cfg, rules) != "local":
        raise ValueError("paged serving reads MoE experts in place on one "
                         f"device; MoE mode {L.moe_mode(cfg, rules)!r} "
                         "shards them")

    def body(carry, xs, kinds, experts):
        xc, cc = carry
        pp, i = xs
        new_pool, reached = {}, []
        for li, layer in enumerate(kinds):
            lpool = dict(cc[f"l{li}"])
            for si, kind in enumerate(layer):
                key = f"s{si}_{kind}"
                sp = pp[f"l{li}"][key]
                if kind in (ATTN, MLA):
                    xc, lpool[key] = attend(kind, sp, xc, lpool[key], i)
                elif kind == MOE:
                    sp = dict(sp, **experts[f"l{li}"][key])
                    with jax.named_scope("mlp"):
                        xn = L.rmsnorm(xc, sp["norm"], cfg.norm_eps)
                        y = L.moe_local(sp, xn, cfg, layer=i,
                                        count_rows=count_rows)
                        if count_rows is not None:
                            y, n = y
                            reached.append(n)
                        xc = xc + y.astype(xc.dtype)
                else:
                    xc = _apply_slot(kind, sp, xc, cfg, rules, None, None)
            new_pool[f"l{li}"] = lpool
        if count_rows is None:
            return (xc, new_pool), None
        return (xc, new_pool), (jnp.stack(reached) if reached
                                else jnp.zeros(0, jnp.int32))

    parts, counts = [], []
    for (name, kinds, n), part in zip(stacks(cfg), split_state(cfg, pool)):
        scanned = {lk: {key: {w: v for w, v in sp.items()
                              if not key.endswith(f"_{MOE}")
                              or w not in EXPERT_STACKS}
                        for key, sp in slots.items()}
                   for lk, slots in params[name].items()}
        experts = {lk: {key: {w: sp[w] for w in EXPERT_STACKS}
                        for key, sp in slots.items()
                        if key.endswith(f"_{MOE}")}
                   for lk, slots in params[name].items()}
        (x, part), r = jax.lax.scan(
            functools.partial(body, kinds=kinds, experts=experts),
            (x, part), (scanned, jnp.arange(n)))
        if count_rows is not None:
            counts.append(r.reshape(-1))
        parts.append(part)
    reached = jnp.concatenate(counts) if count_rows is not None else None
    return x, join_state(cfg, parts), reached


def decode_step_paged(params, token, pool, tables, pos, live,
                      cfg: ModelConfig, rules: ShardingRules):
    """One-token decode through block tables.

    token (B, 1); pool — the :func:`pool_defs` tree; tables
    (B, max_blocks) int32; pos (B,) int32 per-slot positions; live (B,)
    bool -> (logits (B,1,V), new pool).  Bit-identical to
    :func:`decode_step` given tables whose gathered view equals the dense
    cache (zero block 0 ≡ unwritten dense rows)."""
    x = embed_tokens(params, token, cfg, rules)

    def attend(kind, sp, xc, c, i):
        if kind == MLA:
            xc, pc = L.mla_layer_decode_paged(sp, xc, c["c"], i, tables, pos,
                                              live, cfg, rules)
            return xc, {"c": pc}
        xc, pk, pv = L.attn_layer_decode_paged(sp, xc, c["k"], c["v"], i,
                                               tables, pos, live, cfg, rules)
        return xc, {"k": pk, "v": pv}

    x, new_pool, _ = _paged_layers(params, x, pool, cfg, rules, attend)
    logits = logits_fn(params, x, cfg, rules)
    return logits, new_pool


def prefill_chunk(params, tokens, pool, table_row, start, valid,
                  cfg: ModelConfig, rules: ShardingRules):
    """One fixed-size prefill chunk for a single request (B == 1).

    tokens (1, c) padded to the chunk length; ``start`` the chunk's base
    position (multiple of the block size), ``valid`` the count of real
    tokens.  Scatters the chunk's K/V into the pre-allocated blocks of
    ``table_row`` and returns (logits (1, c, V), new pool) — the engine
    reads logits[0, valid-1] on the final chunk for the first generated
    token — and, for a MoE model, a third output: per MoE layer, how many
    experts the chunk's valid rows chose.  Compiles once per chunk shape,
    not once per prompt length."""
    x = embed_tokens(params, tokens, cfg, rules)

    def attend(kind, sp, xc, c, i):
        if kind == MLA:
            xc, pc = L.mla_layer_prefill_paged(sp, xc, c["c"], i, table_row,
                                               start, valid, cfg, rules)
            return xc, {"c": pc}
        xc, pk, pv = L.attn_layer_prefill_paged(
            sp, xc, c["k"], c["v"], i, table_row, start, valid, cfg, rules)
        return xc, {"k": pk, "v": pv}

    count = (jnp.arange(tokens.shape[1]) < valid)[None] \
        if cfg.n_experts else None
    x, new_pool, reached = _paged_layers(params, x, pool, cfg, rules, attend,
                                         count)
    logits = logits_fn(params, x, cfg, rules)
    if reached is None:
        return logits, new_pool
    return logits, new_pool, reached
