"""Family ``deepseek_v2``: DeepSeek-V2's decoder.  Every layer is
multi-head latent attention (MLA) with YaRN rotary embedding; the first
``first_k_dense_replace`` layers end in a dense SwiGLU MLP, the others in
a mixture of routed SwiGLU experts (top k of a softmax over every
expert's score, not renormalised) plus shared experts that see every row.

Weights (``weights.init`` draws them; ``D`` dense and ``M`` MoE layers,
``H`` heads, ``r`` the latent rank, ``E`` routed experts of width ``f``,
``S`` shared experts, ``Vp`` the vocabulary rounded up to 256 rows):

    embed (Vp, d)  head (d, Vp)  final_norm (d,)
    per stack X in {dense, moe} of n in {D, M} layers:
      X.attn_norm (n, d)  X.q_proj (n, d, H*(nope+rope))
      X.kv_a (n, d, r+rope)  X.kv_norm (n, r)  X.kv_b (n, r, H*(nope+v))
      X.o_proj (n, H*v, d)  X.mlp_norm (n, d)
    dense.gate, dense.up (D, d, F)  dense.down (D, F, d)
    moe.router (M, d, E)  moe.gate, moe.up (M, E, d, f)  moe.down (M, E, f, d)
    moe.shared_gate, moe.shared_up (M, d, S*f)  moe.shared_down (M, S*f, d)

Each head's query is ``[nope | rope]`` and each head's ``kv_b`` output
``[k_nope | v]``, as published.  One departure: the rotary parts are
rotated in the half-split layout (``rotate_half``), where the published
code first de-interleaves them; that only permutes the columns of the
weights that make them, and the weights here are random.

The plain reference reads that layout and nothing of the program: one
layer at a time in float32, every product at ``highest`` precision,
attention expanded as published (``kv_b`` applied to every token's
latent, the shared roped key broadcast to every head), YaRN written from
the published formula, the gate a float32 softmax over every expert
read at the top k, and every routed expert run over every row and
weighted by its gate (0 where the row did not choose it).

The work counts are of what the served model needs: projections at the
rows served; ``kv_b`` folded into each row's query and output (the
absorbed form, which never expands the context); routed experts at the
rows routed to them, their weights read once for every expert a call
can reach; shared experts at every row; latent attention over each
row's live context, ``r + rope`` cached values per token and layer.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

import spec
import weights
import work
from reference import _mm, _rms

#: published keys read beyond ``spec.CONFIG_KEYS``
KEYS = ("kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "moe_intermediate_size",
        "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
        "first_k_dense_replace", "moe_layer_freq", "scoring_func",
        "topk_method", "norm_topk_prob", "routed_scaling_factor",
        "rope_scaling")
ATTN_KEYS = ("attn_norm", "q_proj", "kv_a", "kv_norm", "kv_b", "o_proj",
             "mlp_norm")


def check(c: dict) -> None:
    rs = c["rope_scaling"]
    if c["q_lora_rank"] is not None or c["moe_layer_freq"] != 1 \
            or c["scoring_func"] != "softmax" \
            or c["topk_method"] != "greedy" \
            or c["routed_scaling_factor"] != 1.0 \
            or rs.get("type") != "yarn" \
            or not 0 < c["num_experts_per_tok"] <= c["n_routed_experts"] \
            or not 0 <= c["first_k_dense_replace"] < c["num_hidden_layers"]:
        raise spec.SpecError(
            f"config {c['name']}: this family serves DeepSeek-V2 without a "
            f"query LoRA, MoE in every layer after the dense ones, greedy "
            f"softmax top-k gates at scale 1 and YaRN rope")
    program_config(c)       # a program that cannot serve it fails here


def program_config(c: dict):
    """The repo's model configuration for ``c``, checked against every
    published size and switch the file states; SpecError where the
    program has no such architecture or its sizes differ."""
    from repro.configs import get_config
    try:
        cfg = dataclasses.replace(get_config(c["arch"]), **c["overrides"])
    except (KeyError, TypeError) as e:
        raise spec.SpecError(f"the program has no arch {c['arch']} with "
                             f"{c['overrides']}: {e!r}") from e
    rs = c["rope_scaling"]
    want = {"d_model": c["hidden_size"], "d_ff": c["intermediate_size"],
            "d_ff_expert": c["moe_intermediate_size"],
            "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"],
            "n_layers": c["num_hidden_layers"],
            "first_dense": c["first_k_dense_replace"],
            "n_experts": c["n_routed_experts"],
            "experts_per_token": c["num_experts_per_tok"],
            "n_shared_experts": c["n_shared_experts"],
            "norm_topk_prob": c["norm_topk_prob"],
            "kv_lora_rank": c["kv_lora_rank"],
            "qk_nope_head_dim": c["qk_nope_head_dim"],
            "qk_rope_head_dim": c["qk_rope_head_dim"],
            "v_head_dim": c["v_head_dim"],
            "vocab_size": c["vocab_size"], "norm_eps": c["rms_norm_eps"],
            "rope_theta": c["rope_theta"],
            "yarn_factor": rs["factor"],
            "yarn_original_max": rs["original_max_position_embeddings"],
            "yarn_beta_fast": rs["beta_fast"],
            "yarn_beta_slow": rs["beta_slow"],
            "yarn_mscale": rs["mscale"],
            "yarn_mscale_all_dim": rs["mscale_all_dim"],
            "window": None, "moe_tp": False, "period": (),
            "tie_embeddings": c.get("tie_word_embeddings", False)}
    have = {k: getattr(cfg, k, None) for k in want}
    if have != want:
        raise spec.SpecError(f"repo arch {c['arch']} with {c['overrides']} "
                             f"is {have}, the file states {want}")
    return cfg


# ---------------------------------------------------------------- weights

def sizes(c: dict) -> dict:
    D = c["first_k_dense_replace"]
    return {"d": c["hidden_size"], "F": c["intermediate_size"],
            "f": c["moe_intermediate_size"], "H": c["num_attention_heads"],
            "r": c["kv_lora_rank"], "nope": c["qk_nope_head_dim"],
            "rope": c["qk_rope_head_dim"], "v": c["v_head_dim"],
            "E": c["n_routed_experts"], "k": c["num_experts_per_tok"],
            "S": c["n_shared_experts"], "D": D,
            "M": c["num_hidden_layers"] - D, "L": c["num_hidden_layers"],
            "V": c["vocab_size"]}


def shapes(c: dict) -> dict[str, tuple[tuple[int, ...], object]]:
    """name -> (shape, dtype) of every weight of configuration ``c``."""
    z = sizes(c)
    d, H, r = z["d"], z["H"], z["r"]
    Vp = weights.padded_vocab(z["V"])
    bf, f32 = jnp.bfloat16, jnp.float32
    out = {"embed": ((Vp, d), bf), "head": ((d, Vp), bf),
           "final_norm": ((d,), f32)}
    for stack, n in (("dense", z["D"]), ("moe", z["M"])):
        out.update({
            f"{stack}.attn_norm": ((n, d), f32),
            f"{stack}.q_proj": ((n, d, H * (z["nope"] + z["rope"])), bf),
            f"{stack}.kv_a": ((n, d, r + z["rope"]), bf),
            f"{stack}.kv_norm": ((n, r), f32),
            f"{stack}.kv_b": ((n, r, H * (z["nope"] + z["v"])), bf),
            f"{stack}.o_proj": ((n, H * z["v"], d), bf),
            f"{stack}.mlp_norm": ((n, d), f32)})
    D, M, E, f, sf = z["D"], z["M"], z["E"], z["f"], z["S"] * z["f"]
    out.update({
        "dense.gate": ((D, d, z["F"]), bf), "dense.up": ((D, d, z["F"]), bf),
        "dense.down": ((D, z["F"], d), bf),
        "moe.router": ((M, d, E), bf),
        "moe.gate": ((M, E, d, f), bf), "moe.up": ((M, E, d, f), bf),
        "moe.down": ((M, E, f, d), bf),
        "moe.shared_gate": ((M, d, sf), bf), "moe.shared_up": ((M, d, sf), bf),
        "moe.shared_down": ((M, sf, d), bf)})
    return out


def to_program(w: dict, cfg) -> dict:
    """The program's parameter tree (``repro.models.lm.model_defs``) over
    the same arrays (the router in float32, the program's type for it);
    float32 copies where the program runs in float32.  Raises if a shape
    or type disagrees."""
    if cfg.dtype == jnp.float32:
        w = {k: v.astype(jnp.float32) for k, v in w.items()}

    def attn(stack):
        return {"norm": w[f"{stack}.attn_norm"], "wq": w[f"{stack}.q_proj"],
                "wkv_a": w[f"{stack}.kv_a"], "kv_norm": w[f"{stack}.kv_norm"],
                "wkv_b": w[f"{stack}.kv_b"], "wo": w[f"{stack}.o_proj"]}

    return weights.check_tree({
        "embed": w["embed"], "head": w["head"],
        "final_norm": w["final_norm"],
        "lead": {"l0": {
            "s0_mla": attn("dense"),
            "s1_mlp": {"norm": w["dense.mlp_norm"], "wg": w["dense.gate"],
                       "wi": w["dense.up"], "wo": w["dense.down"]}}},
        "period": {"l0": {
            "s0_mla": attn("moe"),
            "s1_moe": {"norm": w["moe.mlp_norm"],
                       "router": w["moe.router"].astype(jnp.float32),
                       "wg": w["moe.gate"], "wi": w["moe.up"],
                       "wo": w["moe.down"],
                       "shared": {"wg": w["moe.shared_gate"],
                                  "wi": w["moe.shared_up"],
                                  "wo": w["moe.shared_down"]}}}},
    }, cfg)


# -------------------------------------------------------------- reference

def yarn(c: dict) -> tuple[np.ndarray, float, float]:
    """(inverse frequencies of the rotary pairs, factor on cos and sin,
    softmax scale), from DeepSeek-V2's published ``rope_scaling``
    (``DeepseekV2YarnRotaryEmbedding``, ``DeepseekV2Attention``)."""
    rs, dim, base = c["rope_scaling"], c["qk_rope_head_dim"], c["rope_theta"]
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))) \
            / (2 * math.log(base))

    def get_mscale(scale, mscale):
        return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    inv_freq = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    cos_scale = get_mscale(factor, rs["mscale"]) \
        / get_mscale(factor, rs["mscale_all_dim"])
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    scale = qk ** -0.5
    if rs["mscale_all_dim"]:
        scale *= get_mscale(factor, rs["mscale_all_dim"]) ** 2
    return inv_freq.astype(np.float32), float(cos_scale), float(scale)


def _rope(x, inv_freq, cos_scale):
    """x (B, L, heads, dim) at positions 0..L-1, half-split layout."""
    dim = x.shape[-1]
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq)
    emb = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    cos, sin = jnp.cos(emb) * cos_scale, jnp.sin(emb) * cos_scale
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _swiglu(h, g, u, dn, fp8):
    a = _mm(h, g, fp8)
    return _mm(a * jax.nn.sigmoid(a) * _mm(h, u, fp8), dn, fp8)


#: query rows attended at once: the float32 scores of one block,
#: (batch, heads, Q_BLOCK, L), stay under half a GB at 5120 positions
Q_BLOCK = 1024


@functools.partial(jax.jit, static_argnames=("c", "fp8"))
def _attention(x, w, i, c, fp8):
    """Latent attention of layer ``i`` of stack ``c.stack``, expanded as
    published, residual included; x (B, L, d) float32."""
    B, L, _ = x.shape
    H, r, nope, rp, v = c.H, c.r, c.nope, c.rope, c.v
    lw = {k: jax.lax.dynamic_index_in_dim(w[f"{c.stack}.{k}"], i,
                                          keepdims=False)
          for k in ATTN_KEYS}
    h = _rms(x, lw["attn_norm"], c.eps)
    q = _mm(h, lw["q_proj"], fp8).reshape(B, L, H, nope + rp)
    kv = _mm(h, lw["kv_a"], fp8)
    latent = _rms(kv[..., :r], lw["kv_norm"], c.eps)
    k_pe = _rope(kv[..., None, r:], c.inv_freq, c.cos_scale)
    kvb = _mm(latent, lw["kv_b"], fp8).reshape(B, L, H, nope + v)
    q = jnp.concatenate([q[..., :nope],
                         _rope(q[..., nope:], c.inv_freq, c.cos_scale)], -1)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe, (B, L, H, rp))], -1)
    val = kvb[..., nope:]
    n_blk = -(-L // Q_BLOCK)
    qb = jnp.pad(q, [(0, 0), (0, n_blk * Q_BLOCK - L), (0, 0), (0, 0)])
    qb = qb.reshape(B, n_blk, Q_BLOCK, H, nope + rp).transpose(1, 0, 2, 3, 4)

    def block(args):
        qc, j = args
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, k,
                       precision=jax.lax.Precision.HIGHEST) * c.scale
        causal = (j * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None] \
            >= jnp.arange(L)[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, val,
                          precision=jax.lax.Precision.HIGHEST)

    o = jax.lax.map(block, (qb, jnp.arange(n_blk)))
    o = o.transpose(1, 0, 2, 3, 4).reshape(B, n_blk * Q_BLOCK, H * v)[:, :L]
    return x + _mm(o, lw["o_proj"], fp8)


@functools.partial(jax.jit, static_argnames=("c", "fp8"))
def _dense_mlp(x, w, i, c, fp8):
    lw = {k: jax.lax.dynamic_index_in_dim(w[f"dense.{k}"], i, keepdims=False)
          for k in ("mlp_norm", "gate", "up", "down")}
    h = _rms(x, lw["mlp_norm"], c.eps)
    return x + _swiglu(h, lw["gate"], lw["up"], lw["down"], fp8)


@functools.partial(jax.jit, static_argnames=("c", "fp8"))
def _moe(x, w, i, c, fp8):
    """Routed experts (every row through every expert, weighted by its
    gate) and shared experts of MoE layer ``i``, residual included."""
    names = ("mlp_norm", "router", "gate", "up", "down", "shared_gate",
             "shared_up", "shared_down")
    lw = {k: jax.lax.dynamic_index_in_dim(w[f"moe.{k}"], i, keepdims=False)
          for k in names}
    h = _rms(x, lw["mlp_norm"], c.eps)
    scores = jax.nn.softmax(_mm(h, lw["router"], fp8), axis=-1)
    top, idx = jax.lax.top_k(scores, c.k)
    gate = jnp.sum(jax.nn.one_hot(idx, c.E) * top[..., None], axis=-2)

    def expert(acc, e):
        g, u, dn, ge = e
        return acc + ge[..., None] * _swiglu(h, g, u, dn, fp8), None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (lw["gate"], lw["up"], lw["down"], jnp.moveaxis(gate, -1, 0)))
    shared = _swiglu(h, lw["shared_gate"], lw["shared_up"],
                     lw["shared_down"], fp8)
    return x + routed + shared


@functools.partial(jax.jit, static_argnames=("eps", "vocab", "fp8"))
def _logits(x, rows, w, eps, vocab, fp8):
    x = _rms(x, w["final_norm"], eps)
    x = jnp.take_along_axis(x, rows[..., None], axis=1)
    return _mm(x, w["head"][:, :vocab], fp8)


class Dims(NamedTuple):
    """The hashable sizes the jitted pieces specialise on."""
    stack: str
    H: int
    r: int
    nope: int
    rope: int
    v: int
    E: int
    k: int
    eps: float
    inv_freq: tuple
    cos_scale: float
    scale: float


def dims_of(c: dict, stack: str) -> Dims:
    z = sizes(c)
    inv, cos_scale, scale = yarn(c)
    return Dims(stack, z["H"], z["r"], z["nope"], z["rope"], z["v"], z["E"],
                z["k"], float(c["rms_norm_eps"]),
                tuple(float(a) for a in inv), cos_scale, scale)


def forward_rows(w: dict, c: dict, tokens: np.ndarray, rows: np.ndarray,
                 *, fp8: bool = False) -> jax.Array:
    """Teacher-forced logits (``reference.forward_rows``)."""
    z = sizes(c)
    dense, moe = dims_of(c, "dense"), dims_of(c, "moe")
    with jax.default_matmul_precision("highest"):
        x = w["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for i in range(z["L"]):
            if i < z["D"]:
                j = jnp.int32(i)
                x = _attention(x, w, j, dense, fp8)
                x = _dense_mlp(x, w, j, dense, fp8)
            else:
                j = jnp.int32(i - z["D"])
                x = _attention(x, w, j, moe, fp8)
                x = _moe(x, w, j, moe, fp8)
        return _logits(x, jnp.asarray(rows), w, float(c["rms_norm_eps"]),
                       z["V"], fp8)


# ------------------------------------------------------------------- work

def latent_token_bytes(c: dict) -> int:
    """Cached bytes per token over every layer: the latent and the roped
    shared key."""
    z = sizes(c)
    return (z["r"] + z["rope"]) * work.BYTES * z["L"]


def _attn_calls(c: dict, rows: int) -> list[tuple[float, float]]:
    """One layer's attention projections at ``rows``: q, kv_a, kv_b's key
    half folded into the queries and its value half into the outputs,
    o."""
    z = sizes(c)
    d, H, r = z["d"], z["H"], z["r"]
    out = [work.matmul(rows, d, H * (z["nope"] + z["rope"])),
           work.matmul(rows, d, r + z["rope"])]
    for half in (z["nope"], z["v"]):        # per head (rows, half) x (half, r)
        out.append((2.0 * rows * H * half * r,
                    float(work.BYTES * H * (half * r + rows * (half + r)))))
    out.append(work.matmul(rows, H * z["v"], d))
    return out


def _moe_calls(c: dict, rows: int) -> list[tuple[float, float]]:
    """The router, the routed experts at ``rows * k`` routed rows (each
    expert's weights read once, for every expert the rows can reach) and
    the shared experts at every row."""
    z = sizes(c)
    d, f, sf = z["d"], z["f"], z["S"] * z["f"]
    out = [work.matmul(rows, d, z["E"])]
    used = min(z["E"], rows * z["k"])
    for K, N in ((d, f), (d, f), (f, d)):
        fl, b = work.matmul(rows * z["k"], K, N)
        out.append((fl, b + (used - 1) * work.BYTES * K * N))
    out += [work.matmul(rows, d, sf), work.matmul(rows, d, sf),
            work.matmul(rows, sf, d)]
    return out


def matmuls(c: dict, rows: int, head_rows: int) -> list[tuple[float, float]]:
    """(FLOPs, bytes) of every projection call of one step over ``rows``
    token rows, the head over ``head_rows``: one entry per call."""
    z = sizes(c)
    out = []
    if rows:
        dense = [work.matmul(rows, z["d"], z["F"])] * 2 \
            + [work.matmul(rows, z["F"], z["d"])]
        out += (_attn_calls(c, rows) + dense) * z["D"]
        out += (_attn_calls(c, rows) + _moe_calls(c, rows)) * z["M"]
    if head_rows:
        out.append(work.matmul(head_rows, z["d"], z["V"]))
    return out


def head_shape(c: dict) -> tuple[int, int]:
    return c["hidden_size"], c["vocab_size"]


def weight_map(c: dict) -> dict[tuple[int, int], tuple[int, int]]:
    """Each weight shape the program may hold -> the model's (K, N)."""
    z = sizes(c)
    d, H, r = z["d"], z["H"], z["r"]
    kn = [(d, H * (z["nope"] + z["rope"])), (d, r + z["rope"]),
          (r, H * (z["nope"] + z["v"])), (H * z["v"], d), (d, z["F"]),
          (z["F"], d), (d, z["E"]), (d, z["f"]), (z["f"], d),
          (d, z["S"] * z["f"]), (z["S"] * z["f"], d), (d, z["V"])]
    out = {x: x for x in kn}
    out[(d, weights.padded_vocab(z["V"]))] = (d, z["V"])
    return out


def moe_weight_map(c: dict) -> dict[tuple[int, int], tuple[int, int]]:
    """The entries of :func:`weight_map` that the MoE sublayer reads: the
    router, the routed experts, the shared experts."""
    z = sizes(c)
    d, f, sf = z["d"], z["f"], z["S"] * z["f"]
    return {kn: kn for kn in ((d, z["E"]), (d, f), (f, d), (d, sf), (sf, d))}


def _attention_work(c: dict, visible: int, tokens: int) -> tuple[float,
                                                                 float]:
    """Latent attention over ``visible`` (row, cached token) pairs: the
    scores over ``r + rope`` values and the weighted sum of ``r`` per
    head; ``tokens`` cached rows read."""
    z = sizes(c)
    flops = 2.0 * z["H"] * (2 * z["r"] + z["rope"]) * visible * z["L"]
    return flops, float(latent_token_bytes(c) * tokens)


def decode_step(c: dict, rows: int, ctx: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step (``work.decode_step``)."""
    calls = matmuls(c, rows, rows)
    fa, ba = _attention_work(c, ctx, ctx + rows)
    return sum(f for f, _ in calls) + fa, \
        sum(b for _, b in calls) + ba + rows * c["hidden_size"] * work.BYTES


def prefill_chunk(c: dict, start: int, valid: int,
                  final: bool) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill chunk (``work.prefill_chunk``)."""
    calls = matmuls(c, valid, 1 if final else 0)
    fa, ba = _attention_work(c, valid * start + valid * (valid + 1) // 2,
                             start + valid)
    return sum(f for f, _ in calls) + fa, \
        sum(b for _, b in calls) + ba + valid * c["hidden_size"] * work.BYTES
