"""Family ``tiny_moe``, for the CPU tests: a decoder whose every layer is
rotary attention (grouped key and value heads) and a top-k mixture of
SwiGLU experts (Mixtral's layout), joined to a copy of the benchmark as
new files only.  A test puts it under ``families/`` of that copy.

Weights (``weights.init`` draws them in bfloat16, like every family's):

    embed (Vp, d)  head (d, Vp)  final_norm (d,)
    attn_norm (L, d)  wq (L, d, H*hd)  wk, wv (L, d, Hkv*hd)  wo (L, H*hd, d)
    moe_norm (L, d)  router (L, d, E)
    w_gate, w_up (L, E, d, F)  w_down (L, E, F, d)

The reference: router logits in float32, the top k, a softmax over the
k chosen logits, each chosen expert's SwiGLU, summed by gate.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

import spec
import weights
import work
from reference import _fp8, _mm, _rms, _rope

KEYS = ("num_local_experts", "num_experts_per_tok")
#: an expert's capacity is ceil(N k / E x factor) of a call's N rows, so
#: it holds every row once the factor is E / k = 4: at 8 the program
#: drops no token, as the reference drops none
CAPACITY_FACTOR = 8.0
LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "moe_norm", "router",
              "w_gate", "w_up", "w_down")


def check(c: dict) -> None:
    if c["hidden_size"] % c["num_attention_heads"] \
            or not 0 < c["num_experts_per_tok"] <= c["num_local_experts"]:
        raise spec.SpecError(f"config {c['name']}: heads must divide "
                             f"hidden_size, and 0 < k <= experts")


def program_config(c: dict):
    """The program runs in float32: under bfloat16 activations near-tied
    router scores can pick another expert than the float32 reference,
    and this family checks the seam, not precision."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(c["arch"]), **c["overrides"],
                              capacity_factor=CAPACITY_FACTOR,
                              dtype=jnp.float32)
    want = {"d_model": c["hidden_size"], "d_ff_expert": c["intermediate_size"],
            "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"],
            "n_layers": c["num_hidden_layers"],
            "n_experts": c["num_local_experts"],
            "experts_per_token": c["num_experts_per_tok"],
            "vocab_size": c["vocab_size"], "norm_eps": c["rms_norm_eps"],
            "rope_theta": c["rope_theta"], "window": None, "family": "moe",
            "tie_embeddings": c.get("tie_word_embeddings", False)}
    have = {k: getattr(cfg, k) for k in want}
    if have != want:
        raise spec.SpecError(f"repo arch {c['arch']} with {c['overrides']} "
                             f"is {have}, the file states {want}")
    return cfg


def _sizes(c: dict):
    d, H = c["hidden_size"], c["num_attention_heads"]
    return (d, c["intermediate_size"], H, c["num_key_value_heads"], d // H,
            c["num_hidden_layers"], c["num_local_experts"],
            c["num_experts_per_tok"], c["vocab_size"])


def shapes(c: dict) -> dict:
    d, F, H, Hkv, hd, L, E, _, V = _sizes(c)
    Vp = weights.padded_vocab(V)
    bf, f32 = jnp.bfloat16, jnp.float32
    return {
        "embed": ((Vp, d), bf), "head": ((d, Vp), bf),
        "final_norm": ((d,), f32),
        "attn_norm": ((L, d), f32), "wq": ((L, d, H * hd), bf),
        "wk": ((L, d, Hkv * hd), bf), "wv": ((L, d, Hkv * hd), bf),
        "wo": ((L, H * hd, d), bf),
        "moe_norm": ((L, d), f32), "router": ((L, d, E), bf),
        "w_gate": ((L, E, d, F), bf), "w_up": ((L, E, d, F), bf),
        "w_down": ((L, E, F, d), bf),
    }


def to_program(w: dict, cfg) -> dict:
    """float32 copies of the weights under the program's tree."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    return weights.check_tree({
        "embed": w["embed"], "head": w["head"],
        "final_norm": w["final_norm"],
        "period": {"l0": {
            "s0_attn": {"norm": w["attn_norm"], "wq": w["wq"],
                        "wk": w["wk"], "wv": w["wv"], "wo": w["wo"]},
            "s1_moe": {"norm": w["moe_norm"], "router": w["router"],
                       "wg": w["w_gate"], "wi": w["w_up"],
                       "wo": w["w_down"]},
        }},
    }, cfg)


def _emm(a, w, spec_, fp8):
    a, w = a.astype(jnp.float32), w.astype(jnp.float32)
    if fp8:
        a, w = _fp8(a), _fp8(w)
    return jnp.einsum(spec_, a, w, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("c", "fp8"))
def _layer(x, w, i, c, fp8):
    """One layer on x (B, L, d) float32; ``c`` = (H, Hkv, E, k, eps,
    theta)."""
    H, Hkv, E, k, eps, theta = c
    B, L, d = x.shape
    hd = d // H
    lw = {n: jax.lax.dynamic_index_in_dim(w[n], i, keepdims=False)
          for n in LAYER_KEYS}
    h = _rms(x, lw["attn_norm"], eps)
    q = _rope(_mm(h, lw["wq"], fp8).reshape(B, L, H, hd), theta)
    kk = _rope(_mm(h, lw["wk"], fp8).reshape(B, L, Hkv, hd), theta)
    v = _mm(h, lw["wv"], fp8).reshape(B, L, Hkv, hd)
    kk = jnp.repeat(kk, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((L, L), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(B, L, H * hd)
    x = x + _mm(o, lw["wo"], fp8)
    h = _rms(x, lw["moe_norm"], eps)
    top, idx = jax.lax.top_k(_mm(h, lw["router"], fp8), k)
    gate = jnp.sum(jax.nn.one_hot(idx, E) * jax.nn.softmax(top)[..., None],
                   axis=-2)                                    # (B, L, E)
    g = _emm(h, lw["w_gate"], "bld,edf->blef", fp8)
    u = _emm(h, lw["w_up"], "bld,edf->blef", fp8)
    y = _emm(g * jax.nn.sigmoid(g) * u, lw["w_down"], "blef,efd->bled", fp8)
    return x + jnp.einsum("ble,bled->bld", gate, y,
                          precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("eps", "vocab", "fp8"))
def _logits(x, rows, w, eps, vocab, fp8):
    x = _rms(x, w["final_norm"], eps)
    x = jnp.take_along_axis(x, rows[..., None], axis=1)
    return _mm(x, w["head"][:, :vocab], fp8)


def forward_rows(w: dict, c: dict, tokens, rows, *, fp8: bool = False):
    _, _, H, Hkv, _, L, E, k, V = _sizes(c)
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    with jax.default_matmul_precision("highest"):
        x = w["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for i in range(L):
            x = _layer(x, w, jnp.int32(i), (H, Hkv, E, k, eps, theta), fp8)
        return _logits(x, jnp.asarray(rows), w, eps, V, fp8)


# work: attention's projections at every row, the router, and each row's
# k experts (an expert's weights read once per call that uses it)

def _layer_calls(c: dict, rows: int) -> list[tuple[float, float]]:
    d, F, H, Hkv, hd, _, E, k, _ = _sizes(c)
    out = [work.matmul(rows, K, N) for K, N in
           ((d, H * hd), (d, Hkv * hd), (d, Hkv * hd), (H * hd, d), (d, E))]
    used = min(E, rows * k)
    for K, N in ((d, F), (d, F), (F, d)):
        f, b = work.matmul(rows * k, K, N)
        out.append((f, b + (used - 1) * work.BYTES * K * N))
    return out


def matmuls(c: dict, rows: int, head_rows: int) -> list[tuple[float, float]]:
    d, *_, L, _, _, V = _sizes(c)
    out = _layer_calls(c, rows) * L if rows else []
    if head_rows:
        out.append(work.matmul(head_rows, d, V))
    return out


def head_shape(c: dict) -> tuple[int, int]:
    return c["hidden_size"], c["vocab_size"]


def weight_map(c: dict) -> dict:
    d, F, H, Hkv, hd, _, E, _, V = _sizes(c)
    kn = [(d, H * hd), (d, Hkv * hd), (H * hd, d), (d, E), (d, F), (F, d),
          (d, V)]
    out = {x: x for x in kn}
    out[(d, weights.padded_vocab(V))] = (d, V)
    return out


def _attn(c: dict, visible: int, tokens: int) -> tuple[float, float]:
    _, _, H, Hkv, hd, L, *_ = _sizes(c)
    return 4.0 * H * hd * visible * L, \
        float(2 * Hkv * hd * work.BYTES * L * tokens)


def decode_step(c: dict, rows: int, ctx: int) -> tuple[float, float]:
    calls = matmuls(c, rows, rows)
    fa, ba = _attn(c, ctx, ctx + rows)
    return sum(f for f, _ in calls) + fa, sum(b for _, b in calls) + ba


def prefill_chunk(c: dict, start: int, valid: int,
                  final: bool) -> tuple[float, float]:
    calls = matmuls(c, valid, 1 if final else 0)
    fa, ba = _attn(c, valid * start + valid * (valid + 1) // 2,
                   start + valid)
    return sum(f for f, _ in calls) + fa, sum(b for _, b in calls) + ba
