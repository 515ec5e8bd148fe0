"""Family ``dense``: a decoder of pre-norm RMSNorm, rotary multi-head
attention (grouped key and value heads) and a SwiGLU MLP in every layer,
head_dim ``hidden_size / num_attention_heads``: phi3-mini, deepseek-llm.

Weights (``weights.init`` draws them; ``L`` layers, ``Vp`` the
vocabulary rounded up to 256 rows, which the program's tables need; ids
at or above ``vocab_size`` never occur):

    embed (Vp, d)  head (d, Vp)  final_norm (d,)
    attn_norm (L, d)  wq (L, d, H*hd)  wk, wv (L, d, Hkv*hd)  wo (L, H*hd, d)
    mlp_norm (L, d)  w_gate, w_up (L, d, F)  w_down (L, F, d)

The plain reference reads that layout and nothing of the program: one
layer at a time in float32, every product at ``highest`` precision.
The work counts are of dense projections and ``2 Hkv hd`` key and value
values per token and layer.
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
from reference import _mm, _rms, _rope

#: published keys read beyond ``spec.CONFIG_KEYS``
KEYS = ()
LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
              "w_up", "w_down")


def check(c: dict) -> None:
    if c["hidden_size"] % c["num_attention_heads"]:
        raise spec.SpecError(f"config {c['name']}: heads do not divide "
                             f"hidden_size")


def program_config(c: dict):
    """The repo's model configuration for ``c``, checked against the
    published sizes the file states."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(c["arch"]), **c["overrides"])
    want = {"d_model": c["hidden_size"], "d_ff": c["intermediate_size"],
            "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"],
            "n_layers": c["num_hidden_layers"],
            "vocab_size": c["vocab_size"], "norm_eps": c["rms_norm_eps"],
            "rope_theta": c["rope_theta"], "window": None,
            "tie_embeddings": c.get("tie_word_embeddings", False)}
    have = {k: getattr(cfg, k) for k in want}
    if have != want or cfg.n_experts or cfg.family != "dense":
        raise spec.SpecError(f"repo arch {c['arch']} with {c['overrides']} "
                             f"is {have}, the file states {want}")
    return cfg


# ---------------------------------------------------------------- weights

def shapes(c: dict) -> dict[str, tuple[tuple[int, ...], object]]:
    """name -> (shape, dtype) of every weight of configuration ``c``."""
    d, F, L = c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // H
    Vp = weights.padded_vocab(c["vocab_size"])
    bf, f32 = jnp.bfloat16, jnp.float32
    return {
        "embed": ((Vp, d), bf), "head": ((d, Vp), bf),
        "final_norm": ((d,), f32),
        "attn_norm": ((L, d), f32), "wq": ((L, d, H * hd), bf),
        "wk": ((L, d, Hkv * hd), bf), "wv": ((L, d, Hkv * hd), bf),
        "wo": ((L, H * hd, d), bf),
        "mlp_norm": ((L, d), f32), "w_gate": ((L, d, F), bf),
        "w_up": ((L, d, F), bf), "w_down": ((L, F, d), bf),
    }


def to_program(w: dict, cfg) -> dict:
    """The program's parameter tree (``repro.models.lm.model_defs``) over
    the same arrays: no copy.  Raises if a shape or type disagrees."""
    return weights.check_tree({
        "embed": w["embed"], "head": w["head"],
        "final_norm": w["final_norm"],
        "period": {"l0": {
            "s0_attn": {"norm": w["attn_norm"], "wq": w["wq"],
                        "wk": w["wk"], "wv": w["wv"], "wo": w["wo"]},
            "s1_mlp": {"norm": w["mlp_norm"], "wg": w["w_gate"],
                       "wi": w["w_up"], "wo": w["w_down"]},
        }},
    }, cfg)


# -------------------------------------------------------------- reference

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
    """Teacher-forced logits (``reference.forward_rows``)."""
    dims = Dims(c)
    with jax.default_matmul_precision("highest"):
        x = _embed(jnp.asarray(tokens), w, dims)
        for i in range(dims.layers):
            x = _layer(x, w, jnp.int32(i), dims, fp8)
        return _logits(x, jnp.asarray(rows), w, dims, fp8)


# ------------------------------------------------------------------- work

def dims(c: dict) -> dict:
    d, H = c["hidden_size"], c["num_attention_heads"]
    hd = d // H
    return {"d": d, "F": c["intermediate_size"], "H": H, "hd": hd,
            "Hkv": c["num_key_value_heads"], "L": c["num_hidden_layers"],
            "V": c["vocab_size"]}


def layer_mats(c: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of one layer's projections."""
    m = dims(c)
    d, F, q, kv = m["d"], m["F"], m["H"] * m["hd"], m["Hkv"] * m["hd"]
    return [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
            ("w_gate", d, F), ("w_up", d, F), ("w_down", F, d)]


def head_shape(c: dict) -> tuple[int, int]:
    m = dims(c)
    return m["d"], m["V"]


def weight_map(c: dict) -> dict[tuple[int, int], tuple[int, int]]:
    """Each weight shape the program may hold -> the model's (K, N): the
    head's vocabulary padded to 256 rows maps to the published one."""
    m = dims(c)
    vp = -(-m["V"] // 256) * 256
    out = {(k, n): (k, n) for _, k, n in layer_mats(c)}
    out[(m["d"], m["V"])] = out[(m["d"], vp)] = (m["d"], m["V"])
    return out


def kv_token_bytes(c: dict) -> int:
    m = dims(c)
    return 2 * m["Hkv"] * m["hd"] * work.BYTES * m["L"]


def matmuls(c: dict, rows: int, head_rows: int) -> list[tuple[float, float]]:
    """(FLOPs, bytes) of every projection call of one step over ``rows``
    token rows, the head over ``head_rows``: one entry per call."""
    m = dims(c)
    out = []
    if rows:
        per_layer = [work.matmul(rows, K, N) for _, K, N in layer_mats(c)]
        out += per_layer * m["L"]
    if head_rows:
        out.append(work.matmul(head_rows, m["d"], m["V"]))
    return out


def decode_step(c: dict, rows: int, ctx: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step (``work.decode_step``).  Bytes
    count every weight once (the step reads them all whatever its batch),
    the live keys and values read, the new ones written and the
    projections' activations; attention's FLOPs are 4 hd per head, row
    and visible key."""
    m = dims(c)
    calls = matmuls(c, rows, rows)
    flops = sum(f for f, _ in calls) + 4.0 * m["H"] * m["hd"] * ctx * m["L"]
    byts = sum(b for _, b in calls) + kv_token_bytes(c) * (ctx + rows) \
        + rows * m["d"] * work.BYTES
    return flops, byts


def prefill_chunk(c: dict, start: int, valid: int,
                  final: bool) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill chunk (``work.prefill_chunk``)."""
    m = dims(c)
    calls = matmuls(c, valid, 1 if final else 0)
    visible = valid * start + valid * (valid + 1) // 2
    flops = sum(f for f, _ in calls) \
        + 4.0 * m["H"] * m["hd"] * visible * m["L"]
    byts = sum(b for _, b in calls) \
        + kv_token_bytes(c) * (start + valid) + valid * m["d"] * work.BYTES
    return flops, byts
