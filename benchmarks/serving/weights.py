"""Random weights for a configuration, drawn on the device from the seed.

The benchmark owns the weights: one jitted call draws every matrix in
bfloat16 (the type it is served in) from ``--seed``, in the layout below.
:func:`to_program` hands the same arrays to the program under its own
parameter tree, and the plain reference (``reference.py``) reads the
layout below and nothing of the program.

Layout (``L`` layers, ``Vp`` the vocabulary rounded up to 256 rows, which
the program's tables need; ids at or above ``vocab_size`` never occur):

    embed (Vp, d)  head (d, Vp)  final_norm (d,)
    attn_norm (L, d)  wq (L, d, H*hd)  wk, wv (L, d, Hkv*hd)  wo (L, H*hd, d)
    mlp_norm (L, d)  w_gate, w_up (L, d, F)  w_down (L, F, d)

Matrices are N(0, 1/fan_in) (the embedding N(0, 0.02^2)); norm gains are
1 + 0.1 N(0, 1) in float32, so a norm that drops its gain is seen.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
GAIN_STD = 0.1


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


def shapes(c: dict) -> dict[str, tuple[tuple[int, ...], object]]:
    """name -> (shape, dtype) of every weight of configuration ``c``."""
    d, F, L = c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // H
    Vp = padded_vocab(c["vocab_size"])
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


def seed_key(seed: int) -> jax.Array:
    """A threefry key from every bit of ``seed`` (``jax.random.key``
    keeps only the low 32 bits of a larger seed)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def _draw(key, shape, dtype, name):
    if dtype == jnp.float32:                     # a norm gain
        return 1.0 + GAIN_STD * jax.random.normal(key, shape, jnp.float32)
    std = EMBED_STD if name == "embed" else 1.0 / np.sqrt(shape[-2])
    return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)


def init(c: dict, seed: int) -> dict:
    """Every weight of ``c``, drawn on the default device in one jitted
    call."""
    spec = shapes(c)
    names = sorted(spec)

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(names))
        return {n: _draw(k, *spec[n], n) for n, k in zip(names, keys)}

    return draw(seed_key(seed))


def to_program(w: dict, cfg) -> dict:
    """The program's parameter tree (``repro.models.lm.model_defs``) over
    the same arrays: no copy.  Raises if a shape or type disagrees."""
    from repro.models import lm
    from repro.parallel.sharding import PV

    tree = {
        "embed": w["embed"], "head": w["head"],
        "final_norm": w["final_norm"],
        "period": {"l0": {
            "s0_attn": {"norm": w["attn_norm"], "wq": w["wq"],
                        "wk": w["wk"], "wv": w["wv"], "wo": w["wo"]},
            "s1_mlp": {"norm": w["mlp_norm"], "wg": w["w_gate"],
                       "wi": w["w_up"], "wo": w["w_down"]},
        }},
    }
    defs = lm.model_defs(cfg)
    want = jax.tree.map(lambda pv: (tuple(pv.shape), jnp.dtype(pv.dtype)),
                        defs, is_leaf=lambda x: isinstance(x, PV))
    have = jax.tree.map(lambda a: (tuple(a.shape), jnp.dtype(a.dtype)), tree)
    if want != have:
        raise ValueError(f"weights do not match the program's tree for "
                         f"{cfg.name}: program {want}, benchmark {have}")
    return tree
