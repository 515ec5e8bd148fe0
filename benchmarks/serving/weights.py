"""Random weights for a configuration, drawn on the device from the seed.

The benchmark owns the weights: one jitted call draws every array in the
type it is served in from ``--seed``, in the layout of the
configuration's family (``families/<family>.py``: ``shapes``).  The
family's ``to_program`` hands the same arrays to the program under its
own parameter tree, and its plain reference reads the family's layout
and nothing of the program.

Matrices are N(0, 1/fan_in) (the embedding N(0, 0.02^2)); norm gains are
1 + 0.1 N(0, 1) in float32, so a norm that drops its gain is seen.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import spec

EMBED_STD = 0.02
GAIN_STD = 0.1


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


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
    """Every weight of ``c`` in its family's layout (name -> (shape,
    dtype)), drawn on the default device in one jitted call."""
    sizes = spec.family(c).shapes(c)
    names = sorted(sizes)

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(names))
        return {n: _draw(k, *sizes[n], n) for n, k in zip(names, keys)}

    return draw(seed_key(seed))


def check_tree(tree: dict, cfg) -> dict:
    """``tree`` if its shapes and types are those of the program's
    parameter tree (``repro.models.lm.model_defs``) for ``cfg``; raises
    otherwise."""
    from repro.models import lm
    from repro.parallel.sharding import PV

    defs = lm.model_defs(cfg)
    want = jax.tree.map(lambda pv: (tuple(pv.shape), jnp.dtype(pv.dtype)),
                        defs, is_leaf=lambda x: isinstance(x, PV))
    have = jax.tree.map(lambda a: (tuple(a.shape), jnp.dtype(a.dtype)), tree)
    if want != have:
        raise ValueError(f"weights do not match the program's tree for "
                         f"{cfg.name}: program {want}, benchmark {have}")
    return tree

