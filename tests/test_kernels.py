"""Per-kernel interpret-mode sweeps against the pure-jnp oracles.

Every Pallas kernel is validated over a shape x dtype grid plus a
hypothesis-driven randomized sweep (paper-kernel semantics on top in
tests/test_core_multidevice.py and the ISA layer).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

jax.config.update("jax_platform_name", "cpu")

RNG = np.random.default_rng(42)


def rand(shape, dtype):
    x = RNG.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: dict(rtol=1e-4, atol=1e-4),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 128, 384),
                                   (128, 256, 512)])
def test_matmul(shape, dtype):
    M, N, K = shape
    a, b = rand((M, K), dtype), rand((K, N), dtype)
    got = ops.matmul(a, b, use_pallas=True)
    want = ref.matmul(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("hw", [(16, 256), (8, 512), (24, 128)])
def test_jacobi2d(hw, dtype):
    x = rand(hw, dtype)
    got = ops.jacobi2d(x, use_pallas=True, bh=8, bw=128)
    want = ref.jacobi2d(jnp.pad(x, 1))
    np.testing.assert_allclose(got, want, **TOL[dtype])


@pytest.mark.parametrize("f", [(7, 7), (3, 3)])
def test_fconv2d(f):
    x = rand((16 + f[0] - 1, 256 + f[1] - 1), jnp.float32)
    filt = rand(f, jnp.float32)
    got = ops.fconv2d(x, filt, use_pallas=True, bh=8, bw=128)
    want = ref.fconv2d(x, filt)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n", [16384, 8 * 2048 * 3])
def test_dotprod(n):
    a, b = rand((n,), jnp.float32), rand((n,), jnp.float32)
    got = ops.dotprod(a, b, use_pallas=True)
    np.testing.assert_allclose(float(got), float(ref.dotprod(a, b)),
                               rtol=1e-4)


def test_expv_polynomial_accuracy():
    x = jnp.asarray(RNG.uniform(-20, 20, size=16384), jnp.float32)
    got = ops.expv(x, use_pallas=True)
    np.testing.assert_allclose(got, np.exp(np.asarray(x)), rtol=3e-6)


@pytest.mark.parametrize("rw", [(8, 512), (32, 1024), (16, 128)])
def test_softmax_rows(rw):
    x = rand(rw, jnp.float32) * 4
    got = ops.softmax_rows(x, use_pallas=True)
    want = ref.softmax_rows(x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got).sum(-1), 1.0, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("cfg", [
    dict(B=1, Hq=4, Hkv=2, S=256, D=64, causal=True, window=None),
    dict(B=2, Hq=4, Hkv=4, S=128, D=64, causal=False, window=None),
    dict(B=1, Hq=8, Hkv=2, S=256, D=32, causal=True, window=128),
])
def test_flash_attention(cfg, dtype):
    B, Hq, Hkv, S, D = cfg["B"], cfg["Hq"], cfg["Hkv"], cfg["S"], cfg["D"]
    q = rand((B, Hq, S, D), dtype)
    k = rand((B, Hkv, S, D), dtype)
    v = rand((B, Hkv, S, D), dtype)
    got = ops.attention(q, k, v, causal=cfg["causal"], window=cfg["window"],
                        use_pallas=True, bq=64, bk=64)
    want = ref.attention(q, k, v, causal=cfg["causal"], window=cfg["window"])
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               **TOL[dtype])


@pytest.mark.parametrize("shape", [(8, 512), (32, 4096), (16, 3072),
                                   (4, 3072), (20, 3072)])
def test_rmsnorm(shape):
    """Rows below the block take the whole of R; 20 rows pad to 24."""
    x = rand(shape, jnp.float32)
    g = rand((shape[-1],), jnp.float32)
    got = ops.rmsnorm(x, g, use_pallas=True)
    np.testing.assert_allclose(got, ref.rmsnorm(x, g), rtol=2e-5, atol=2e-5)


def _paged_case(hd, G, dtype, *, H=4, n=3, NB=12, bt=8, max_blocks=4):
    """A stacked pool whose layers other than 1 hold NaN, and four slots
    at lens 0 (dead), 1, one whole block and max_seq, the last two
    sharing their first block."""
    from repro.models.layers import pool_row
    row = (H, hd) if hd % 128 == 0 else (pool_row(H * hd),)
    kp = jnp.full((n, NB, bt) + row, jnp.nan, dtype)
    vp = jnp.full((n, NB, bt) + row, jnp.nan, dtype)
    kp = kp.at[1].set(rand((NB, bt) + row, dtype))
    vp = vp.at[1].set(rand((NB, bt) + row, dtype))
    tables = np.zeros((4, max_blocks), np.int32)
    tables[1, 0] = 1
    tables[2, 0] = tables[3, 0] = 2                     # a shared block
    tables[3, 1:] = [5, 9, 3]
    lens = np.array([0, 1, bt, bt * max_blocks], np.int32)
    q = rand((4, H, G, hd), dtype)
    return q, kp, vp, jnp.int32(1), jnp.asarray(tables), jnp.asarray(lens)


@pytest.mark.parametrize("pages", [1, 3, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("hd,G", [(96, 1), (96, 2), (128, 1), (128, 4)],
                         ids=["packed", "packed-gqa", "heads", "heads-gqa"])
def test_paged_attention(hd, G, dtype, pages):
    """The live-block kernel against the gather + softmax the model runs
    off the TPU (``layers._attend`` over ``layers._context``), in both row
    layouts: one packed row of heads of 96, or whole heads of 128.  Only
    layer 1's blocks are finite, so reading any other layer's shows as
    NaN; the dead slot gives zeros.  The kernel runs in the TPU interpret
    mode, which lands each copy only at its wait, fills unwritten
    on-chip memory with NaN and looks for races between copies and
    reads."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels import paged_attention as pa
    from repro.models import layers as L
    q, kp, vp, i, tables, lens = _paged_case(hd, G, dtype)
    B, H = q.shape[:2]
    W = tables.shape[1] * kp.shape[2]

    def gathered():
        ck = L._context(kp[i, tables], (B, W), (H, hd))
        cv = L._context(vp[i, tables], (B, W), (H, hd))
        idx = jnp.arange(W)
        return L._attend(q[:, None], ck, cv, lambda: (
            idx < lens[:, None])[:, None, None, None, :])[:, 0]

    got = np.asarray(pa.paged_attention(
        q, kp, vp, i, tables, lens, pages=pages,
        interpret=pltpu.InterpretParams(detect_races=True)))
    assert not interpret_pallas_call.races.races_found
    want = np.asarray(ops.paged_attention(q, kp, vp, i, tables, lens,
                                          gathered, use_pallas=False))
    assert got.shape == q.shape and got.dtype == np.float32
    assert np.isfinite(got).all()
    assert not got[0].any()                             # the dead slot
    np.testing.assert_allclose(got[1:], want[1:], rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# hypothesis sweeps (randomized shapes within tiling envelopes)
# ---------------------------------------------------------------------------

@given(m=st.integers(1, 3), n=st.integers(1, 3), k=st.integers(1, 4))
@settings(max_examples=8, deadline=None)
def test_matmul_shape_sweep(m, n, k):
    a = rand((m * 128, k * 128), jnp.float32)
    b = rand((k * 128, n * 128), jnp.float32)
    got = ops.matmul(a, b, use_pallas=True)
    np.testing.assert_allclose(got, ref.matmul(a, b), rtol=1e-4, atol=1e-3)


@given(s=st.sampled_from([64, 128, 192]), hq=st.sampled_from([2, 4]),
       g=st.sampled_from([1, 2]), causal=st.booleans())
@settings(max_examples=8, deadline=None)
def test_attention_shape_sweep(s, hq, g, causal):
    hkv = hq // g
    q = rand((1, hq, s, 32), jnp.float32)
    k = rand((1, hkv, s, 32), jnp.float32)
    v = rand((1, hkv, s, 32), jnp.float32)
    got = ops.attention(q, k, v, causal=causal, use_pallas=True, bq=64, bk=64)
    want = ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)
