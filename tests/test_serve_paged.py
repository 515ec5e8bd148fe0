"""Paged-KV serving: dense-vs-paged bit-identity + COW + chunked prefill on
8 fake devices (subprocess check), allocator units, submit() boundary, the
per-slot decode-position equivalence, and the BENCH_serve.json >= 2x
paged-concurrency acceptance pin."""
import numpy as np
import pytest

from repro.analysis.bench import load_serve_bench, validate_serve_bench
from repro.serve import (BlockAllocator, PromptTooLongError, Request,
                         kv_token_bytes, validate_prompt)
from repro.serve.paged import prefix_keys
from repro.testing.subproc import run_check


def test_serve_paged_multidevice():
    out = run_check("repro.testing.check_serve_paged", devices=8)
    assert "check_serve_paged OK" in out


# ---------------------------------------------------------------------------
# BlockAllocator units
# ---------------------------------------------------------------------------

def test_alloc_free_bookkeeping():
    a = BlockAllocator(4, 8)
    assert a.n_free == 4 and a.n_allocated == 0
    b1, b2 = a.alloc(), a.alloc()
    assert (b1, b2) == (1, 2)               # lowest ids first, 0 reserved
    assert a.n_allocated == 2 and a.peak_allocated == 2
    a.release(b1)
    assert a.n_free == 3
    assert a.alloc() == 1                   # freed id comes back
    a.release(1)
    a.release(b2)
    assert a.n_allocated == 0 and a.peak_allocated == 2


def test_alloc_exhaustion_raises():
    a = BlockAllocator(2, 8)
    a.alloc(), a.alloc()
    with pytest.raises(RuntimeError, match="exhausted"):
        a.alloc()


def test_refcount_sharing_and_release():
    a = BlockAllocator(4, 8)
    key = ("full", (1, 2, 3))
    bid = a.alloc(key)
    assert a.lookup(key) == bid
    a.retain(bid)
    assert a.refcount[bid] == 2 and a.shared_hits == 1
    a.release(bid)                          # one sharer gone: still keyed
    assert a.refcount[bid] == 1 and a.lookup(key) == bid
    a.release(bid)                          # last ref: key dropped, freed
    assert a.lookup(key) is None
    assert a.n_allocated == 0


def test_register_first_writer_wins_and_forget():
    a = BlockAllocator(4, 8)
    key = ("part", (9, 9))
    b1 = a.alloc(key)
    b2 = a.alloc(key)                       # duplicate content: stays private
    assert a.lookup(key) == b1
    a.forget_key(b2)                        # no-op: b2 never owned the key
    assert a.lookup(key) == b1
    a.forget_key(b1)                        # pre-divergence unpublish
    assert a.lookup(key) is None
    assert a.refcount[b1] == 1              # forget does not free


def test_prefix_keys_name_each_block_by_its_whole_prefix():
    prompt = list(range(100, 137))          # 2 full blocks of 16 + 5 tokens
    keys = prefix_keys(prompt, 16)
    assert [k[0] for k in keys] == ["full", "full", "part"]
    # the same tokens give the same keys whatever container holds them
    assert prefix_keys(np.asarray(prompt, np.int32), 16) == keys
    assert prefix_keys(np.asarray(prompt, np.int64), 16) == keys
    # a block's key covers every token before it: a change in block 0
    # changes every later key, a change in the tail changes only "part"
    early = prefix_keys([7] + prompt[1:], 16)
    assert all(a != b for a, b in zip(early, keys))
    late = prefix_keys(prompt[:-1] + [7], 16)
    assert late[:2] == keys[:2] and late[2] != keys[2]
    # a prompt of whole blocks has no "part" key; its first blocks' keys
    # are those of any longer prompt that starts with it
    whole = prefix_keys(prompt[:32], 16)
    assert whole == keys[:2]
    assert len(set(keys)) == len(keys)


# ---------------------------------------------------------------------------
# submit() boundary (the silent-overflow bugfix)
# ---------------------------------------------------------------------------

def test_validate_prompt_boundary():
    assert validate_prompt(np.arange(63, dtype=np.int32), 64) == 63
    with pytest.raises(PromptTooLongError, match="64-position cache"):
        validate_prompt(np.arange(64, dtype=np.int32), 64)
    with pytest.raises(PromptTooLongError):
        validate_prompt(np.arange(100, dtype=np.int32), 64)
    with pytest.raises(ValueError, match="empty"):
        validate_prompt(np.zeros(0, np.int32), 64)


def test_engine_submit_rejects_oversized_prompt():
    import jax
    from repro.configs import get_smoke_config
    from repro.models import lm
    from repro.parallel.sharding import default_rules, init_params
    from repro.serve import (PagedServeConfig, PagedServingEngine,
                             ServeConfig, ServingEngine)
    cfg = get_smoke_config("llama3-8b")
    rules = default_rules(None)
    params = init_params(lm.model_defs(cfg), jax.random.key(0))
    dense = ServingEngine(cfg, params, rules,
                          ServeConfig(max_batch=2, max_seq=32))
    paged = PagedServingEngine(cfg, params, rules,
                               PagedServeConfig(max_batch=2, max_seq=32,
                                                block_tokens=8, n_blocks=8))
    bad = Request(rid=0, prompt=np.ones(32, np.int32), max_new_tokens=4)
    for eng in (dense, paged):
        with pytest.raises(PromptTooLongError):
            eng.submit(bad)
        assert eng.n_waiting == 0           # rejected before enqueue
    ok = Request(rid=1, prompt=np.ones(31, np.int32), max_new_tokens=4)
    dense.submit(ok)                        # boundary length is admissible
    assert dense.n_waiting == 1


# ---------------------------------------------------------------------------
# per-slot decode positions (the shared-max-pos bugfix)
# ---------------------------------------------------------------------------

def test_decode_step_vector_pos_matches_scalar():
    """For equal-length slots the vectorised per-slot position path must be
    bit-identical to the historical scalar-pos path (same logits, same
    cache) — the regression guard for the pos = max(slot_pos) retirement."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.models import lm
    from repro.parallel.sharding import default_rules, init_params
    cfg = get_smoke_config("llama3-8b")
    rules = default_rules(None)
    params = init_params(lm.model_defs(cfg), jax.random.key(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 6)), jnp.int32)
    cache, _ = lm.prefill(params, toks, cfg, rules, 32)
    step_tok = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 1)), jnp.int32)
    lg_s, c_s = lm.decode_step(params, step_tok, cache, 6, cfg, rules)
    lg_v, c_v = lm.decode_step(params, step_tok, cache,
                               jnp.array([6, 6], jnp.int32), cfg, rules)
    assert jnp.array_equal(lg_s, lg_v)
    for a, b in zip(jax.tree.leaves(c_s), jax.tree.leaves(c_v)):
        assert jnp.array_equal(a, b)


# ---------------------------------------------------------------------------
# block sizing: the pool only has to tile max_seq (no register budget)
# ---------------------------------------------------------------------------

def test_block_sizing_respects_vreg_budget():
    """On the TPU the paged pool is sized by tiling alone: a one-layer cut
    of phi3-mini at its published widths takes 64-token blocks, whose K
    block (64 x 32 x 96 bf16 = 384 KiB) is six times the RISC-V register
    group that used to cap it at 2 tokens.  A block that does not tile
    max_seq is still refused."""
    import dataclasses
    from repro.configs import get_config
    from repro.parallel.sharding import default_rules
    from repro.serve import PagedServeConfig, PagedServingEngine
    cfg = dataclasses.replace(get_config("phi3-mini-3.8b"), n_layers=1)
    rules = default_rules(None)
    eng = PagedServingEngine(cfg, None, rules,
                             PagedServeConfig(max_batch=2, max_seq=256,
                                              block_tokens=64, n_blocks=4,
                                              chunk=128))
    pk = eng.pool["l0"]["s0_attn"]["k"]
    assert pk.shape == (1, 5, 64, cfg.n_kv_heads, cfg.head_dim)
    assert 64 * cfg.n_kv_heads * cfg.head_dim * 2 == 6 * 65536
    with pytest.raises(ValueError, match="multiple of"):
        PagedServingEngine(cfg, None, rules,
                           PagedServeConfig(max_batch=2, max_seq=256,
                                            block_tokens=48, n_blocks=4))
    assert kv_token_bytes(cfg) == 2 * cfg.n_kv_heads * cfg.head_dim * 2


# ---------------------------------------------------------------------------
# the recorded ablation: paged serves >= 2x dense concurrency at equal KV
# ---------------------------------------------------------------------------

def test_bench_serve_concurrency_acceptance():
    doc = load_serve_bench()
    if doc is None:
        pytest.skip("BENCH_serve.json not recorded yet "
                    "(python -m benchmarks.run serve)")
    assert validate_serve_bench(doc) == []
    arms = doc["open_loop"]
    dense, paged = arms["dense"], arms["paged"]
    # equal device memory is the premise of the comparison
    assert paged["kv_bytes_capacity"] == dense["kv_bytes_capacity"]
    assert paged["max_concurrent"] >= 2 * dense["max_concurrent"], \
        (paged["max_concurrent"], dense["max_concurrent"])
    for arm in arms.values():
        assert arm["completed"] == arm["n_requests"]


# ---------------------------------------------------------------------------
# shutdown hygiene: assert_quiescent / BlockLeakError (the fd-leak analogue)
# ---------------------------------------------------------------------------

def test_assert_quiescent_passes_when_clean():
    from repro.serve import BlockLeakError  # noqa: F401 (export check)
    a = BlockAllocator(4, 8)
    b = a.alloc(("prefix", (1, 2)))
    a.retain(b)
    a.release(b)
    a.release(b)                            # last ref: key dropped, freed
    a.assert_quiescent()                    # no raise


def test_assert_quiescent_names_live_refcounts():
    from repro.serve import BlockLeakError
    a = BlockAllocator(4, 8)
    b1, b2 = a.alloc(), a.alloc()
    a.release(b1)
    with pytest.raises(BlockLeakError, match="live refcounts"):
        a.assert_quiescent()
    a.release(b2)
    a.assert_quiescent()


def test_assert_quiescent_catches_stale_registry_entry():
    """A registry key whose block was freed behind its back (the COW
    forget_key contract violated) is a leak even with all refcounts
    zero — the stale key would alias future prefills to a recycled
    block's contents."""
    from repro.serve import BlockLeakError
    a = BlockAllocator(4, 8)
    b = a.alloc(("k", (7,)))
    a.release(b)
    a.assert_quiescent()
    a._prefix[("stale", (0,))] = 3          # inject the violation
    with pytest.raises(BlockLeakError, match="registry"):
        a.assert_quiescent()


def test_engine_shutdown_refuses_inflight_then_catches_leak():
    """PagedServingEngine.shutdown(): refuses while work is in flight,
    passes after a clean drain, and surfaces an injected block leak as
    BlockLeakError instead of silently shrinking the pool."""
    import jax
    from repro.configs import get_smoke_config
    from repro.models import lm
    from repro.parallel.sharding import default_rules, init_params
    from repro.serve import (BlockLeakError, PagedServeConfig,
                             PagedServingEngine)
    cfg = get_smoke_config("llama3-8b")
    rules = default_rules(None)
    params = init_params(lm.model_defs(cfg), jax.random.key(0))
    eng = PagedServingEngine(cfg, params, rules,
                             PagedServeConfig(max_batch=2, max_seq=32,
                                              block_tokens=8, n_blocks=8))
    eng.submit(Request(rid=0, prompt=np.ones(8, np.int32),
                       max_new_tokens=2))
    with pytest.raises(BlockLeakError, match="in flight"):
        eng.shutdown()                      # still queued
    eng.run()                               # drain to completion
    eng.shutdown()                          # clean: no raise

    leaked = eng.alloc.alloc()              # inject a leaked reservation
    with pytest.raises(BlockLeakError, match="live refcounts"):
        eng.shutdown()
    eng.alloc.release(leaked)
    eng.shutdown()
