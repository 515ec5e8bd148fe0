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
    assert pk.shape == (1, 5, 64, cfg.n_kv_heads * cfg.head_dim)
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


# ---------------------------------------------------------------------------
# the pool updated in place: a scan carry, donated to the engine's programs
# ---------------------------------------------------------------------------

def _paged_setup(arch: str):
    """A paged model's (cfg, rules, params): the 1-layer phi3 cut at its
    published widths, a tiny config by arch name, or ``wide-heads``, the
    tiny llama3 with heads of 128 (pool rows keep their heads)."""
    import dataclasses
    import jax
    from repro.configs import get_config, get_smoke_config
    from repro.models import lm
    from repro.parallel.sharding import default_rules, init_params
    if arch == "phi3-mini-3.8b":
        cfg = dataclasses.replace(get_config(arch), n_layers=1)
    elif arch == "wide-heads":
        cfg = dataclasses.replace(get_smoke_config("llama3-8b"), d_head=128)
    else:
        cfg = get_smoke_config(arch)
    params = init_params(lm.model_defs(cfg), jax.random.key(0))
    return cfg, default_rules(None), params


def _zero_pool(cfg, n_blocks: int, bt: int):
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    return jax.tree.map(lambda pv: jnp.zeros(pv.shape, pv.dtype),
                        lm.pool_defs(cfg, n_blocks, bt),
                        is_leaf=lambda x: hasattr(x, "logical"))


def _scan_eqns(jaxpr):
    """Every scan equation of a jaxpr, its sub-jaxprs included."""
    import jax
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _scan_eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _scan_eqns(sub)


# the 1-layer phi3 cut at its published widths (rows of 3,072 lanes) and a
# tiny MLA + MoE model (rows padded to a whole 128-lane register)
PAGED_ARCHS = ["phi3-mini-3.8b", "deepseek-v2-lite"]


@pytest.mark.parametrize("arch", PAGED_ARCHS)
def test_engine_donates_the_pool_to_its_programs(arch):
    """One engine step with a live slot consumes the pool it was given
    (each leaf is deleted: the chunk and decode programs wrote into its
    buffers), and both compiled programs alias the pool to an output."""
    import jax
    import jax.numpy as jnp
    from repro.serve import PagedServeConfig, PagedServingEngine
    cfg, rules, params = _paged_setup(arch)
    eng = PagedServingEngine(cfg, params, rules,
                             PagedServeConfig(max_batch=2, max_seq=64,
                                              block_tokens=16, n_blocks=6,
                                              chunk=32))
    eng.submit(Request(rid=0, prompt=np.arange(1, 21, dtype=np.int32),
                       max_new_tokens=4))
    given = jax.tree.leaves(eng.pool)
    assert eng.step()
    assert eng.prefill_chunks == 1 and eng.decode_steps == 1
    assert all(leaf.is_deleted() for leaf in given)
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(eng.pool))
    i32 = jnp.int32
    step = eng._step.lower(params, jnp.zeros((2, 1), i32), eng.pool,
                           jnp.asarray(eng.tables), jnp.zeros(2, i32),
                           jnp.zeros(2, bool)).compile()
    chunk = eng._chunk.lower(params, jnp.zeros((1, 32), i32), eng.pool,
                             jnp.asarray(eng.tables[0]), i32(0),
                             i32(1)).compile()
    for compiled in (step, chunk):
        assert "input_output_alias" in compiled.as_text().splitlines()[0]
    eng.run()
    eng.shutdown()


@pytest.mark.parametrize("arch", ["llama3-8b", "wide-heads",
                                  "deepseek-v2-lite"])
def test_paged_programs_carry_the_pool_through_the_layer_scan(arch):
    """Neither paged program scans the pool as a stacked input nor
    returns it as a stacked output: the pool is in the scan's carry, so
    each layer writes its rows into the whole stack in place."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    cfg, rules, params = _paged_setup(arch)
    B, nb, bt, c = 2, 4, 8, 16
    pool = _zero_pool(cfg, 2 * nb + 1, bt)
    leaves = {leaf.shape for leaf in jax.tree.leaves(pool)}
    i32 = jnp.int32
    programs = {
        "decode": jax.make_jaxpr(functools.partial(
            lm.decode_step_paged, cfg=cfg, rules=rules))(
                params, jnp.zeros((B, 1), i32), pool,
                jnp.zeros((B, nb), i32), jnp.zeros(B, i32),
                jnp.ones(B, bool)),
        "chunk": jax.make_jaxpr(functools.partial(
            lm.prefill_chunk, cfg=cfg, rules=rules))(
                params, jnp.zeros((1, c), i32), pool, jnp.zeros(nb, i32),
                i32(0), i32(c)),
    }
    for name, closed in programs.items():
        scans = list(_scan_eqns(closed.jaxpr))
        assert scans, name
        carried = 0
        for eqn in scans:
            k, n = eqn.params["num_consts"], eqn.params["num_carry"]
            xs = [v.aval.shape for v in eqn.invars[k + n:]]
            ys = [v.aval.shape for v in eqn.outvars[n:]]
            assert not leaves & set(xs), (name, xs)
            assert not leaves & set(ys), (name, ys)
            carried += sum(v.aval.shape in leaves for v in eqn.outvars[:n])
        assert carried == len(jax.tree.leaves(pool)), name


@pytest.mark.parametrize("arch", ["llama3-8b", "wide-heads",
                                  "deepseek-v2-lite"])
def test_decode_writes_each_live_row_at_its_block_and_offset(arch):
    """A few donated decode steps over a batch with a dead slot: each
    live slot's rows land at (its table's block, offset) of every layer,
    equal to the rows the dense decode writes at those positions, the
    logits agree, and block 0 and the lanes past each row stay zero.
    Equal bit for bit without experts; with them, to float32 rounding,
    since the dense step runs each layer's expert slice and the paged
    one the whole stack at the layer's index."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ATTN
    from repro.models import lm
    cfg, rules, params = _paged_setup(arch)
    B, S, bt = 3, 32, 8
    nb = S // bt
    pool = _zero_pool(cfg, 2 * nb + 1, bt)
    cache = jax.tree.map(lambda pv: jnp.zeros(pv.shape, pv.dtype),
                         lm.cache_defs(cfg, B, S),
                         is_leaf=lambda x: hasattr(x, "logical"))
    tables = np.zeros((B, nb), np.int32)
    tables[0] = np.arange(1, nb + 1)
    tables[2] = np.arange(nb + 1, 2 * nb + 1)      # slot 1 is dead
    live = np.array([True, False, True])
    start = np.array([0, 0, 5], np.int32)           # slot 2 crosses a block
    paged = jax.jit(functools.partial(lm.decode_step_paged, cfg=cfg,
                                      rules=rules), donate_argnums=(2,))
    dense = jax.jit(functools.partial(lm.decode_step, cfg=cfg, rules=rules))
    tol = 1e-5 if cfg.n_experts else 0.0
    rng = np.random.default_rng(0)
    steps = 6
    for t in range(steps):
        tok = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, 1)), jnp.int32)
        pos = jnp.asarray(start + t * live)
        lg_p, pool = paged(params, tok, pool, jnp.asarray(tables), pos,
                           jnp.asarray(live))
        lg_d, cache = dense(params, tok, cache, pos)
        np.testing.assert_allclose(np.asarray(lg_p)[live],
                                   np.asarray(lg_d)[live], rtol=tol, atol=tol)
    written = [(b, p) for b in np.flatnonzero(live)
               for p in range(start[b], start[b] + steps)]
    for name, kinds, _ in lm.stacks(cfg):
        parts = (pool[name], cache[name]) if cfg.first_dense \
            else (pool, cache)
        for lk, layer in enumerate(kinds):
            for si, kind in enumerate(layer):
                key = f"s{si}_{kind}"
                if key not in parts[0][f"l{lk}"]:
                    continue
                for leaf, pl in parts[0][f"l{lk}"][key].items():
                    assert pl.shape[-1] % 128 == 0
                    pl = np.asarray(pl, np.float32)
                    pl = pl.reshape(pl.shape[:3] + (-1,))
                    dl = np.asarray(parts[1][f"l{lk}"][key][leaf],
                                    np.float32)
                    w = dl[0, 0, 0].size
                    assert w <= pl.shape[-1]
                    assert not pl[:, 0].any(), "zero block written"
                    assert not pl[..., w:].any(), "lanes past a row"
                    for b, p in written:
                        np.testing.assert_allclose(
                            pl[:, tables[b, p // bt], p % bt, :w],
                            dl[:, b, p].reshape(len(pl), w),
                            rtol=tol, atol=tol)
                    assert (kind == ATTN) == (leaf in ("k", "v"))
