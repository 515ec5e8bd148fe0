"""DeepSeek-V2 through the paged engine (``families/deepseek_v2.py``), at
a tiny size on the CPU: latent attention (absorbed decode over the
latent pool, chunked prefill), YaRN rope, a dense layer ahead of the MoE
period, softmax-over-all gates, shared experts and dropless routing,
each against the plain float32 reference.

Tolerances, with their reasons:

* ``LOGIT_TOL`` 1e-4 on logits of size ~4: a float32 program and the
  float32 reference differ only in the order of their sums (absorbed
  against expanded attention, grouped against dense experts), ~1e-5.
  A bfloat16 program misses it by three orders of magnitude.
* ``LAYER_TOL`` 1e-5 on one MoE layer's output: the same sums, fewer.
"""
import dataclasses
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_serving_testkit as kit
import reference
import spec
import weights
import work
from repro.configs import archs
from repro.models import layers as L
from repro.parallel.sharding import default_rules
from repro.serve import Request

LOGIT_TOL = 1e-4
LAYER_TOL = 1e-5

#: DeepSeek-V2-Lite's layout at widths a CPU test can run: d 64, 4 heads,
#: latent 32 + rope 16, 8 experts of 32 (top 3) and 2 shared, one dense
#: layer and two MoE layers
TINY_DSV2 = {
    "name": "tiny_dsv2", "source": "test configuration: DeepSeek-V2-Lite's "
    "layout at widths a CPU test can run",
    "family": "deepseek_v2", "arch": "deepseek-v2-lite",
    "overrides": {"n_layers": 3, "d_model": 64, "n_heads": 4,
                  "n_kv_heads": 4, "d_ff": 96, "d_ff_expert": 32,
                  "n_experts": 8, "experts_per_token": 3, "vocab_size": 512,
                  "kv_lora_rank": 32, "qk_nope_head_dim": 16,
                  "qk_rope_head_dim": 16, "v_head_dim": 16,
                  "yarn_original_max": 64},
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 3, "vocab_size": 512, "kv_lora_rank": 32,
    "q_lora_rank": None, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
    "v_head_dim": 16, "n_routed_experts": 8, "n_shared_experts": 2,
    "num_experts_per_tok": 3, "first_k_dense_replace": 1,
    "moe_layer_freq": 1, "scoring_func": "softmax", "topk_method": "greedy",
    "norm_topk_prob": False, "routed_scaling_factor": 1.0,
    "rope_scaling": {"type": "yarn", "factor": 40,
                     "original_max_position_embeddings": 64,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707},
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "reduced": [], "assumed": {},
    "deployment": "a CPU test",
    "engine": {"max_batch": 4, "max_seq": 128, "block_tokens": 8,
               "pool_tokens": 512, "chunk": 32},
}
PROMPTS = (40, 70, 23, 9)          # one, two and three chunks of 32
NEW = 6


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    base = kit.tiny_bench(tmp_path_factory.mktemp("dsv2"))
    (base / "configs" / "tiny_dsv2.json").write_text(json.dumps(TINY_DSV2))
    return spec.load_config("tiny_dsv2", base)


def _program(c, dtype):
    return dataclasses.replace(spec.family(c).program_config(c), dtype=dtype)


def _served_logits(c, w, cfg, seed=0, chunked=True):
    """Serve ``PROMPTS`` through the paged engine (chunked prefill, or
    with ``chunked`` False the whole-prompt one, then decode) and keep
    the logits of every row it computed, by (request, position): each
    chunk's valid rows, each whole prefill's last row and each decode
    step's live rows."""
    from repro.serve import PagedServeConfig, PagedServingEngine
    e = c["engine"]
    eng = PagedServingEngine(
        cfg, spec.family(c).to_program(w, cfg), default_rules(None),
        PagedServeConfig(max_batch=e["max_batch"], max_seq=e["max_seq"],
                         eos_id=-1, block_tokens=e["block_tokens"],
                         n_blocks=e["pool_tokens"] // e["block_tokens"],
                         chunk=e["chunk"] if chunked else 0))
    got = {}
    chunk, step, whole = eng._chunk, eng._step, eng._prefill

    def whole_logged(p, t):
        cache, logits = whole(p, t)
        rid = PROMPTS.index(t.shape[1])
        got[(rid, t.shape[1] - 1)] = np.asarray(logits[0, -1], np.float32)
        return cache, logits

    def chunk_logged(p, t, pool, row, start, valid):
        logits, pool, reached = chunk(p, t, pool, row, start, valid)
        slot = next(i for i, r in enumerate(eng.slots)
                    if r is not None and eng.slot_fill[i] == int(start)
                    and eng.slot_state[i] == 0)
        for j in range(int(valid)):
            got[(eng.slots[slot].rid, int(start) + j)] = np.asarray(
                logits[0, j], np.float32)
        return logits, pool, reached

    def step_logged(p, t, pool, tab, pos, live):
        logits, pool = step(p, t, pool, tab, pos, live)
        for i in np.flatnonzero(np.asarray(live)):
            got[(eng.slots[i].rid, int(pos[i]))] = np.asarray(
                logits[i, 0], np.float32)
        return logits, pool

    eng._chunk, eng._step, eng._prefill = (chunk_logged, step_logged,
                                           whole_logged)
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=r, prompt=rng.integers(0, c["vocab_size"], n)
                    .astype(np.int32), max_new_tokens=NEW)
            for r, n in enumerate(PROMPTS)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    eng.shutdown()
    return reqs, got


def _worst_gap(c, w, reqs, got) -> float:
    """Largest |program - reference| over every logit the engine
    computed, teacher-forced on the served tokens."""
    worst = 0.0
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        pos = np.arange(len(seq), dtype=np.int32)
        ref = np.asarray(reference.forward_rows(w, c, seq[None], pos[None]))
        rows = [int(p) for p in pos if (r.rid, int(p)) in got]
        mine = np.stack([got[(r.rid, p)] for p in rows])
        worst = max(worst, float(np.abs(mine[:, :c["vocab_size"]]
                                        - ref[0, rows]).max()))
    return worst


@pytest.mark.parametrize("seed", [5, 2**40 + 3])
def test_engine_prefill_and_decode_match_the_reference(tiny, seed):
    w = weights.init(tiny, seed)
    reqs, got = _served_logits(tiny, w, _program(tiny, jnp.float32))
    assert all(len(r.out) == NEW for r in reqs)
    assert len(got) == sum(p + NEW - 1 for p in PROMPTS)
    assert _worst_gap(tiny, w, reqs, got) <= LOGIT_TOL


def test_whole_prompt_prefill_matches_the_reference(tiny):
    """Without chunked prefill the engine prefills each prompt with
    ``lm.prefill``, whose MoE layers route dropless under this config as
    the chunk program's do: each prompt's last row and every decode row
    match the reference."""
    w = weights.init(tiny, 5)
    reqs, got = _served_logits(tiny, w, _program(tiny, jnp.float32),
                               chunked=False)
    assert all(len(r.out) == NEW for r in reqs)
    assert len(got) == len(PROMPTS) * NEW
    assert _worst_gap(tiny, w, reqs, got) <= LOGIT_TOL


def test_the_engine_stamps_each_chunk_with_the_experts_it_chose(tiny):
    """One stamp per prefill chunk, in order of dispatch, each counting
    per MoE layer the experts its valid rows chose: at least the k of
    one row, at most every expert or k per valid row."""
    reqs, _ = _served_logits(tiny, weights.init(tiny, 5),
                             _program(tiny, jnp.float32))
    chunk, k, E = tiny["engine"]["chunk"], 3, 8
    for r in reqs:
        plen = len(r.prompt)
        assert len(r.chunk_experts) == -(-plen // chunk)
        times = [t for t, _ in r.chunk_experts]
        assert times == sorted(times) and times[0] == r.t_prefill_start
        for j, (_, n) in enumerate(r.chunk_experts):
            valid = min(chunk, plen - j * chunk)
            n = np.asarray(n)
            assert n.shape == (2,)
            assert (n >= k).all() and (n <= min(E, k * valid)).all()


def test_moe_local_counts_the_experts_its_rows_chose(tiny):
    """``count_rows`` counts the held experts that the masked rows chose:
    all 8 over 7 rows of both batches, fewer over one row, and a share
    counts only its own."""
    cfg, _, lp = _moe_pieces(tiny)
    x = jax.random.normal(jax.random.key(3), (2, 7, cfg.d_model),
                          jnp.float32)
    xn = L.rmsnorm(x, lp["norm"], cfg.norm_eps)
    _, idx = L.moe_route(xn, lp["router"], cfg)
    idx = np.asarray(idx)
    mask = np.zeros((2, 7), bool)
    mask[1, :3] = True
    half = cfg.n_experts // 2
    sp = dict(lp, **{k: lp[k][half:] for k in ("wg", "wi", "wo")})
    for m in (np.ones((2, 7), bool), mask):
        y, n = L.moe_local(lp, xn, cfg, count_rows=jnp.asarray(m))
        np.testing.assert_array_equal(y, L.moe_local(lp, xn, cfg))
        assert int(n) == len(set(idx[m].ravel()))
        _, n = L.moe_local(sp, xn, cfg, e_lo=half,
                           count_rows=jnp.asarray(m))
        assert int(n) == len({e for e in idx[m].ravel() if e >= half})
    assert len(set(idx[mask].ravel())) < cfg.n_experts


def test_a_bfloat16_program_fails_the_tolerance(tiny):
    w = weights.init(tiny, 5)
    reqs, got = _served_logits(tiny, w, _program(tiny, jnp.bfloat16))
    assert _worst_gap(tiny, w, reqs, got) > 100 * LOGIT_TOL


def test_a_program_without_the_arch_is_refused_before_weights(
        tiny, monkeypatch):
    """As on a program that has no ``deepseek-v2-lite``: loading the
    configuration raises SpecError, before any weight is drawn."""
    monkeypatch.delitem(archs.CONFIGS, "deepseek-v2-lite")
    with pytest.raises(spec.SpecError, match="no arch"):
        spec.load_config("tiny_dsv2", pathlib.Path(tiny[spec.BASE_KEY]))
    with pytest.raises(spec.SpecError, match="no arch"):
        spec.load_config("dsv2_lite_9l")


# ------------------------------------------------------------------ YaRN

def _published_yarn(dim, base, factor, orig, beta_fast, beta_slow, mscale,
                    mscale_all_dim, qk):
    """DeepSeek-V2's ``yarn_find_correction_range``,
    ``yarn_linear_ramp_mask``, ``yarn_get_mscale`` and softmax scale,
    transcribed."""
    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) \
            / (2 * math.log(base))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                               dtype=np.float32) / dim))

    def get_mscale(s, m):
        return 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0

    inv = inter * (1 - mask) + extra * mask
    cos = get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim)
    scale = qk ** -0.5 * get_mscale(factor, mscale_all_dim) ** 2
    return inv, cos, scale, (low, high)


def test_yarn_is_the_published_formula():
    cfg = archs.CONFIGS["deepseek-v2-lite"]
    inv, cos, scale, (low, high) = _published_yarn(
        64, 10000.0, 40, 4096, 32, 1, 0.707, 0.707, 192)
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(L.yarn_inv_freq(cfg, 64), inv, rtol=1e-6)
    assert L.yarn_cos_scale(cfg) == pytest.approx(cos) == 1.0
    assert L.mla_softmax_scale(cfg) == pytest.approx(scale, rel=1e-12)
    assert scale == pytest.approx(192 ** -0.5 * 1.58962, rel=1e-5)
    c = spec.load_config("dsv2_lite_9l")
    f_inv, f_cos, f_scale = spec.family(c).yarn(c)
    np.testing.assert_allclose(f_inv, inv, rtol=1e-6)
    assert (f_cos, f_scale) == pytest.approx((cos, scale), rel=1e-12)
    # below the ramp the pairs keep their frequency, above it they are
    # interpolated by the factor
    np.testing.assert_allclose(inv[:low], 10000.0 ** (
        -np.arange(0, 2 * low, 2) / 64), rtol=1e-6)
    np.testing.assert_allclose(inv[high:], 10000.0 ** (
        -np.arange(2 * high, 64, 2) / 64) / 40, rtol=1e-6)


# ---------------------------------------------------------------- routing

def _moe_pieces(tiny, seed=5):
    """The tiny config's program, its first MoE layer's program params
    (float32) and reference layout weights."""
    cfg = _program(tiny, jnp.float32)
    w = weights.init(tiny, seed)
    p = spec.family(tiny).to_program(w, cfg)
    lp = jax.tree.map(lambda a: a[0], p["period"]["l0"]["s1_moe"])
    return cfg, w, lp


def test_near_tied_router_scores_route_as_the_reference(tiny):
    """One row whose 3rd and 4th router scores (k = 3) lie within 1e-6
    of each other, the higher one at the higher expert id: the program
    routes it as the float32 reference does.  In bfloat16 the two tie and
    the lower id would win."""
    cfg, w, lp = _moe_pieces(tiny)
    E, d = cfg.n_experts, cfg.d_model
    xn = jnp.zeros((1, 1, d), jnp.float32).at[0, 0, 0].set(1.0)
    logits = jnp.array([3.0, 2.5, 1.0, 0.5, 0.0, 1.0 + 4e-6, -0.5, -1.0])
    router = jnp.zeros((d, E), jnp.float32).at[0].set(logits)
    gate, idx = L.moe_route(xn, router, cfg)
    scores = np.asarray(jax.nn.softmax(reference._mm(xn, router, False)))
    s = np.sort(scores[0, 0])[::-1]
    assert 0 < s[2] - s[3] < 1e-6
    ref_gate, ref_idx = jax.lax.top_k(jnp.asarray(scores), 3)
    assert sorted(np.asarray(idx).ravel()) == sorted(
        np.asarray(ref_idx).ravel()) == [0, 1, 5]
    np.testing.assert_allclose(gate, ref_gate, rtol=1e-6)
    assert float(jnp.sum(gate)) < 1.0          # not renormalised
    _, bf_idx = jax.lax.top_k(jnp.asarray(scores, jnp.bfloat16), 3)
    assert 5 not in np.asarray(bf_idx).ravel()


def test_expert_shares_add_up_to_the_whole_layer(tiny):
    """Two chips' shares of the layer (experts 0-3 and 4-7, each routing
    over all 8) plus the shared experts, counted once, give the uncut
    reference's MoE layer."""
    cfg, w, lp = _moe_pieces(tiny)
    fam = spec.family(tiny)
    dims = fam.dims_of(tiny, "moe")
    x = jax.random.normal(jax.random.key(1), (2, 7, cfg.d_model),
                          jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = fam._moe(x, w, jnp.int32(0), dims, False) - x
    xn = L.rmsnorm(x, lp["norm"], cfg.norm_eps)
    half = cfg.n_experts // 2

    def share(lo, shared):
        sp = dict(lp, **{k: lp[k][lo:lo + half] for k in ("wg", "wi", "wo")})
        return L.moe_local(sp, xn, cfg, e_lo=lo, shared=shared)

    with jax.default_matmul_precision("highest"):
        parts = share(0, True) + share(half, False)
        alone = share(0, False) + share(half, False)
    np.testing.assert_allclose(parts, whole, atol=LAYER_TOL)
    shared = L.shared_experts(lp["shared"], xn)
    np.testing.assert_allclose(alone + shared, whole, atol=LAYER_TOL)
    assert float(jnp.abs(shared).max()) > 100 * LAYER_TOL


def test_dropless_routing_keeps_every_row_when_all_pick_the_same_experts(
        tiny):
    """Every row routes to experts 0, 1 and 2: the dropless layer
    computes all 24 rows of each, as the reference does, where a
    capacity of 1.25 x the even share (12 rows) drops half of them."""
    cfg, w, lp = _moe_pieces(tiny)
    fam = spec.family(tiny)
    router = jnp.zeros_like(lp["router"]).at[:, :3].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.key(2), (1, 24, cfg.d_model),
                                  jnp.float32)) + 0.5
    w = dict(w, **{"moe.router": w["moe.router"].at[0].set(
        router.astype(jnp.bfloat16))})
    lp = dict(lp, router=router)
    xn = L.rmsnorm(x, lp["norm"], cfg.norm_eps)
    _, idx = L.moe_route(xn, router, cfg)
    assert (np.sort(np.asarray(idx), -1) == [0, 1, 2]).all()
    capped = dataclasses.replace(cfg, capacity_factor=1.25)
    with jax.default_matmul_precision("highest"):
        ref = fam._moe(x, w, jnp.int32(0), fam.dims_of(tiny, "moe"),
                       False) - x
        got = L.moe_local(lp, xn, cfg)
        layer = L.moe_layer(lp, x, cfg, default_rules(None)) - x
        kept = L.moe_layer(lp, x, capped, default_rules(None)) - x
        kept_local = L.moe_local(lp, xn, capped)
    assert cfg.capacity_factor is None
    np.testing.assert_allclose(got, ref, atol=LAYER_TOL)
    np.testing.assert_allclose(layer, ref, atol=LAYER_TOL)
    row_err = np.abs(np.asarray(kept - ref)).max(-1)[0]
    assert (row_err > 1e-3).sum() >= 10        # the capacity path drops
    # a config's capacity caps the grouped experts as it caps the
    # capacity path: the same rows dropped, the same sums otherwise
    np.testing.assert_allclose(kept_local, kept, atol=LAYER_TOL)


def test_a_capacity_caps_each_expert_at_its_first_rows(tiny):
    """Under a ``capacity_factor`` the grouped experts keep each expert's
    first rows, in row order, as the capacity path does, on rows routed
    as the router picks them: at factors that drop some rows and none."""
    cfg, _, lp = _moe_pieces(tiny)
    x = jax.random.normal(jax.random.key(3), (2, 20, cfg.d_model),
                          jnp.float32)
    xn = L.rmsnorm(x, lp["norm"], cfg.norm_eps)
    with jax.default_matmul_precision("highest"):
        dropless = L.moe_local(lp, xn, cfg)
    for factor, drops in ((0.5, True), (1.0, True), (8.0, False)):
        capped = dataclasses.replace(cfg, capacity_factor=factor)
        with jax.default_matmul_precision("highest"):
            want = L.moe_layer(lp, x, capped, default_rules(None)) - x
            got = L.moe_local(lp, xn, capped)
        np.testing.assert_allclose(got, want, atol=LAYER_TOL)
        gap = float(jnp.abs(got - dropless).max())
        assert (gap > 1e-3) if drops else (gap <= LAYER_TOL), (factor, gap)


def test_moe_on_a_mesh_mode_is_refused_where_it_would_not_hold(
        tiny, monkeypatch):
    """The paged programs read every expert in place on one device, and a
    dropless layer has no capacity to shard by: on a mesh MoE mode both
    raise rather than serve something else."""
    from repro.models import lm
    cfg = _program(tiny, jnp.float32)
    p = spec.family(tiny).to_program(weights.init(tiny, 0), cfg)
    monkeypatch.setattr(L, "moe_mode", lambda cfg, rules: "ep")
    pool = jax.tree.map(lambda pv: jnp.zeros(pv.shape, pv.dtype),
                        lm.pool_defs(cfg, 17, 8),
                        is_leaf=lambda x: hasattr(x, "logical"))
    with pytest.raises(ValueError, match="in place"):
        lm.decode_step_paged(p, jnp.zeros((2, 1), jnp.int32), pool,
                             jnp.zeros((2, 8), jnp.int32),
                             jnp.zeros(2, jnp.int32), jnp.ones(2, bool),
                             cfg, default_rules(None))
    lp = jax.tree.map(lambda a: a[0], p["period"]["l0"]["s1_moe"])
    with pytest.raises(ValueError, match="capacity_factor"):
        L.moe_layer(lp, jnp.zeros((1, 4, cfg.d_model)), cfg,
                    default_rules(None))


# ------------------------------------------------------------- the config

def test_the_config_is_the_published_model_cut_to_nine_layers():
    c = spec.load_config("dsv2_lite_9l")
    cfg = spec.family(c).program_config(c)
    sizes = spec.family(c).shapes(c)
    n = sum(math.prod(s) for s, _ in sizes.values())
    pad = 2 * (weights.padded_vocab(c["vocab_size"]) - c["vocab_size"]) \
        * c["hidden_size"]
    assert n - pad == cfg.n_params() == 5_179_222_528
    assert dataclasses.replace(cfg, n_layers=27).n_params() == 15_706_484_224
    assert spec.family(c).latent_token_bytes(c) == 10_368
    assert c["published"] == {"num_hidden_layers": 27}


@pytest.mark.parametrize("rows, ctx", [(1, 1), (32, 51_200)])
def test_decode_work_counts_every_expert_once_and_the_latent_context(
        rows, ctx):
    """A step reads the weights of every expert its rows can reach (6 at
    one row, all 64 at 32) once in each MoE layer, and 576 cached values
    per token and layer of the visible context."""
    c = spec.load_config("dsv2_lite_9l")
    fam = spec.family(c)
    flops, byts = work.decode_step(c, rows, ctx)
    expert = 3 * 2048 * 1408 * work.BYTES
    used = min(64, rows * 6)
    routed = sum(b for f, b in fam._moe_calls(c, rows)[1:4])
    assert routed == pytest.approx(
        used * expert + 3 * rows * 6 * (2048 + 1408) * work.BYTES, rel=1e-12)
    latent = 10_368 * (ctx + rows)
    assert byts > 8 * used * expert + latent
    assert flops > 2.0 * 16 * (2 * 512 + 64) * ctx * 9
