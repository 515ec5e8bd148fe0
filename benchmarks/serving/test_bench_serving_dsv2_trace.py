"""What the latent-attention and MoE program writes for the trace, and
the four per-layer metrics that read it: the op_names of a tiny
DeepSeek-V2 program fall under the sublayer scopes ``trace_spans``
knows (``attn.*``; the MoE's ``mlp/moe.router``, ``mlp/moe.experts``,
``mlp/moe.shared``), ``prefix_hit_share`` reads the engine's
``shared_blocks`` stamp (and nothing where a program stamps nothing),
and ``moe_share.decode``, ``expert_roofline.prefill`` and
``latent_attn_roofline.decode`` read traces made by hand."""
import json
import pathlib
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_serving_testkit as kit
import harness
import spec
import trace_reduce
import trace_spans
import weights
import workload
from repro.serve import Request
from test_bench_serving_dsv2 import TINY_DSV2

PEAKS = spec.load_peaks("TPU v5 lite")
OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module")
def tiny_programs(tmp_path_factory):
    """The op_names of the tiny program's decode step and prefill chunk,
    as compiled."""
    base = kit.tiny_bench(tmp_path_factory.mktemp("dsv2_trace"))
    (base / "configs" / "tiny_dsv2.json").write_text(json.dumps(TINY_DSV2))
    c = spec.load_config("tiny_dsv2", base)
    eng = harness.make_engine(c, weights.init(c, 3))
    i32 = jnp.int32
    B = c["engine"]["max_batch"]
    step = eng._step.lower(eng.params, jnp.zeros((B, 1), i32), eng.pool,
                           jnp.asarray(eng.tables), jnp.asarray(eng.slot_pos),
                           jnp.ones(B, bool)).compile().as_text()
    chunk = eng._chunk.lower(eng.params, jnp.zeros((1, c["engine"]["chunk"]),
                                                   i32),
                             eng.pool, jnp.asarray(eng.tables[0]), i32(0),
                             i32(5)).compile().as_text()
    return {"decode": set(OP_NAME.findall(step)),
            "chunk": set(OP_NAME.findall(chunk))}


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_mla_and_moe_ops_fall_under_their_scopes(tiny_programs, program):
    names = tiny_programs[program]
    scopes = {trace_spans.scope_of(n) for n in names}
    assert set(trace_spans.SCOPES) <= scopes
    moe = [n for n in names if "/moe." in n]
    for part in ("moe.router", "moe.experts", "moe.shared"):
        assert any(f"/mlp/{part}/" in n for n in moe), part
    assert all(re.search(r"(^|/)mlp/moe\.(router|experts|shared)/", n)
               and trace_spans.scope_of(n) == "mlp" for n in moe)
    # the latent attention's products sit in its scopes (the CPU's
    # compiler turns some of the chunk's into other ops)
    dots = {trace_spans.scope_of(n) for n in names
            if n.endswith("dot_general") and "/attn." in n}
    assert {"attn.qkv", "attn.out"} <= dots <= {"attn.qkv", "attn.core",
                                                 "attn.out"}


# ------------------------------------------------------- prefix_hit_share

def _track(plen, shared, admit):
    req = types.SimpleNamespace(shared_blocks=shared, t_admit=admit)
    return types.SimpleNamespace(req=req, plen=plen)


def _record(tracks, t0=0.0, t_end=10.0, bt=16):
    return {"serve": {"t0": t0, "t_end": t_end, "tracks": tracks},
            "config": {"engine": {"block_tokens": bt}}}


def test_prefix_hit_share_is_shared_over_full_prompt_blocks():
    """Requests admitted in the window: 32 + 32 + 0 shared blocks of
    36 + 36 + 4 full ones (a partial block is no full block); one
    admitted after the window and one never admitted are left out."""
    tracks = [_track(580, 32, 1.0), _track(590, 32, 2.0), _track(70, 0, 3.0),
              _track(600, 32, 11.0), _track(600, 32, None)]
    got = spec.load_metric("prefix_hit_share").compute(_record(tracks))
    assert got == pytest.approx(100.0 * 64 / 76)


def test_prefix_hit_share_reads_nothing_without_the_stamp():
    tracks = [types.SimpleNamespace(
        req=types.SimpleNamespace(t_admit=1.0), plen=600)]
    assert spec.load_metric("prefix_hit_share").compute(
        _record(tracks)) is None


def test_the_engine_stamps_the_blocks_it_found_shared():
    """A tiny dense engine, blocks of 8: three requests open with the
    same 16 tokens; the first finds nothing, the two after it (sent once
    its prefill has published its blocks, while it decodes) find both
    prefix blocks."""
    from repro.configs import get_smoke_config
    from repro.models import lm
    from repro.parallel.sharding import default_rules, init_params
    from repro.serve import PagedServeConfig, PagedServingEngine
    cfg = get_smoke_config("llama3-8b")
    eng = PagedServingEngine(
        cfg, init_params(lm.model_defs(cfg), jax.random.key(0)),
        default_rules(None),
        PagedServeConfig(max_batch=2, max_seq=64, block_tokens=8,
                         n_blocks=32, chunk=16))
    rng = np.random.default_rng(1)
    prefix = rng.integers(1, 100, 16).astype(np.int32)
    reqs = [Request(rid=r, max_new_tokens=6, prompt=np.concatenate(
        [prefix, rng.integers(1, 100, 10).astype(np.int32)]))
        for r in range(3)]
    eng.submit(reqs[0])
    while not reqs[0].out:
        eng.step()
    for r in reqs[1:]:
        eng.submit(r)
    eng.run()
    assert [r.shared_blocks for r in reqs] == [0, 2, 2]
    tracks = [types.SimpleNamespace(req=r, plen=len(r.prompt)) for r in reqs]
    t0 = min(r.t_admit for r in reqs)
    got = spec.load_metric("prefix_hit_share").compute(
        _record(tracks, t0=t0, t_end=t0 + 1e6, bt=8))
    assert got == pytest.approx(100.0 * 4 / 9)


# --------------------------------------------- the device-trace readers

DSV2 = spec.load_config("dsv2_lite_9l")


def _op(text, start_us, dur_us, module=None):
    return trace_reduce.Op(text, start_us * 1e3, dur_us * 1e3, module)


ROUTER = "%dot.1 = f32[32,64]{1,0} dot(f32[32,2048]{1,0} %x, " \
    "f32[2048,64]{1,0} %router)"
GATE = "%ragged-dot-none = f32[{m},1408]{{1,0}} custom-call(s32[1]{{0}} " \
    "%n, bf16[{m},2048]{{1,0}} %x, bf16[512,2048,1408]{{2,1,0}} %w)"
SHARED = "%fusion.7 = bf16[32,2048]{1,0} fusion(bf16[32,2816]{1,0} %h, " \
    "bf16[2816,2048]{1,0} %w)"
QPROJ = "%fusion.2 = bf16[32,3072]{1,0} fusion(bf16[32,2048]{1,0} %x, " \
    "bf16[2048,3072]{1,0} %w)"
SCORES = "%fusion.9 = f32[32,16,1,5120]{3,2,1,0} fusion(" \
    "bf16[32,1,16,576]{3,2,1,0} %q, bf16[32,5120,576]{2,1,0} %ctx)"
STACKED = "%dynamic-slice.3 = bf16[10241,16,576]{2,1,0} dynamic-slice(" \
    "bf16[8,10241,16,576]{3,2,1,0} %pool, s32[] %i)"
GATHER = "%gather.4 = bf16[32,320,16,576]{3,2,1,0} gather(" \
    "bf16[10241,16,576]{2,1,0} %pool, s32[32,320,1]{2,1,0} %tables)"
COPY = "%copy.5 = bf16[10241,16,576]{2,1,0} copy(" \
    "bf16[10241,16,576]{2,1,0} %pool)"
WRITE_BACK = "%fusion.6 = bf16[8,10241,16,576]{3,2,1,0} fusion(" \
    "bf16[8,10241,16,576]{3,2,1,0} %pools, bf16[10241,16,576]{2,1,0} " \
    "%pool, s32[] %i)"


def _traced(kinds_ops, steps):
    return {"trace": {"kind": {k: kind for k, (kind, _) in
                               kinds_ops.items()},
                      "ops": {k: ops for k, (_, ops) in kinds_ops.items()}},
            "serve": {"steps": steps}, "config": DSV2, "peaks": PEAKS}


def test_moe_share_is_router_experts_and_shared_over_decode_busy():
    ops = [_op(ROUTER, 0, 1), _op(GATE.format(m=192), 1, 5),
           _op(SHARED, 6, 2), _op(QPROJ, 8, 2)]
    chunk = [_op(GATE.format(m=3072), 100, 50)]
    rec = _traced({0: ("decode", ops), 1: ("chunk", chunk)},
                  [{"k": 0}, {"k": 1}])
    assert spec.load_metric("moe_share.decode").compute(rec) == \
        pytest.approx(80.0)
    rec["config"] = spec.load_config("phi3_mini")
    assert spec.load_metric("moe_share.decode").compute(rec) is None


def test_expert_roofline_counts_full_chunks_of_the_prefill_program():
    """3072 routed rows of a 512-row chunk through a (2048, 1408)
    projection: the least time is its bytes, every expert's weights and
    the rows, 390,332,416 B over 819 GB/s; the op took twice that.  A
    chunk of 100 rows, and the decode program's grouped matmul in the
    same step, are left out."""
    least = 2 * (64 * 2048 * 1408 + 3072 * (2048 + 1408)) / 819e9
    gate = GATE.format(m=3072)
    ops = [_op(gate, 0, 2 * least * 1e6, "jit_prefill_chunk(7)"),
           _op(GATE.format(m=192), 5000, 900, "jit_decode_step_paged(3)")]
    short = [_op(GATE.format(m=600), 9000, 10, "jit_prefill_chunk(7)")]
    rec = _traced({0: ("chunk+decode", ops), 1: ("chunk", short)},
                  [{"k": 0, "chunks": [(0, 512, False)]},
                   {"k": 1, "chunks": [(512, 100, True)]}])
    assert spec.load_metric("expert_roofline.prefill").compute(rec) == \
        pytest.approx(50.0)


def test_expert_roofline_counts_the_experts_the_chunk_chose():
    """With the engine's stamp of a chunk dispatched inside the step (55
    experts chosen in one layer, 57 in the other: 56 on the mean), the
    least time counts those experts' weights, not all 64; a stamp from
    outside the step is not read."""
    least = 2 * (56 * 2048 * 1408 + 3072 * (2048 + 1408)) / 819e9
    ops = [_op(GATE.format(m=3072), 0, 2 * least * 1e6,
               "jit_prefill_chunk(7)")]
    req = types.SimpleNamespace(chunk_experts=[
        (1.5, jnp.asarray([55, 57], jnp.int32)),
        (9.0, jnp.asarray([1, 1], jnp.int32))])
    rec = _traced({0: ("chunk+decode", ops)},
                  [{"k": 0, "t0": 1.0, "t1": 2.0,
                    "chunks": [(0, 512, False)]}])
    rec["serve"]["tracks"] = [types.SimpleNamespace(req=req)]
    assert spec.load_metric("expert_roofline.prefill").compute(rec) == \
        pytest.approx(50.0)


def test_each_chunk_step_of_a_served_run_holds_one_stamp(tmp_path):
    """Served by the harness, a tiny DeepSeek-V2 program's steps hold as
    many ``chunk_experts`` stamps, by the step's span, as they ran
    chunks: the match ``expert_roofline.prefill`` makes."""
    base = kit.tiny_bench(tmp_path)
    (base / "configs" / "tiny_dsv2.json").write_text(json.dumps(TINY_DSV2))
    toy = pathlib.Path(__file__).parent / "testdata_families"
    cell = dict(json.loads((toy / "tiny_moe.mixed.json").read_text()),
                name="tiny_dsv2.mixed", config="tiny_dsv2")
    (base / "traffic" / "tiny_dsv2.mixed.json").write_text(json.dumps(cell))
    cell = spec.load_cell("tiny_dsv2.mixed", base)
    c = spec.load_config("tiny_dsv2", base)
    gen = spec.load_generator(cell["arrivals"]["kind"], base)
    eng = harness.make_engine(c, weights.init(c, 3))
    run = harness.serve(eng, workload.plan(cell, c, 3, gen), cell,
                        gen.CLOSED, 1.0)
    stamps = [t for tr in run["tracks"] for t, _ in tr.req.chunk_experts]
    for step in run["steps"]:
        inside = [t for t in stamps if step["t0"] <= t <= step["t1"]]
        assert len(inside) == step["d_chunks"], step
    assert sum(step["d_chunks"] for step in run["steps"]) > 4


def test_latent_attn_roofline_reads_the_latent_rows_ops():
    """51,200 visible tokens: the least time is their 576 values in 9
    layers, 530,841,600 B over 819 GB/s; the gather and the score fusion
    took 4 times that.  The layer loop's slice, copy and write-back of
    the pool (each outputs a whole layer's pool of 10,241 blocks) and the
    q projection are no latent attention."""
    least = 2 * 576 * 51200 * 9 / 819e9
    ops = [_op(GATHER, 0, least * 1e6), _op(SCORES, 5000, 3 * least * 1e6),
           _op(STACKED, 9000, 500), _op(COPY, 9600, 700),
           _op(WRITE_BACK, 10400, 600), _op(QPROJ, 11100, 100)]
    rec = _traced({0: ("decode", ops)},
                  [{"k": 0, "decode_ctx": 51200}])
    assert spec.load_metric("latent_attn_roofline.decode").compute(rec) == \
        pytest.approx(25.0)
    rec["config"] = spec.load_config("phi3_mini")
    assert spec.load_metric("latent_attn_roofline.decode").compute(
        rec) is None
