"""Compile the serving path's kernels for a TPU v5e that is described, not
attached: the TPU compiler is installed here, and it refuses what the chip
would refuse (block tiling, VMEM, memory, a kernel in a multi-device
jit).  Interpret mode cannot show that.  Shapes are phi3-mini-3.8b's
published widths, at decode rows (max_batch 4), a prefill chunk (256) and
a long prefill (2048).

The topology is described inside a fixture (never at import: one process
at a time may load the TPU library), and the persistent compilation cache
is off around these compiles — an entry written for a described chip
cannot be read back without one.
"""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import substrate
from repro.configs import get_config
from repro.kernels import ops
from repro.models import lm
from repro.parallel.sharding import PV, default_rules, param_shardings

PHI3 = get_config("phi3-mini-3.8b")
D, F = PHI3.d_model, PHI3.d_ff
PROJ = {"qkvo": (D, D), "mlp_in": (D, F), "mlp_out": (F, D)}
ROWS = {"decode": 4, "chunk": 256, "prefill": 2048}
#: Pallas kernels in a one-layer step: its two norms and the final one
NORMS = 3
#: the sublayer scopes of models/lm.py and models/layers.py
SCOPES = {"embed", "attn.qkv", "attn.kv_write", "attn.core", "attn.out",
          "mlp", "head"}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer ``kernels.ops`` onto its TPU path (it asks jax.devices(),
    which here is the CPU)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tree_sds(defs, sharding):
    return jax.tree.map(lambda pv: _sds(pv.shape, pv.dtype, sharding), defs,
                        is_leaf=lambda x: isinstance(x, PV))


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _weights(cfg) -> set[tuple[int, int]]:
    """(K, N) of one layer's projections: q, k, v, o, gate/up, down."""
    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    return {(cfg.d_model, q), (cfg.d_model, kv), (q, cfg.d_model),
            (cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model)}


def _no_projection_kernel(compiled, cfg) -> bool:
    """No Pallas call takes a projection's weight: the projections are
    XLA's own dots and the kernels that remain are the norms."""
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    return not any(f"{k},{n}]" in ln for ln in calls
                   for k, n in _weights(cfg))


def _top_level_outputs(compiled) -> list[tuple[int, ...]]:
    """Output dims of every instruction that writes a buffer: those of
    every computation but the bodies of fusions, whose instructions live
    in registers and on-chip memory inside the fused kernel."""
    text = compiled.as_text()
    fused = set(re.findall(r"\bfusion\(.*\bcalls=%?([\w.\-]+)", text))
    out, keep = [], True
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+) .*\{$", ln.rstrip())
        if head:                                # a computation's header
            keep = head.group(1) not in fused
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\w+\[([\d,]*)\]",
                     ln)
        if keep and m:
            out.append(tuple(int(d) for d in m.group(1).split(",") if d))
    return out


def _scopes(compiled) -> set[str]:
    """The sublayer scopes named in the compiled program's op metadata."""
    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    return {part for n in names for part in n.split("/")} & SCOPES


@pytest.mark.parametrize("rows", ROWS.values(), ids=ROWS.keys())
@pytest.mark.parametrize("proj", PROJ.values(), ids=PROJ.keys())
def test_matmul_compiles_for_v5e(one_chip, on_tpu, proj, rows):
    K, N = proj
    compiled = jax.jit(ops.matmul).lower(
        _sds((rows, K), jnp.bfloat16, one_chip),
        _sds((K, N), jnp.bfloat16, one_chip)).compile()
    assert _kernels(compiled) == 1


@pytest.mark.parametrize("rows", [4, 20, 256, 2048])
def test_rmsnorm_compiles_for_v5e(one_chip, on_tpu, rows):
    """Decode rows (4) and a short prompt (20) take the whole of R as one
    block; longer prefills take tile-aligned blocks over padded rows."""
    compiled = jax.jit(lambda x, g: ops.rmsnorm(x, g, eps=PHI3.norm_eps)) \
        .lower(_sds((rows, D), jnp.bfloat16, one_chip),
               _sds((D,), jnp.float32, one_chip)).compile()
    assert _kernels(compiled) == 1


@pytest.mark.parametrize("S", [256, 2048])
def test_flash_attention_compiles_for_v5e(one_chip, on_tpu, S):
    """Not on the serving path yet (prefill uses chunked XLA attention),
    but the kernel ops offers for it: phi3's 32 heads of 96."""
    H, Dh = PHI3.n_heads, PHI3.head_dim
    qkv = [_sds((1, H, S, Dh), jnp.bfloat16, one_chip)] * 3
    compiled = jax.jit(ops.attention).lower(*qkv).compile()
    assert _kernels(compiled) == 1


@pytest.mark.parametrize("arch,S,layers", [("phi3-mini-3.8b", 1024, 32),
                                           ("deepseek-7b", 2048, 15)],
                         ids=["phi3", "deepseek"])
def test_paged_attention_compiles_for_v5e(one_chip, on_tpu, arch, S, layers):
    """The live-block decode kernel over a benchmark cell's stacked pool
    (8 slots, blocks of 16, pool of 6 x max_seq tokens): phi3's heads of
    96 packed in one 3,072-lane row, deepseek's whole heads of 128."""
    from repro.models.layers import kv_row
    cfg = get_config(arch)
    B, bt, Hkv, hd = 8, 16, cfg.n_kv_heads, cfg.head_dim
    G = cfg.n_heads // Hkv
    pool = _sds((layers, 6 * S // bt + 1, bt) + kv_row(cfg), jnp.bfloat16,
                one_chip)
    compiled = jax.jit(lambda *a: ops.paged_attention(*a, None)).lower(
        _sds((B, Hkv, G, hd), jnp.bfloat16, one_chip), pool, pool,
        _sds((), jnp.int32, one_chip), _sds((B, S // bt), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip)).compile()
    assert _kernels(compiled) == 1


def test_one_layer_decode_step_compiles_for_v5e(one_chip, on_tpu):
    """A full-width decode step of a one-layer cut: the 3 norms (the
    layer's two and the final one) are Pallas kernels, the 7 projections
    XLA's dots, and the step fits the chip."""
    cfg = dataclasses.replace(PHI3, n_layers=1)
    rules = default_rules(None)
    B, S = 4, 1024
    step = jax.jit(lambda p, t, c, pos: lm.decode_step(p, t, c, pos, cfg,
                                                       rules))
    compiled = step.lower(
        _tree_sds(lm.model_defs(cfg), one_chip),
        _sds((B, 1), jnp.int32, one_chip),
        _tree_sds(lm.cache_defs(cfg, B, S), one_chip),
        _sds((B,), jnp.int32, one_chip)).compile()
    assert _kernels(compiled) == NORMS
    assert _no_projection_kernel(compiled, cfg)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_one_layer_prefill_chunk_compiles_for_v5e(one_chip, on_tpu):
    """The paged engine's chunked prefill at full width: a 256-token chunk
    scattered into a pool of 16-token blocks; the norms are the only
    Pallas kernels."""
    cfg = dataclasses.replace(PHI3, n_layers=1)
    rules = default_rules(None)
    S, bt, c = 1024, 16, 256
    chunk = jax.jit(lambda p, t, pool, row, start, valid: lm.prefill_chunk(
        p, t, pool, row, start, valid, cfg, rules))
    compiled = chunk.lower(
        _tree_sds(lm.model_defs(cfg), one_chip),
        _sds((1, c), jnp.int32, one_chip),
        _tree_sds(lm.pool_defs(cfg, 4 * S // bt + 1, bt), one_chip),
        _sds((S // bt,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), _sds((), jnp.int32, one_chip)).compile()
    assert _kernels(compiled) == NORMS
    assert _no_projection_kernel(compiled, cfg)
    assert _scopes(compiled) == SCOPES


def test_one_layer_paged_decode_step_compiles_for_v5e(one_chip, on_tpu):
    """The paged engine's decode step at full width (8 slots, max_seq
    1024, blocks of 16): the 3 norms and the paged attention kernel are
    the only Pallas kernels, and every sublayer scope reaches the
    compiled program's op metadata."""
    cfg = dataclasses.replace(PHI3, n_layers=1)
    rules = default_rules(None)
    B, S, bt = 8, 1024, 16
    step = jax.jit(lambda p, t, pool, tab, pos, lv: lm.decode_step_paged(
        p, t, pool, tab, pos, lv, cfg, rules))
    compiled = step.lower(
        _tree_sds(lm.model_defs(cfg), one_chip),
        _sds((B, 1), jnp.int32, one_chip),
        _tree_sds(lm.pool_defs(cfg, 6 * S // bt + 1, bt), one_chip),
        _sds((B, S // bt), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip),
        _sds((B,), jnp.bool_, one_chip)).compile()
    assert _kernels(compiled) == NORMS + 1
    assert _no_projection_kernel(compiled, cfg)
    assert _scopes(compiled) == SCOPES


@pytest.mark.parametrize("arch,B,S", [("phi3-mini-3.8b", 8, 1024),
                                      ("deepseek-7b", 8, 2048)],
                         ids=["phi3", "deepseek"])
def test_two_layer_paged_decode_reads_weights_in_place(one_chip, on_tpu,
                                                       arch, B, S):
    """The benchmark's decode step over a real layer scan (two layers),
    jitted as the engine jits it (the pool donated): each projection's
    dot reads its layer's slice of the stacked weight in place, so no
    instruction writes one layer's weight out first."""
    cfg = dataclasses.replace(get_config(arch), n_layers=2)
    rules = default_rules(None)
    bt = 16
    step = jax.jit(lambda p, t, pool, tab, pos, lv: lm.decode_step_paged(
        p, t, pool, tab, pos, lv, cfg, rules), donate_argnums=(2,))
    compiled = step.lower(
        _tree_sds(lm.model_defs(cfg), one_chip),
        _sds((B, 1), jnp.int32, one_chip),
        _tree_sds(lm.pool_defs(cfg, 4 * S // bt + 1, bt), one_chip),
        _sds((B, S // bt), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip),
        _sds((B,), jnp.bool_, one_chip)).compile()
    outs = {tuple(d for d in dims if d != 1)
            for dims in _top_level_outputs(compiled)}
    assert outs, "no instruction found in the compiled program"
    assert not outs & _weights(cfg)
    assert _no_projection_kernel(compiled, cfg)


def _copies(compiled) -> list[tuple[int, ...]]:
    """Output dims of every copy outside fusions."""
    return [tuple(int(d) for d in m.group(1).split(",") if d)
            for m in re.finditer(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\w+"
                                 r"\[([\d,]*)\]\S*\s+copy\(",
                                 compiled.as_text(), re.M)]


def _pool_writers(compiled, shapes) -> set[str]:
    """Each instruction outside fusions that outputs an array of one of
    ``shapes``, as its opcode, a fusion as the opcodes of what it calls
    (buffers passed on whole are left out)."""
    text = compiled.as_text()
    out = set()
    for m in re.finditer(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\w+\[([\d,]*)\]"
                         r"\S*\s+([a-z\-]+)\((.*)$", text, re.M):
        dims, op = tuple(int(d) for d in m.group(1).split(",") if d), \
            m.group(2)
        if dims not in shapes or op in ("parameter", "get-tuple-element",
                                        "tuple", "bitcast"):
            continue
        if op == "fusion":
            body = re.search(r"\n%" + re.escape(
                re.search(r"calls=%?([\w.\-]+)", m.group(3)).group(1))
                + r" .*?\n}", text, re.S).group(0)
            op = "fusion of " + ",".join(sorted(
                set(re.findall(r"\s([a-z\-]+)\(", body)) - {"parameter"}))
        out.add(op)
    return out


@pytest.mark.parametrize("arch,layers,B,S,pool_tokens",
                         [("phi3-mini-3.8b", 2, 8, 1024, 6144),
                          ("deepseek-7b", 2, 8, 2048, 10240),
                          ("deepseek-v2-lite", 3, 32, 5120, 163840)],
                         ids=["phi3", "deepseek", "deepseek_v2_lite"])
def test_paged_programs_update_the_pool_in_place(one_chip, on_tpu, arch,
                                                  layers, B, S, pool_tokens):
    """The decode and chunk programs as the engine jits them, the pool
    donated, over a benchmark cell's whole pool: the pool is aliased to
    an output, and the one kind of instruction that outputs a pool leaf
    or one layer of one is the scatter of the new rows into it.  Rows of
    phi3's 32 x 96 keys and of the 576-value latent are what the TPU
    would keep block axis minor-most, copied into row order and back by
    every program, were they not padded to whole 128-lane registers.
    Nor is the gathered context copied into attention's layout: deepseek's
    heads of 128 stay heads in the pool (``layers.kv_row``)."""
    cfg = dataclasses.replace(get_config(arch), n_layers=layers)
    rules = default_rules(None)
    bt, chunk = 16, 512
    pool = _tree_sds(lm.pool_defs(cfg, pool_tokens // bt + 1, bt), one_chip)
    shapes = {leaf.shape for leaf in jax.tree.leaves(pool)}
    shapes |= {shape[1:] for shape in shapes}
    i32 = jnp.int32
    step = jax.jit(lambda p, t, pool, tab, pos, lv: lm.decode_step_paged(
        p, t, pool, tab, pos, lv, cfg, rules), donate_argnums=(2,))
    fill = jax.jit(lambda p, t, pool, row, start, valid: lm.prefill_chunk(
        p, t, pool, row, start, valid, cfg, rules), donate_argnums=(2,))
    params = _tree_sds(lm.model_defs(cfg), one_chip)
    programs = [
        step.lower(params, _sds((B, 1), i32, one_chip), pool,
                   _sds((B, S // bt), i32, one_chip),
                   _sds((B,), i32, one_chip),
                   _sds((B,), jnp.bool_, one_chip)).compile(),
        fill.lower(params, _sds((1, chunk), i32, one_chip), pool,
                   _sds((S // bt,), i32, one_chip), _sds((), i32, one_chip),
                   _sds((), i32, one_chip)).compile()]
    for compiled in programs:
        assert "input_output_alias" in compiled.as_text().splitlines()[0]
        writers = _pool_writers(compiled, shapes)
        assert writers and all("scatter" in op and "copy" not in op
                               and "dynamic-slice" not in op
                               for op in writers), writers
    if not cfg.kv_lora_rank:
        context = B * S * cfg.n_kv_heads * cfg.head_dim
        assert all(math.prod(dims) != context
                   for dims in _copies(programs[0])), _copies(programs[0])


def test_one_layer_mesh_decode_compiles_for_v5e_2x2(topo, on_tpu):
    """The sharded serving step on a 2x2 (data x model) mesh: XLA cannot
    partition a Mosaic kernel, so every seam takes the XLA expression
    (GSPMD shards it) and the step compiles with no Pallas call."""
    cfg = dataclasses.replace(PHI3, n_layers=1)
    mesh = substrate.make_mesh((2, 2), ("data", "model"),
                               devices=topo.devices)
    rules = default_rules(mesh, kv_heads=cfg.n_kv_heads, batch=1)
    B, S = 4, 1024

    def placed(defs):
        return jax.tree.map(
            lambda pv, sh: jax.ShapeDtypeStruct(pv.shape, pv.dtype,
                                                sharding=sh),
            defs, param_shardings(defs, rules),
            is_leaf=lambda x: isinstance(x, PV))

    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    step = jax.jit(lambda p, t, c, pos: lm.decode_step(p, t, c, pos, cfg,
                                                       rules))
    compiled = step.lower(
        placed(lm.model_defs(cfg)), _sds((B, 1), jnp.int32, rep),
        placed(lm.cache_defs(cfg, B, S)), _sds((B,), jnp.int32, rep)).compile()
    text = compiled.as_text()
    assert _kernels(compiled) == 0
    assert "all-reduce" in text                 # tensor-parallel partials
