"""Front 2b — S1 (collective pricing coverage) and S3 (Pallas VRF budget),
plus the entry-point registry that traces the repo's public surface.

S1: a collective is *priced* when its replica group resolves onto the
declared :class:`repro.topology.Topology` as an axis-aligned subgrid —
``math.prod(group_level_extents(members, topo)) == len(members)``.  When
that fails (an axis the topology does not own, a mesh/topology size
mismatch, devices outside the topology) the roofline silently falls back
to flat outermost-wire attribution — exactly the PR 2 fig6 memo-bug class
this rule exists to catch before runtime.

S3: blocked specs must tile their arrays exactly.  The paper's kernels
(stencils, reductions, softmax) also keep the emulated machine's budget:
every Pallas buffer (operand block or scratch) must fit an LMUL=8 register
group (8 x VLEN = 64 KiB at the RVV-maximum 64 Kibit/vreg of
``AraXLParams``) and all resident buffers together the 32-vreg VRF
(256 KiB).  The language-model kernels (matmul, flash, paged attention,
rmsnorm) run on the TPU instead, so they obey its (8, 128) block tiling.

The registry traces with ``jax.make_jaxpr`` only — nothing executes — but
the ring/attention/MoE entries shard_map over an 8-device mesh, so the
semantic front needs ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(``python -m repro.analysis`` sets it before importing jax).
"""
from __future__ import annotations

import dataclasses
import itertools
import math

from repro.analysis import Finding
from repro.analysis.schedule_check import (axis_tuple, check_aliasing,
                                           check_ppermute_schedules,
                                           iter_eqns)

#: primitives whose replica groups the roofline prices; reductions are
#: matched by prefix ("psum" traces as `psum2` on this jax)
COLLECTIVE_PRIMITIVES = {
    "ppermute", "all_gather", "all_to_all", "reduce_scatter",
    "psum_scatter",
}
COLLECTIVE_PREFIXES = ("psum", "pmax", "pmin")


def _collective_axes(eqn) -> tuple[str, ...] | None:
    """The mesh axis names a collective runs over, or None if ``eqn`` is
    not a collective (reductions carry ``axes``, the rest ``axis_name``)."""
    name = eqn.primitive.name
    if name in COLLECTIVE_PRIMITIVES:
        return axis_tuple(eqn.params["axis_name"])
    if name.startswith(COLLECTIVE_PREFIXES) and "axes" in eqn.params:
        axes = tuple(a for a in axis_tuple(eqn.params["axes"])
                     if isinstance(a, str))
        return axes or None
    return None

#: RVV 1.0 register file: 32 vregs, LMUL=8 groups of 8 vregs
VRF_VREGS = 32
LMUL_MAX = 8


# ---------------------------------------------------------------------------
# S1 — pricing coverage
# ---------------------------------------------------------------------------

def _pricing_problems(axes: tuple[str, ...], mesh_shape: dict,
                      topology) -> list[str]:
    from repro.roofline.analysis import group_level_extents
    from repro.topology import mesh_levels

    owned: set = set()
    for lvl in topology.levels:
        owned |= set(lvl.axes)
    missing = [a for a in axes if a not in owned]
    if missing:
        return [f"axes {missing} not owned by any level of the declared "
                f"topology {topology.axis_names} — the roofline would "
                f"fall back to flat outermost-wire pricing"]
    try:
        mesh_levels(topology, {a: s for a, s in mesh_shape.items()
                               if a in owned})
    except ValueError as e:
        return [f"mesh/topology mismatch: {e}"]

    # Build the replica group in topology-flat (outer-major) numbering:
    # the collective's axes vary, every other mesh axis is pinned to 0.
    axes_set = set(axes)
    level_coords = []
    for lvl in topology.levels:
        laxes = lvl.axes
        ranges = [range(mesh_shape[a]) if a in axes_set else range(1)
                  for a in laxes]
        coords = set()
        for combo in itertools.product(*ranges):
            c = 0
            for a, v in zip(laxes, combo):
                c = c * mesh_shape[a] + v
            coords.add(c)
        level_coords.append(sorted(coords))
    members = tuple(sorted(
        sum(c * s for c, s in zip(combo, topology.strides()))
        for combo in itertools.product(*level_coords)))
    extents = group_level_extents(members, topology)
    if math.prod(extents) != len(members):
        return [f"replica group of {len(members)} over {axes} is not an "
                f"axis-aligned subgrid of {topology.axis_names} (extents "
                f"{extents}) — priced by the conservative flat fallback"]
    return []


def check_collective_pricing(closed_jaxpr, topology,
                             label: str) -> list[Finding]:
    """Every collective in the trace must price as an axis-aligned subgrid
    of the declared topology (no silent flat-fallback attribution)."""
    findings = []
    seen = set()
    for eqn, mesh in iter_eqns(closed_jaxpr.jaxpr):
        name = eqn.primitive.name
        axes = _collective_axes(eqn)
        if axes is None:
            continue
        if mesh is None:
            findings.append(Finding(
                "S1", label, 0,
                f"{name} over {axes} outside any shard_map mesh — "
                f"unpriceable replica group",
                "run collectives inside the substrate shard_map wrappers"))
            continue
        key = (name, axes)
        if key in seen:                      # one finding per (prim, axes)
            continue
        seen.add(key)
        for prob in _pricing_problems(axes, dict(mesh.shape), topology):
            findings.append(Finding(
                "S1", label, 0, f"{name} over {axes}: {prob}",
                "declare every collective axis as a Topology level (the "
                "geometry the roofline prices) or move the collective "
                "onto declared axes"))
    return findings


# ---------------------------------------------------------------------------
# S3 — Pallas grid/BlockSpec divisibility + block budget
# ---------------------------------------------------------------------------

#: TPU tiling rule: a block's last two dims are multiples of (8, 128) or
#: equal the array's dims — what the Mosaic compiler enforces on v5e
TPU_TILE = (8, 128)


def _dim(d) -> int:
    return int(getattr(d, "block_size", d))


def _pallas_calls(closed_jaxpr):
    for eqn, _ in iter_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["grid_mapping"], eqn.params["jaxpr"]


def _operand_blocks(gm):
    """(index, block shape, array aval, blocked?) per operand/output."""
    for i, bmap in enumerate(gm.block_mappings):
        shape = tuple(_dim(d) for d in bmap.block_shape)
        blocked = all(type(d).__name__ == "Blocked"
                      for d in bmap.block_shape)
        yield i, shape, bmap.array_aval, blocked


def _ragged(gm, label) -> list[Finding]:
    findings = []
    for i, shape, arr, blocked in _operand_blocks(gm):
        if not blocked or len(shape) != len(arr.shape):
            continue
        for bd, ad in zip(shape, arr.shape):
            if bd and ad % bd:
                findings.append(Finding(
                    "S3", label, 0,
                    f"operand {i}: array dim {ad} not divisible by block "
                    f"dim {bd} (grid {tuple(gm.grid)}) — ragged trailing "
                    f"block", "pad the array or pick a divisor block shape"))
    return findings


def check_pallas_budget(closed_jaxpr, params, label: str) -> list[Finding]:
    """The paper's kernels on the emulated vector machine.  ``params`` is
    an :class:`repro.sim.AraXLParams` — the budget source: 64 Kibit/vreg,
    32 vregs, LMUL=8 groups."""
    vreg_bytes = params.vlen_bits // 8
    buf_budget = LMUL_MAX * vreg_bytes       # one LMUL=8 register group
    total_budget = VRF_VREGS * vreg_bytes    # the whole VRF
    findings = []
    for gm, inner in _pallas_calls(closed_jaxpr):
        findings += _ragged(gm, label)
        bufs = [(f"operand {i} block {shape} ({arr.dtype})",
                 math.prod(shape) * arr.dtype.itemsize)
                for i, shape, arr, _ in _operand_blocks(gm)]
        n_io = gm.num_inputs + gm.num_outputs
        for v in inner.invars[n_io:]:
            aval = getattr(v.aval, "inner_aval", v.aval)
            nbytes = math.prod(aval.shape) * aval.dtype.itemsize
            bufs.append(
                (f"scratch {tuple(aval.shape)} ({aval.dtype})", nbytes))
        for desc, nbytes in bufs:
            if nbytes > buf_budget:
                findings.append(Finding(
                    "S3", label, 0,
                    f"{desc} = {nbytes} B exceeds one LMUL={LMUL_MAX} "
                    f"register group ({buf_budget} B at "
                    f"{params.vlen_bits}-bit VLEN)",
                    "shrink the block (bm/bn/bk) so a block fits 8 vregs"))
        total = sum(nbytes for _, nbytes in bufs)
        if total > total_budget:
            findings.append(Finding(
                "S3", label, 0,
                f"resident blocks+scratch = {total} B exceed the "
                f"{VRF_VREGS}-vreg VRF ({total_budget} B)",
                "shrink block shapes — the kernel cannot keep all "
                "operands register-resident"))
    return findings


def check_tpu_tiling(closed_jaxpr, label: str) -> list[Finding]:
    """The language-model kernels, which run on the TPU: blocks tile their
    arrays exactly and obey :data:`TPU_TILE`.  No register budget — VMEM
    legality comes from compiling for the chip (tests/test_tpu_compile.py)."""
    findings = []
    for gm, _ in _pallas_calls(closed_jaxpr):
        findings += _ragged(gm, label)
        for i, shape, arr, blocked in _operand_blocks(gm):
            if not blocked:
                continue
            for bd, ad, q in zip(shape[-2:], arr.shape[-2:],
                                 TPU_TILE[-len(shape[-2:]):]):
                if bd != ad and bd % q:
                    findings.append(Finding(
                        "S3", label, 0,
                        f"operand {i}: block {shape} of array "
                        f"{tuple(arr.shape)} breaks the TPU {TPU_TILE} "
                        f"tile (dim {bd} is neither a multiple of {q} nor "
                        f"the whole {ad})",
                        "round the block up to the tile and pad the array"))
    return findings


# ---------------------------------------------------------------------------
# Entry-point registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Entry:
    label: str
    closed_jaxpr: object
    topology: object | None      # declared Topology (S1) or None
    params: object | None        # AraXLParams (S3 register budget) or None
    tpu: bool = False            # S3 TPU tiling rule (the LM kernels)


def _ring_entries():
    import jax
    import jax.numpy as jnp
    from repro.core import ring
    from repro.core.machine import make_machine
    from repro.sim import araxl_params

    p8 = araxl_params(8)                     # 2 clusters x 4 lanes
    spec = make_machine(topology=p8.topology).spec
    topo = spec.topology
    reg = jnp.zeros((16, 2, 4), jnp.float32)
    row = jnp.zeros((8, 8), jnp.float32)
    rs_in = jnp.zeros((8, 16), jnp.float32)

    for h in ("flat", "two-level"):
        yield Entry(
            f"entry:reduce_scalar[{h}]",
            jax.make_jaxpr(lambda d, h=h: ring.reduce_scalar(
                spec, d, "sum", mode="ring", hierarchy=h))(reg),
            topo, None)
        for sched in ("seq", "db"):
            yield Entry(
                f"entry:ring_allgather[{h},{sched}]",
                jax.make_jaxpr(lambda d, h=h, s=sched: ring.ring_allgather(
                    spec, d, mode="ring", hierarchy=h, schedule=s))(row),
                topo, None)
            yield Entry(
                f"entry:ring_reduce_scatter[{h},{sched}]",
                jax.make_jaxpr(
                    lambda d, h=h, s=sched: ring.ring_reduce_scatter(
                        spec, d, mode="ring", hierarchy=h,
                        schedule=s))(rs_in),
                topo, None)
    yield Entry(
        "entry:ring_allgather[xla]",
        jax.make_jaxpr(lambda d: ring.ring_allgather(
            spec, d, mode="xla"))(row),
        topo, None)
    yield Entry(
        "entry:ring_reduce_scatter[xla]",
        jax.make_jaxpr(lambda d: ring.ring_reduce_scatter(
            spec, d, mode="xla"))(rs_in),
        topo, None)


def _ring_attention_entries():
    import jax
    import jax.numpy as jnp
    from repro.parallel.ring_attention import ring_attention
    from repro import substrate
    from repro.topology import Topology

    q = jnp.zeros((1, 16, 2, 8), jnp.float32)
    topo3 = Topology.from_levels([("pod", 2, 8.0), ("cluster", 2, 4.0),
                                  ("lane", 2, 2.0)])
    mesh3 = substrate.make_mesh((2, 2, 2), ("pod", "cluster", "lane"))
    topo1 = Topology.from_levels([("lane", 8, 2.0)])
    mesh1 = substrate.make_mesh((8,), ("lane",))
    for sched in ("seq", "db"):
        yield Entry(
            f"entry:ring_attention[hier2x2x2,{sched}]",
            jax.make_jaxpr(lambda a, b, c, s=sched: ring_attention(
                a, b, c, mesh3, topology=topo3, schedule=s))(q, q, q),
            topo3, None)
        yield Entry(
            f"entry:ring_attention[flat,{sched}]",
            jax.make_jaxpr(lambda a, b, c, s=sched: ring_attention(
                a, b, c, mesh1, axis="lane", schedule=s))(q, q, q),
            topo1, None)


def _moe_entries():
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro import substrate
    from repro.models import layers as L
    from repro.parallel.sharding import ShardingRules, init_params
    from repro.topology import Topology

    cfg = dataclasses.replace(
        get_smoke_config("qwen3-moe-235b-a22b"), n_experts=8,
        experts_per_token=2, capacity_factor=8.0, moe_impl="a2a")
    topo3 = Topology.from_levels([("pod", 2, 8.0), ("cluster", 2, 4.0),
                                  ("lane", 2, 2.0)])
    mesh3 = substrate.make_mesh((2, 2, 2), ("pod", "cluster", "lane"))
    axes = ("pod", "cluster", "lane")
    rules3 = ShardingRules(mesh3, {"batch": None, "seq": None,
                                   "fsdp": None, "model": axes,
                                   "kv": None, "cache_seq": None,
                                   "act_seq": axes})
    params = init_params(L.moe_defs(cfg), jax.random.key(0))
    x = jnp.zeros((4, 16, cfg.d_model), jnp.float32)
    assert L.moe_mode(cfg, rules3) == "ep_a2a"
    with mesh3:
        for topo, tag in ((topo3, "hier2x2x2"), (None, "flat")):
            yield Entry(
                f"entry:moe_ep_a2a[{tag}]",
                jax.make_jaxpr(lambda p, x_, t=topo: L.moe_layer(
                    p, x_, cfg, rules3, topology=t))(params, x),
                topo3, None)


def _kernel_entries():
    import jax
    import jax.numpy as jnp
    from repro.kernels import flash_attention as fa
    from repro.kernels import matmul as mm
    from repro.kernels import paged_attention as pa
    from repro.kernels import reduction as red
    from repro.kernels import rmsnorm as rn
    from repro.kernels import stencil as st
    from repro.sim import araxl_params

    p64 = araxl_params(64)
    z = lambda *s: jnp.zeros(s, jnp.float32)

    # the language-model kernels run on the TPU: tiling rule, no RVV budget
    tpu_cases = [
        ("fmatmul[256]", lambda: jax.make_jaxpr(
            lambda a, b: mm.matmul(a, b, interpret=True))(
                z(256, 256), z(256, 256))),
        ("flash_attention[S256,D64]", lambda: jax.make_jaxpr(
            lambda q, k, v: fa.flash_attention(q, k, v, interpret=True))(
                z(1, 4, 256, 64), z(1, 2, 256, 64), z(1, 2, 256, 64))),
        ("paged_attention[T256,bt16,D64]", lambda: jax.make_jaxpr(
            lambda q, kp, vp, tb, ln: pa.paged_attention(
                q, kp, vp, tb, ln, interpret=True))(
                z(1, 2, 2, 64), z(2, 17, 16, 64), z(2, 17, 16, 64),
                jnp.zeros((1, 16), jnp.int32), jnp.zeros((1,), jnp.int32))),
        ("rmsnorm[D4096]", lambda: jax.make_jaxpr(
            lambda x, g: rn.rmsnorm(x, g, interpret=True))(
                z(64, 4096), z(4096))),
    ]
    # the paper's kernels keep the AraXL register budget
    cases = [
        ("jacobi2d[64x512]", lambda: jax.make_jaxpr(
            lambda x: st.jacobi2d(x, interpret=True))(z(66, 514))),
        ("fconv2d[64x512,7x7]", lambda: jax.make_jaxpr(
            lambda x, f: st.fconv2d(x, f, interpret=True))(
                z(70, 518), z(7, 7))),
        ("fdotproduct[16Ki]", lambda: jax.make_jaxpr(
            lambda a, b: red.dotprod(a, b, interpret=True))(
                z(16384), z(16384))),
        ("exp[16Ki]", lambda: jax.make_jaxpr(
            lambda x: red.expv(x, interpret=True))(z(16384))),
        ("softmax_rows[W2048]", lambda: jax.make_jaxpr(
            lambda x: red.softmax_rows(x, interpret=True))(z(64, 2048))),
    ]
    for label, trace in tpu_cases:
        yield Entry(f"entry:{label}", trace(), None, None, tpu=True)
    for label, trace in cases:
        yield Entry(f"entry:{label}", trace(), None, p64)


def entries() -> list[Entry]:
    import jax
    n = len(jax.devices())
    if n < 8:
        raise RuntimeError(
            f"semantic analysis shard_maps over 8 devices but only {n} "
            f"exist — run `python -m repro.analysis` (sets "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8 before "
            f"importing jax) or set the env yourself")
    out = []
    out += _ring_entries()
    out += _ring_attention_entries()
    out += _moe_entries()
    out += _kernel_entries()
    return out


def semantic_findings() -> list[Finding]:
    """Trace every registered entry point and run S1 + S2 + S3."""
    findings: list[Finding] = []
    for e in entries():
        if e.topology is not None:
            findings += check_collective_pricing(
                e.closed_jaxpr, e.topology, e.label)
        findings += check_ppermute_schedules(e.closed_jaxpr, e.label)
        findings += check_aliasing(e.closed_jaxpr, e.label)
        if e.params is not None:
            findings += check_pallas_budget(e.closed_jaxpr, e.params,
                                            e.label)
        if e.tpu:
            findings += check_tpu_tiling(e.closed_jaxpr, e.label)
    return findings
