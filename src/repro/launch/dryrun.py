"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: a successful
compile on the 16x16 (single-pod) and 2x16x16 (multi-pod) meshes means every
sharding constraint, collective, and buffer fits together; the printed
memory_analysis proves per-device HBM fit, cost_analysis + the collective
parse feed §Roofline.

Per cell we compile:
  * the FULL model (memory analysis is exact; while bodies counted once),
  * 1-period and 2-period variants (cost extrapolation: total(L) =
    f1 + (L-1)(f2-f1) — DESIGN.md §8).

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
  python -m repro.launch.dryrun --arch llama3-8b --topology 32x8:two-level
  python -m repro.launch.dryrun --arch llama3-8b --topology 2x16x8

``--topology [Px]CxL[:hierarchy]`` overrides the production mesh with an
explicit topology (clusters on the `data` axis, lanes on `model`; a third
leading size adds the outermost `pod` ring level) — the same
:class:`repro.topology.Topology` value the sim layer prices, so the
fig6/fig7 factorisation sweeps and the compile surface stay in lock-step.
"""
# The VERY FIRST lines — before ANY other import (jax locks device count on
# first init).
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse
import dataclasses
import json
import pathlib
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config, list_archs
from repro.configs.base import ModelConfig, ShapeSpec
from repro.launch.mesh import (make_production_mesh, parse_launch_topology,
                               production_topology, topology_tag)
from repro.launch.specs import input_shardings, input_specs
from repro.models import lm
from repro.parallel.sharding import (abstract_params, default_rules,
                                     param_shardings)
from repro.roofline.analysis import (HW, collective_bytes,
                                     collective_level_bytes,
                                     exposed_level_seconds, extrapolate,
                                     level_wire_seconds, memory_model_bytes,
                                     parse_collectives, resident_model_bytes,
                                     roofline_terms, wire_seconds)
from repro.testing.timing import now
from repro.topology import Topology
from repro.train import OptConfig, TrainState, make_train_step
from repro.train.optimizer import opt_state_defs

#: memory-bound giants keep m/v + grad accumulators in bf16
#: (EXPERIMENTS.md records the trade)
OPT_BF16 = {"qwen3-moe-235b-a22b", "jamba-1.5-large-398b"}

#: target local microbatch (sequences per device per accumulation step)
TARGET_LOCAL_MB = 2
LOSS_CHUNK = 512


def _dp_size(mesh) -> int:
    return int(np_prod(mesh.shape.get(a, 1) for a in ("pod", "data")))


def np_prod(it):
    out = 1
    for x in it:
        out *= x
    return out


def n_microbatches(cfg: ModelConfig, shape: ShapeSpec, mesh) -> int:
    if shape.kind != "train":
        return 1
    local = max(1, shape.global_batch // _dp_size(mesh))
    n = max(1, local // TARGET_LOCAL_MB)
    while shape.global_batch % n:
        n -= 1
    return n


def build_rules(cfg: ModelConfig, shape: ShapeSpec, mesh):
    return default_rules(
        mesh,
        kv_heads=cfg.n_kv_heads,
        cache_seq="model" if shape.is_decode else None,
        act_seq=not shape.is_decode,
        batch=shape.global_batch)


def _opt_cfg(cfg: ModelConfig) -> OptConfig:
    if cfg.name in OPT_BF16:
        # HBM-bound giants: bf16 states, bf16 update math, no fp32 master
        # (8-bit-Adam-class trade; EXPERIMENTS.md documents it)
        return OptConfig(state_dtype=jnp.bfloat16, master_fp32=False,
                         math_dtype=jnp.bfloat16)
    return OptConfig()


def lower_cell(cfg: ModelConfig, shape: ShapeSpec, mesh, *,
               n_micro: int | None = None, rules=None, grad_sync=None):
    """Returns (lowered, compiled) for one cell on one mesh.

    ``rules`` overrides the default sharding rules (a plain argument — the
    §Perf strategies pass their rule tables here instead of monkey-patching
    :func:`build_rules`); ``grad_sync`` is an optional gradient-sync hook
    forwarded to :func:`repro.train.make_train_step`.
    """
    cfg = dataclasses.replace(cfg, loss_chunk=LOSS_CHUNK)
    if rules is None:
        rules = build_rules(cfg, shape, mesh)
    specs = input_specs(cfg, shape)
    shard = input_shardings(cfg, shape, rules)
    pdefs = lm.model_defs(cfg)
    p_abs = abstract_params(pdefs)
    p_sh = param_shardings(pdefs, rules)

    with mesh:
        if shape.kind == "train":
            ocfg = _opt_cfg(cfg)
            acc_dt = jnp.bfloat16 if cfg.name in OPT_BF16 else jnp.float32
            odefs = opt_state_defs(pdefs, ocfg)
            state = TrainState(p_abs, abstract_params(odefs))
            state_sh = TrainState(p_sh, param_shardings(odefs, rules))
            nm = n_micro if n_micro is not None else \
                n_microbatches(cfg, shape, mesh)
            step = make_train_step(cfg, rules, ocfg, n_microbatches=nm,
                                   acc_dtype=acc_dt, grad_sync=grad_sync)
            fn = jax.jit(step, in_shardings=(state_sh, shard),
                         out_shardings=(state_sh, None),
                         donate_argnums=(0,))
            lowered = fn.lower(state, specs)
        elif shape.kind == "prefill":
            def pre(params, batch):
                return lm.prefill(params, batch["tokens"], cfg, rules,
                                  shape.seq_len, batch.get("ctx"))
            fn = jax.jit(pre, in_shardings=(p_sh, shard))
            lowered = fn.lower(p_abs, specs)
        else:
            def dec(params, batch):
                return lm.decode_step(params, batch["token"], batch["cache"],
                                      batch["pos"], cfg, rules)
            fn = jax.jit(dec, in_shardings=(p_sh, shard),
                         donate_argnums=(1,))
            lowered = fn.lower(p_abs, specs)
        compiled = lowered.compile()
    return lowered, compiled


def _variant(cfg: ModelConfig, n: int) -> ModelConfig:
    """n-period reduced-depth variant with layers UNROLLED (python loop):
    XLA's cost_analysis counts a while body once regardless of trip count,
    so cost extrapolation must come from unrolled 1- vs 2-period compiles."""
    kw = dict(n_layers=n * len(cfg.layer_period), unroll_layers=True)
    if cfg.n_enc_layers:
        kw["n_enc_layers"] = max(1, cfg.n_enc_layers * n // cfg.n_periods)
    return dataclasses.replace(cfg, **kw)


def _cost_shape(shape: ShapeSpec, nm: int) -> ShapeSpec:
    """Per-microbatch shape for the cost variants (totals are scaled back
    by n_microbatches)."""
    if nm == 1:
        return shape
    return dataclasses.replace(shape, global_batch=shape.global_batch // nm)


def model_flops(cfg: ModelConfig, shape: ShapeSpec) -> float:
    n_active = cfg.n_active_params()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch          # one token


def analyse_cell(cfg: ModelConfig, shape: ShapeSpec, mesh,
                 mesh_name: str, *, topology: Topology | None = None,
                 rules=None, n_micro: int | None = None,
                 grad_sync=None) -> dict:
    """Lower + compile one cell and derive its roofline record.

    ``topology`` prices the collectives per level (the record gains
    ``roofline.collective_s_by_level`` and ``per_device.wire_bytes_by_level``;
    without one the historical flat pricing applies).  ``rules`` /
    ``n_micro`` / ``grad_sync`` are explicit strategy overrides (no
    module-global mutation): sharding-rule table, microbatch count, and the
    trainer's gradient-sync hook.
    """
    n_dev = mesh.devices.size
    t0 = now()
    rec = {"arch": cfg.name, "shape": shape.name, "mesh": mesh_name,
           "devices": int(n_dev), "kind": shape.kind}
    if topology is not None:
        rec["topology"] = topology.describe()

    # full compile: memory truth + sharding coherence
    nm = n_micro if n_micro is not None else n_microbatches(cfg, shape, mesh)
    rec["n_microbatches"] = nm
    lowered, compiled = lower_cell(cfg, shape, mesh, n_micro=nm, rules=rules,
                                   grad_sync=grad_sync)
    ma = compiled.memory_analysis()
    # CPU backend's peak_memory_in_bytes omits the temp arena; the honest
    # per-device residency is args + temps + (outputs - donated aliases).
    live = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    rec["mem_per_device"] = {
        "arguments_gib": ma.argument_size_in_bytes / 2**30,
        "outputs_gib": ma.output_size_in_bytes / 2**30,
        "temps_gib": ma.temp_size_in_bytes / 2**30,
        "aliased_gib": ma.alias_size_in_bytes / 2**30,
        "peak_gib": ma.peak_memory_in_bytes / 2**30,
        "total_gib": live / 2**30,
    }
    # CPU arenas double-buffer where TPU aliases donated state: report the
    # measured arena as the upper bound and analytic TPU residency as the
    # fit criterion (EXPERIMENTS.md §Dry-run documents both).
    resident = resident_model_bytes(cfg, shape, n_dev, nm,
                                    ma.argument_size_in_bytes,
                                    topology=topology)
    rec["mem_per_device"]["resident_model_gib"] = resident / 2**30
    rec["fits_16gib_hbm"] = bool(resident < 16 * 2**30)
    rec["cpu_arena_exceeds"] = bool(live >= 16 * 2**30)
    rec["compile_s_full"] = round(now() - t0, 1)
    del compiled, lowered

    # 1- and 2-period UNROLLED variants at per-microbatch shape:
    # per-device cost extrapolation (x n_microbatches for train)
    costs = {}
    cshape = _cost_shape(shape, nm)
    for n in (1, 2):
        lo, co = lower_cell(_variant(cfg, n), cshape, mesh, n_micro=1,
                            rules=rules, grad_sync=grad_sync)
        ca = co.cost_analysis()
        colls = parse_collectives(co.as_text())
        costs[n] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "wire": collective_bytes(colls),
        }
        if topology is not None:
            costs[n]["wire_levels"] = collective_level_bytes(colls, topology)
        del co, lo
    L = cfg.n_periods
    flops = nm * extrapolate(costs[1]["flops"], costs[2]["flops"], L)
    bytes_ = nm * extrapolate(costs[1]["bytes"], costs[2]["bytes"], L)
    wire = nm * extrapolate(costs[1]["wire"]["total"],
                            costs[2]["wire"]["total"], L)
    rec["per_device"] = {"flops": flops, "bytes": bytes_, "wire_bytes": wire}
    rec["collectives_p2"] = {k: v for k, v in costs[2]["wire"].items()}
    coll_s = None
    if topology is not None:
        # per-level wire bytes extrapolate level by level (each level's
        # traffic scales with depth exactly like the total does)
        wire_by_level = {
            lab: nm * extrapolate(costs[1]["wire_levels"][lab],
                                  costs[2]["wire_levels"][lab], L)
            for lab in topology.wire_labels()}
        secs = level_wire_seconds(wire_by_level, topology)
        coll_s = secs.pop("total")
        rec["per_device"]["wire_bytes_by_level"] = wire_by_level
    rec["roofline"] = roofline_terms(flops, bytes_, wire, collective_s=coll_s)
    if topology is not None:
        rec["roofline"]["collective_s_by_level"] = secs
        # the historical single-class price, for the flat-vs-level ablation
        rec["roofline"]["collective_s_flat_hw"] = wire_seconds(wire)
    # fusion-aware analytic memory second opinion (the CPU HLO byte count
    # has no TPU fusion: treat it as an upper bound, the model as the
    # realistic term; bottleneck classification uses the model)
    mm = memory_model_bytes(cfg, shape, n_dev, nm, topology=topology)
    rec["roofline"]["memory_s_hlo_upper"] = rec["roofline"]["memory_s"]
    rec["roofline"]["memory_s"] = mm / HW["hbm_bw"]
    terms = {k: rec["roofline"][k]
             for k in ("compute_s", "memory_s", "collective_s")}
    rec["roofline"]["bottleneck"] = max(terms, key=terms.get)
    rec["roofline"]["step_s_lower_bound"] = max(terms.values())
    if topology is not None:
        # overlap-aware exposure: the additive per-level seconds stay as
        # recorded above; these fields say how much of them an ideally
        # double-buffered schedule could NOT hide behind the compute
        exp = exposed_level_seconds(rec["roofline"]["collective_s_by_level"],
                                    terms["compute_s"], topology)
        rec["roofline"]["exposed_collective_s"] = exp.pop("total")
        rec["roofline"]["exposed_collective_s_by_level"] = exp
        rec["roofline"]["step_s_overlap_aware"] = max(
            terms["memory_s"],
            terms["compute_s"] + rec["roofline"]["exposed_collective_s"])
    mf = model_flops(cfg, shape)
    rec["model_flops_global"] = mf
    hlo_global = flops * n_dev
    rec["model_vs_hlo_flops"] = mf / hlo_global if hlo_global else 0.0
    rec["roofline"]["mfu_upper_bound"] = (
        mf / n_dev / HW["peak_flops"] / rec["roofline"]["step_s_lower_bound"]
        if rec["roofline"]["step_s_lower_bound"] else 0.0)
    rec["elapsed_s"] = round(now() - t0, 1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None)
    ap.add_argument("--shape", action="append", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--topology", default=None,
                    metavar="[P x]CxL[:hierarchy]",
                    help="override the mesh with an explicit Topology "
                         "(clusters on `data`, lanes on `model`; a third "
                         "leading size adds the `pod` ring level, e.g. "
                         "2x16x8:three-level)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    archs = args.arch or (list_archs() if args.all else ["llama3-8b"])
    shapes = args.shape or list(SHAPES)
    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    if args.topology is not None:
        if args.mesh != "single":
            ap.error("--topology replaces the pod mesh entirely; drop "
                     "--mesh (or run the pod meshes in a separate invocation)")
        topo = parse_launch_topology(args.topology)
        mesh_plan = [(make_production_mesh(topology=topo),
                      topology_tag(topo), topo)]
    else:
        meshes = {"single": [False], "multi": [True],
                  "both": [False, True]}[args.mesh]
        # every cell carries its Topology: `--mesh multi` prices the true
        # three-level production_topology(multi_pod=True) per level
        mesh_plan = [(make_production_mesh(multi_pod=m),
                      "pod2x16x16" if m else "pod16x16",
                      production_topology(multi_pod=m)) for m in meshes]

    failures = []
    for mesh, mname, topo in mesh_plan:
        for arch in archs:
            cfg = get_config(arch)
            for sname in shapes:
                shape = SHAPES[sname]
                path = outdir / f"{arch}__{sname}__{mname}.json"
                if not cfg.runnable(sname):
                    rec = {"arch": arch, "shape": sname, "mesh": mname,
                           "skipped": cfg.skip_shapes[sname]}
                    path.write_text(json.dumps(rec, indent=2))
                    print(f"[skip] {arch} x {sname} ({cfg.skip_shapes[sname]})")
                    continue
                if path.exists():
                    print(f"[cached] {path}")
                    continue
                try:
                    rec = analyse_cell(cfg, shape, mesh, mname,
                                       topology=topo)
                    path.write_text(json.dumps(rec, indent=2))
                    r = rec["roofline"]
                    print(f"[ok] {arch} x {sname} x {mname}: "
                          f"mem={rec['mem_per_device']['total_gib']:.2f}GiB "
                          f"compute={r['compute_s']:.4f}s "
                          f"mem={r['memory_s']:.4f}s "
                          f"coll={r['collective_s']:.4f}s "
                          f"bound={r['bottleneck']} "
                          f"({rec['elapsed_s']}s)", flush=True)
                except Exception as e:
                    failures.append((arch, sname, mname, repr(e)))
                    print(f"[FAIL] {arch} x {sname} x {mname}: {e}",
                          flush=True)
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nall requested dry-run cells passed")


if __name__ == "__main__":
    main()
