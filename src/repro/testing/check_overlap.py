"""Multi-device overlap checks (8 fake devices): the double-buffered
schedules are *semantically free* — bit-identical or tolerance-equivalent
to their sequential twins — and their 8-device wall-clock is measured.

Parts (first CLI argument; default ``all``):

``attn``  ring attention ``schedule="db"`` vs ``"seq"`` on the flat 8-ring
          and on the hierarchical 2x2x2 (pod, cluster, lane) odometer —
          bit-identical results, plus ``ringattn/...`` CSV rows with the
          median wall-clock of both schedules (the measured sequential-vs-
          double-buffered comparison ``benchmarks/run.py ring_attn``
          records into BENCH_sim.json).

``grad``  the bucketed, backward-overlapped gradient sync
          (``make_grad_sync(bucket_mb=...)``, ``fsdp_hier_ov``) is
          grad-equivalent to the plain hierarchical hook (``fsdp_hier``)
          on the tiny trainer: one train step of the smoke llama3-8b on a
          2x2x2 mesh under pod-local FSDP rules, updated params and loss
          compared across the two hooks (and against no hook at all —
          sharding constraints and optimization barriers are identities).

Usage: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       python -m repro.testing.check_overlap [attn|grad|all]
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro import substrate
from repro.testing.timing import median_time_us
from repro.testing.x64 import x64_mode


def _attn(n: int = 8) -> None:
    from repro.parallel.ring_attention import ring_attention
    from repro.topology import Topology

    rng = np.random.default_rng(0)
    B, S, H, Hkv, D = 2, n * 16, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)

    mesh = substrate.make_mesh((n,), ("data",))
    cases = {"flat": dict(mesh=mesh)}
    if n == 8:
        topo = Topology.from_levels([("pod", 2, 8.0), ("cluster", 2, 4.0),
                                     ("lane", 2, 2.0)])
        mesh3 = substrate.make_mesh((2, 2, 2), ("pod", "cluster", "lane"))
        cases["hier2x2x2"] = dict(mesh=mesh3, topology=topo)

    for name, kw in cases.items():
        outs = {}
        for sched in ("seq", "db"):
            fn = jax.jit(lambda q, k, v, kw=kw, sched=sched: ring_attention(
                q, k, v, kw["mesh"], topology=kw.get("topology"),
                causal=True, schedule=sched))
            outs[sched] = np.asarray(fn(q, k, v))
            us = median_time_us(fn, q, k, v, reps=5, warmup=1)
            print(f"ringattn/{name}/{sched},{us:.0f},ok")
        # same blocks, same order, same arithmetic: db must be bit-identical
        np.testing.assert_array_equal(outs["db"], outs["seq"],
                                      err_msg=f"db vs seq ({name})")
    print(f"check_overlap attn OK (n={n})")


def _grad() -> None:
    from repro.configs import get_smoke_config
    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_production_mesh, parse_launch_topology
    from repro.launch.perf import apply_strategy
    from repro.train import (OptConfig, init_train_state, make_grad_sync,
                             make_train_step)

    cfg = get_smoke_config("llama3-8b")
    topo = parse_launch_topology("2x2x2")
    mesh = make_production_mesh(topology=topo)
    shape = ShapeSpec("tiny_train", 32, 8, "train")
    cfg, rules, _, sync_hier = apply_strategy("fsdp_hier", cfg, shape, mesh,
                                              topo)
    # tiny bucket size so the smoke model genuinely splits into >1 bucket
    sync_ov = make_grad_sync(cfg, rules, bucket_mb=0.02)

    ocfg = OptConfig()
    key = jax.random.PRNGKey(0)
    state0 = init_train_state(cfg, ocfg, key)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size,
                                          size=(shape.global_batch,
                                                shape.seq_len)), jnp.int32)
    batch = {"tokens": tokens}

    results = {}
    for name, sync in (("none", None), ("hier", sync_hier), ("ov", sync_ov)):
        step = jax.jit(make_train_step(cfg, rules, ocfg, grad_sync=sync))
        state1, metrics = step(state0, batch)
        results[name] = (jax.tree.map(np.asarray, state1.params),
                         float(metrics["loss"]))

    l_none, l_hier, l_ov = (results[k][1] for k in ("none", "hier", "ov"))
    assert l_hier == l_ov, (l_hier, l_ov)     # loss precedes the sync: exact
    assert l_none == l_hier, (l_none, l_hier)
    ref = results["hier"][0]
    for name in ("none", "ov"):
        got = results[name][0]
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                a, b, rtol=1e-6, atol=1e-7,
                err_msg=f"params diverge ({name} vs hier)"),
            got, ref)
    print("check_overlap grad OK (fsdp_hier == fsdp_hier_ov == unsynced)")


def main(part: str = "all", n: int = 8) -> None:
    with x64_mode(False):                     # f32 tolerances assume x64 off
        if part in ("attn", "all"):
            _attn(n)
        if part in ("grad", "all"):
            _grad()


if __name__ == "__main__":
    args = sys.argv[1:]
    main(args[0] if args else "all", *(int(a) for a in args[1:]))
