"""Multi-device check: pod-local KV serving on a 2x2x2 mesh of 8 devices.

Two :class:`repro.serve.ServingEngine` instances run the identical request
stream on the same (pod, data, model) mesh with the same sharding rules —
one topology-blind, one with the three-level Topology.  The check asserts:

  1. *placement*: every KV-cache leaf of the topology engine is sharded by
     inner-level axes only (the `pod` axis never appears in a cache
     PartitionSpec), both at construction and after the decode loop ran;
  2. *affinity*: after pods have served distinct prompt prefixes, a request
     repeating a prefix is admitted into a slot of the pod that already
     holds it, even though lower-numbered slots in the other pod are free
     (the blind engine keeps the historical first-free order);
  3. *bit-identity*: per-request token streams of the two engines match
     exactly — placement and affinity only move where a request lands,
     never what it computes.

Usage: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       python -m repro.testing.check_serve_topology
"""
from __future__ import annotations

import sys

import jax
import numpy as np

from repro import substrate


def _spec_axes(spec) -> set:
    out = set()
    for part in spec:
        if part is None:
            continue
        out.update((part,) if isinstance(part, str) else part)
    return out


def _assert_pod_local(engine, when: str) -> set:
    seen = set()
    for leaf in jax.tree.leaves(engine.cache):
        axes = _spec_axes(leaf.sharding.spec)
        assert "pod" not in axes, \
            f"cache sharded across the pod ring {when}: {leaf.sharding.spec}"
        seen |= axes
    return seen


def main(n: int = 8) -> None:
    from repro.configs import get_smoke_config
    from repro.models import lm
    from repro.parallel.sharding import default_rules, init_params
    from repro.serve import Request, ServeConfig, ServingEngine
    from repro.topology import Topology

    assert len(jax.devices()) >= n, "need more fake devices"
    mesh = substrate.make_mesh((2, 2, 2), ("pod", "data", "model"))
    topo = Topology.from_levels([("pod", 2, 8.0), ("data", 2, 4.0),
                                 ("model", 2, 2.0)])
    cfg = get_smoke_config("llama3-8b")
    # serving rules: batch stays unsharded (the admit loop prefills one
    # request at a time), TP over `model` as in the decode dry-run cells
    rules = default_rules(mesh, kv_heads=cfg.n_kv_heads, batch=1)
    params = init_params(lm.model_defs(cfg), jax.random.key(0))
    scfg = ServeConfig(max_batch=4, max_seq=64)

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, int(rng.integers(4, 12)))
               .astype(np.int32) for _ in range(3)]
    prompts.append(prompts[2].copy())       # r3 repeats r2's prefix

    def request_stream():
        return [Request(rid=i, prompt=p, max_new_tokens=6)
                for i, p in enumerate(prompts)]

    def drive(engine, reqs):
        # phase 1: three distinct prompts fill slots 0..2 in both engines
        for r in reqs[:3]:
            engine.submit(r)
        engine.run()
        # phase 2: all slots free again; r3 repeats r2's prefix
        engine.submit(reqs[3])
        engine.run()
        return {r.rid: (r.slot, list(r.out)) for r in reqs}

    blind = ServingEngine(cfg, params, rules, scfg)
    aware = ServingEngine(cfg, params, rules, scfg, topology=topo)
    assert aware.n_pods == 2

    axes_used = _assert_pod_local(aware, "at construction")
    assert {"data", "model"} <= axes_used, \
        f"cache should still shard over inner axes, got {axes_used}"

    reqs_b, reqs_a = request_stream(), request_stream()
    got_b = drive(blind, reqs_b)
    got_a = drive(aware, reqs_a)
    _assert_pod_local(aware, "after the decode loop")

    # bit-identical token streams, request by request
    for rid in got_b:
        assert got_b[rid][1] == got_a[rid][1], \
            (rid, got_b[rid][1], got_a[rid][1])

    # phase-1 admission is first-free in both engines (no prefix history)
    assert [got_a[i][0] for i in range(3)] == [0, 1, 2]
    # r2's prefix landed in slot 2 = pod 1; the aware engine steers the
    # repeat there while the blind engine reuses the first free slot
    assert aware.slot_pod(2) == 1
    assert got_b[3][0] == 0, got_b[3]
    assert aware.slot_pod(got_a[3][0]) == 1, got_a[3]

    print(f"check_serve_topology OK (mesh 2x2x2, {n} devices; "
          f"pod-local cache axes={sorted(axes_used)})")


if __name__ == "__main__":
    argv = [int(a) for a in sys.argv[1:]]
    main(*argv)
