"""Multi-device check: paged-KV serving on a 2x2x2 mesh of 8 devices.

The paged engine (:class:`repro.serve.PagedServingEngine`) and the dense
:class:`repro.serve.ServingEngine` run the identical request stream on the
same (pod, data, model) mesh with the same sharding rules.  Asserts:

  1. *bit-identity*: per-request token streams of dense and paged match
     exactly for the same admission order — the block-table indirection,
     COW prefix sharing, and the zero-block gather are all invisible to
     the math;
  2. *block reuse*: with duplicate prompts in the stream the allocator
     records shared-prefix hits, and a shared block that must diverge is
     copied (COW) rather than mutated in place;
  3. *hygiene*: after all requests finish every block is back on the free
     list (no leaks) and the zero block stays all-zeros;
  4. *chunked prefill*: the chunk-interleaved engine completes the same
     stream (admission under PREFILL, per-slot positions) and its streams
     also match dense for this single-slot-prefill admission order;
  5. *router affinity*: behind :class:`repro.serve.PrefixRouter`, a
     repeated prompt routes to the pod that served it first.

Usage: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       python -m repro.testing.check_serve_paged
"""
from __future__ import annotations

import sys

import jax
import numpy as np

from repro import substrate


def _drive(engine, reqs):
    for r in reqs:
        engine.submit(r)
    engine.run()
    return {r.rid: list(r.out) for r in reqs}


def main(n: int = 8) -> None:
    from repro.configs import get_smoke_config
    from repro.models import lm
    from repro.parallel.sharding import default_rules, init_params
    from repro.serve import (PagedServeConfig, PagedServingEngine,
                             PrefixRouter, Request, ServeConfig,
                             ServingEngine)

    assert len(jax.devices()) >= n, "need more fake devices"
    mesh = substrate.make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_smoke_config("llama3-8b")
    rules = default_rules(mesh, kv_heads=cfg.n_kv_heads, batch=1)
    params = init_params(lm.model_defs(cfg), jax.random.key(0))

    rng = np.random.default_rng(0)
    base = [rng.integers(1, cfg.vocab_size, int(rng.integers(5, 20)))
            .astype(np.int32) for _ in range(4)]
    # duplicates adjacent to their originals so the sharing pairs are
    # co-resident (admitted in the same wave -> block retain, not re-alloc)
    prompts = [base[0], base[0].copy(), base[1], base[1].copy(),
               base[2], base[3]]
    reqs = lambda: [Request(rid=i, prompt=p, max_new_tokens=8)
                    for i, p in enumerate(prompts)]

    dense = ServingEngine(cfg, params, rules, ServeConfig(max_batch=4,
                                                          max_seq=64))
    got_dense = _drive(dense, reqs())

    scfg = PagedServeConfig(max_batch=4, max_seq=64, block_tokens=8,
                            n_blocks=32)
    paged = PagedServingEngine(cfg, params, rules, scfg)
    got_paged = _drive(paged, reqs())

    # 1. bit-identity per request
    for rid in got_dense:
        assert got_dense[rid] == got_paged[rid], \
            (rid, got_dense[rid], got_paged[rid])

    # 2. duplicate prompts became block reuse, and divergence copied
    assert paged.alloc.shared_hits >= 1, "no shared-prefix block reuse"
    assert paged.cow_copies >= 1, "no COW copy despite shared full blocks"

    # 3. allocator hygiene: everything returned, zero block untouched —
    # shutdown() is the full gate (free list, refcounts, prefix registry)
    paged.shutdown()
    zeros = jax.tree.leaves(paged.pool)
    assert all(bool((leaf[:, 0] == 0).all()) for leaf in zeros), \
        "zero block written"

    # 4. chunked prefill completes the stream with identical streams for
    # this admission order (single prefill slot at a time)
    chunked = PagedServingEngine(cfg, params, rules,
                                 PagedServeConfig(max_batch=4, max_seq=64,
                                                  block_tokens=8,
                                                  n_blocks=32, chunk=16))
    got_chunked = _drive(chunked, reqs())
    for rid in got_dense:
        assert got_dense[rid] == got_chunked[rid], \
            (rid, got_dense[rid], got_chunked[rid])
    assert chunked.prefill_chunks > 0, "chunked engine never chunked"

    # 5. prefix-affinity routing: r1 (dup of r0) follows r0's pod even
    # when the other pod is idle
    def fresh():
        return PagedServingEngine(cfg, params, rules, scfg)

    router = PrefixRouter([fresh(), fresh()])
    stream = reqs()
    pod_first = router.submit(stream[0])     # r0 lands somewhere
    router.run()
    for r in stream[2:]:
        router.submit(r)                     # load up both pods
    router.run()
    pod_dup = router.submit(stream[1])       # dup of r0
    router.run()
    assert pod_dup == pod_first, \
        f"duplicate prompt routed {pod_first} -> {pod_dup}"
    assert router.affinity_hits >= 1

    print(f"check_serve_paged OK (mesh 2x2x2, {n} devices; "
          f"shared_hits={paged.alloc.shared_hits} "
          f"cow_copies={paged.cow_copies} "
          f"peak_blocks={paged.alloc.peak_allocated} "
          f"prefill_chunks={chunked.prefill_chunks})")


if __name__ == "__main__":
    argv = [int(a) for a in sys.argv[1:]]
    main(*argv)
