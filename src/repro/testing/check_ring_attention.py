"""Multi-device check: ring attention == reference attention (8 devices).

Covers both schedules: the flat single-axis ring, and the hierarchical
(pod, cluster, lane) odometer schedule on a 2x2x2 mesh driven by a shared
:class:`repro.topology.Topology`.  The hierarchical result must match the
flat-axis result to fp-reassociation precision (the online-softmax terms
are identical, only their combine order differs) and the reference oracle
at the same tolerance as the flat path.
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro import substrate
from repro.testing.x64 import x64_mode

#: |hier - flat| bound: same softmax terms, re-associated combine (f32)
REASSOC_TOL = 2e-6


def main(n: int = 8) -> None:
    # the f32 reassociation bounds assume x64 OFF, scoped via x64_mode
    # (flag restored + tamper-asserted on exit; import-clean)
    with x64_mode(False):
        _main(n)


def _main(n: int = 8) -> None:
    from repro.kernels import ref
    from repro.parallel.ring_attention import ring_attention
    from repro.topology import Topology

    mesh = substrate.make_mesh((n,), ("data",))
    rng = np.random.default_rng(0)
    B, S, H, Hkv, D = 2, 8 * 16, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)

    hier = None
    if n == 8:                       # the 2x2x2 three-level machine
        topo = Topology.from_levels([("pod", 2, 8.0), ("cluster", 2, 4.0),
                                     ("lane", 2, 2.0)])
        mesh3 = substrate.make_mesh((2, 2, 2), ("pod", "cluster", "lane"))
        hier = lambda q, k, v, causal, window: ring_attention(
            q, k, v, mesh3, topology=topo, causal=causal, window=window)

    for causal, window in [(True, None), (False, None), (True, 24)]:
        got = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, mesh, causal=causal, window=window))(q, k, v)
        want = ref.attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), causal=causal,
                             window=window).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        if hier is None:
            continue
        got3 = jax.jit(lambda q, k, v: hier(q, k, v, causal, window))(q, k, v)
        np.testing.assert_allclose(np.asarray(got3), np.asarray(want),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"hier vs oracle ({causal},{window})")
        np.testing.assert_allclose(
            np.asarray(got3), np.asarray(got), rtol=0, atol=REASSOC_TOL,
            err_msg=f"hier vs flat ({causal},{window})")
    print(f"check_ring_attention OK (n={n}"
          f"{', hier 2x2x2' if hier is not None else ''})")


if __name__ == "__main__":
    main(*(int(a) for a in sys.argv[1:]))
