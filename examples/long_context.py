"""Long-context via the RINGI idiom: hierarchical ring attention.

Demonstrates the paper's thesis at the sequence level: a long context
sharded over the AraXL hierarchy — the one :class:`repro.topology.Topology`
value that also drives the sim and the emulator.  KV blocks rotate
odometer-style (the intra-cluster `lane` ring turns every step; the
`cluster` ring only once per lane cycle, so the long wires carry 1/L of
the traffic), exactness verified against the single-device oracle and the
flat single-axis schedule.

Run:  PYTHONPATH=src python examples/long_context.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro import substrate
from repro.kernels import ref
from repro.parallel.ring_attention import ring_attention
from repro.testing.timing import now
from repro.topology import Topology


def main():
    # 2 clusters x 4 lanes — the same geometry type the sim prices
    topo = Topology(2, 4, cluster_axis="cluster", lane_axis="lane")
    mesh = substrate.make_mesh(topo.shape, ("cluster", "lane"))
    n = topo.n_lanes
    rng = np.random.default_rng(0)
    B, S, H, Hkv, D = 1, n * 256, 8, 2, 64       # 2k tokens over the 8-ring
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.bfloat16)

    fn = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh, topology=topo,
                                                causal=True, window=512))
    out = fn(q, k, v)                             # compile + run
    t0 = now()
    out = jax.block_until_ready(fn(q, k, v))
    dt = now() - t0

    want = ref.attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                         v.transpose(0, 2, 1, 3), causal=True,
                         window=512).transpose(0, 2, 1, 3)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    C, L = topo.grid
    print(f"hierarchical ring attention over {C}x{L} devices: "
          f"S={S}, SWA window 512")
    print(f"  wall {dt*1e3:.1f} ms, max err vs oracle {err:.2e}")
    kv_mb = 2 * (S // n) * H * D * 2 / 1e6
    print(f"  KV bytes rotated/device/step: {kv_mb:.2f} MB; "
          f"inter-cluster wires carry only 1/{L} of the steps")


if __name__ == "__main__":
    main()
