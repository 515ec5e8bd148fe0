"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The single-pod mesh is 16x16 = 256 chips
(a TPU v5e pod); multi-pod adds a leading 2-pod axis (512 chips) — the AraXL
hierarchy recursing outward: `model` = lanes within a cluster, `data` =
clusters, `pod` = the next ring level.

The geometry is also expressible as a shared :class:`repro.topology.Topology`
(``production_topology()`` — two levels single-pod, three levels multi-pod),
and ``make_production_mesh(topology=...)`` builds the mesh straight from one
(one mesh axis per topology level) — the same value ``repro.sim`` prices and
``repro.core.machine.make_machine`` emulates, so a fig6/fig7 sweep and a
dry-run compile describe the identical machine.
"""
from __future__ import annotations

from repro import substrate
from repro.topology import Level, Topology, parse_topology


def parse_launch_topology(s: str) -> Topology:
    """Parse a ``--topology`` spec onto the production axis names:
    ``CxL[:hierarchy]`` puts clusters on `data` and lanes on `model`;
    ``PxCxL[:hierarchy]`` adds the outermost `pod` ring level."""
    n_sizes = len(s.partition(":")[0].split("x"))
    if n_sizes == 2:
        return parse_topology(s, cluster_axis="data", lane_axis="model")
    axes = ("pod", "data", "model")
    if n_sizes > 3:
        axes = tuple(f"pod{j}" for j in range(n_sizes - 3)) + axes
    return parse_topology(s, level_axes=axes)


def topology_tag(topology: Topology) -> str:
    """Short artifact tag, e.g. "topo16x4-two-level" / "topo2x8x4-flat"."""
    sizes = "x".join(str(l.size) for l in topology.levels)
    return f"topo{sizes}-{topology.hierarchy}"


def production_topology(*, multi_pod: bool = False) -> Topology:
    """The production geometry as a Topology: clusters ride the `data` axis,
    lanes the `model` axis; the multi-pod machine adds an outermost 2-wide
    `pod` ring level."""
    if multi_pod:
        return Topology(levels=(Level("pod", 2, 8.0),
                                Level("data", 16, 4.0),
                                Level("model", 16, 2.0)))
    return Topology(16, 16, hierarchy="two-level",
                    cluster_axis="data", lane_axis="model")


def make_production_mesh(*, multi_pod: bool = False,
                         topology: Topology | None = None):
    # one mesh axis per topology level — the same builder the emulator uses
    from repro.core.machine import make_topology_mesh
    if topology is not None:
        if multi_pod:
            raise ValueError("multi_pod and topology= are mutually exclusive "
                             "(use a three-level pod x cluster x lane "
                             "topology instead)")
        return make_topology_mesh(topology)
    return make_topology_mesh(production_topology(multi_pod=multi_pod))


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CPU multi-device tests."""
    return substrate.make_mesh((n_data, n_model), ("data", "model"))
