"""Machine construction helpers (the REQI view: one program, many clusters).

``make_machine`` is topology-first: pass a :class:`repro.topology.Topology`
(e.g. ``repro.sim.araxl_params(8).topology``) and the mesh axes, level grid,
and interconnect hierarchy are all derived from it — the emulator and the
analytical cost model then provably share one geometry value
(``machine.spec.topology == params.topology``).  The mesh gets **one axis
per topology level** (outermost first), so a three-level (pod, cluster,
lane) topology builds a (P, C, L) mesh whose non-lane axes ride the spec's
``cluster_axis`` tuple.  The legacy ``make_machine(C, L, hierarchy=...)``
form still works and builds the equivalent two-level Topology internally.
"""
from __future__ import annotations

from jax.sharding import Mesh

from repro import substrate
from repro.topology import Topology
from .isa import AraXLMachine
from .layout import VectorMachineSpec


def make_vector_mesh(n_clusters: int, n_lanes: int,
                     cluster_axis: str = "cluster",
                     lane_axis: str = "lane") -> Mesh:
    """A (C, L) mesh over however many devices exist (C*L must divide in)."""
    return substrate.make_mesh((n_clusters, n_lanes), (cluster_axis, lane_axis))


def make_topology_mesh(topology: Topology) -> Mesh:
    """One mesh axis per topology level, outermost first."""
    names = []
    for l in topology.levels:
        if not isinstance(l.axis, str):
            raise ValueError(f"make_machine needs single-name level axes, "
                             f"got {l.axis!r}")
        names.append(l.axis)
    return substrate.make_mesh(topology.shape, tuple(names))


def make_machine(n_clusters: int | None = None, n_lanes: int | None = None,
                 *, topology: Topology | None = None, vlen_bits: int = 65536,
                 sew_bits: int = 64, glsu_mode: str = "staged",
                 reduce_mode: str = "ring", hierarchy: str | None = None,
                 dtype=None, trace: list | None = None) -> AraXLMachine:
    import jax.numpy as jnp
    if topology is None:
        if n_clusters is None or n_lanes is None:
            raise ValueError("pass either topology= or (n_clusters, n_lanes)")
        # Historical default: the flattened ring unless asked otherwise.
        topology = Topology(n_clusters, n_lanes,
                            hierarchy=hierarchy or "flat")
    else:
        if (n_clusters, n_lanes) != (None, None) and \
                (n_clusters, n_lanes) != topology.grid:
            raise ValueError(f"(n_clusters, n_lanes)=({n_clusters}, "
                             f"{n_lanes}) conflicts with topology grid "
                             f"{topology.grid}")
        if hierarchy is not None:
            topology = topology.with_hierarchy(hierarchy)
    mesh = make_topology_mesh(topology)
    spec = VectorMachineSpec(mesh, topology.cluster_axis, topology.lane_axis,
                             vlen_bits, sew_bits, topology=topology)
    return AraXLMachine(spec, glsu_mode=glsu_mode, reduce_mode=reduce_mode,
                        dtype=dtype or jnp.float32, trace=trace)
