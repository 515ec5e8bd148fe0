"""Multi-device check: MoE EP (psum) and EP (a2a) match the local oracle —
including the hierarchical a2a, which must be *bit-identical* to the flat
exchange (the per-level all-to-all stages invert exactly and the expert FFN
is row-independent, so no fp reassociation occurs)."""
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro import substrate


def main(nd: int = 2, nm: int = 4) -> None:
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.models import layers as L
    from repro.models import lm
    from repro.parallel.sharding import (ShardingRules, default_rules,
                                         init_params)
    from repro.topology import Topology

    mesh = substrate.make_mesh((nd, nm), ("data", "model"))
    cfg0 = get_smoke_config("qwen3-moe-235b-a22b")
    cfg0 = dataclasses.replace(cfg0, n_experts=8, experts_per_token=2,
                               capacity_factor=8.0)
    rng = np.random.default_rng(0)
    B, S = 4, 16
    from repro.parallel.sharding import PV
    defs = L.moe_defs(cfg0)
    params = init_params(defs, jax.random.key(1))
    x = jnp.asarray(rng.normal(size=(B, S, cfg0.d_model)) * 0.3, jnp.float32)

    rules0 = default_rules(None)
    want = L.moe_layer(params, x, cfg0, rules0)

    rules = default_rules(mesh, act_seq=True, batch=B)
    with mesh:
        got_ep = jax.jit(lambda p, x: L.moe_layer(
            p, x, cfg0, rules))(params, x)
        cfg_a2a = dataclasses.replace(cfg0, moe_impl="a2a")
        got_a2a = jax.jit(lambda p, x: L.moe_layer(
            p, x, cfg_a2a, rules))(params, x)
    np.testing.assert_allclose(np.asarray(got_ep), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_a2a), np.asarray(want),
                               rtol=2e-4, atol=2e-4)

    # Hierarchical EP a2a on the 2x2x2 three-level machine: the expert ring
    # spans every topology level axis; results must be BIT-identical both
    # to the one-stage exchange on the same mesh and to the single-axis
    # flat machine.
    if nd * nm == 8:
        topo = Topology.from_levels([("pod", 2, 8.0), ("cluster", 2, 4.0),
                                     ("lane", 2, 2.0)])
        mesh3 = substrate.make_mesh((2, 2, 2), ("pod", "cluster", "lane"))
        axes = ("pod", "cluster", "lane")
        rules3 = ShardingRules(mesh3, {"batch": None, "seq": None,
                                       "fsdp": None, "model": axes,
                                       "kv": None, "cache_seq": None,
                                       "act_seq": axes})
        assert L.moe_mode(cfg_a2a, rules3) == "ep_a2a"
        mesh1 = substrate.make_mesh((8,), ("model",))
        rules1 = default_rules(mesh1, act_seq=True, batch=B)
        with mesh1:
            got_flat1 = jax.jit(lambda p, x: L.moe_layer(
                p, x, cfg_a2a, rules1))(params, x)
        with mesh3:
            got_hier = jax.jit(lambda p, x: L.moe_layer(
                p, x, cfg_a2a, rules3, topology=topo))(params, x)
            got_flat3 = jax.jit(lambda p, x: L.moe_layer(
                p, x, cfg_a2a, rules3))(params, x)
        np.testing.assert_allclose(np.asarray(got_hier), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_array_equal(np.asarray(got_hier),
                                      np.asarray(got_flat3),
                                      err_msg="hier vs one-stage (same mesh)")
        np.testing.assert_array_equal(np.asarray(got_hier),
                                      np.asarray(got_flat1),
                                      err_msg="hier vs flat single axis")
        print("check_moe hier 2x2x2 bitwise OK")
    print(f"check_moe OK (mesh {nd}x{nm})")


if __name__ == "__main__":
    main(*(int(a) for a in sys.argv[1:]))
