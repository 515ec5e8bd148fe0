"""repro.analysis acceptance: every rule (L1-L4, S1-S3) fires on its bad
fixture and stays silent on the good twin, the suppression syntax works,
the bench schema validator accepts the recorded artifact and rejects a
mutated one, and the repo itself analyzes clean end to end.

Lint fixtures are source *strings* fed to ``lint_source`` with a crafted
relpath (the relpath decides the allow-lists), so the banned spellings
below never execute and never trip the lint on this file.  Semantic
fixtures are traced in-process — the conftest gives the main pytest
process 8 fake devices, which is all ``jax.make_jaxpr`` needs.
"""
import copy
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from repro import substrate
from repro.analysis import RULES, Finding
from repro.analysis.bench import validate_section
from repro.analysis.jaxpr_check import (check_collective_pricing,
                                        check_pallas_budget,
                                        check_tpu_tiling)
from repro.analysis.lint import lint_source
from repro.analysis.schedule_check import (check_aliasing,
                                           check_ppermute_schedules,
                                           check_ring_permutation)
from repro.core.ring import _shift_perm
from repro.sim import araxl_params
from repro.testing.subproc import run_check
from repro.topology import Topology

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# L1 — substrate-only
# ---------------------------------------------------------------------------

L1_BAD = """\
import jax

def step(x):
    return jax.lax.ppermute(x, "lane", perm=[(0, 1)])
"""

L1_GOOD = """\
from repro import substrate

def step(x):
    return substrate.ppermute(x, "lane", perm=[(0, 1)])
"""


def test_l1_fires_on_direct_jax_and_not_on_substrate():
    bad = lint_source(L1_BAD, "src/repro/parallel/foo.py")
    assert _rules(bad) == ["L1"] and bad[0].line == 4
    assert "substrate" in bad[0].hint
    assert lint_source(L1_GOOD, "src/repro/parallel/foo.py") == []
    # the allow-list: the same spelling is legal inside substrate.py itself
    assert lint_source(L1_BAD, "src/repro/substrate.py") == []


def test_l1_catches_aliased_imports_and_halo_specs():
    src = ("from jax.experimental.shard_map import shard_map as smap\n"
           "out = smap(lambda x: x, mesh=None, in_specs=(), out_specs=())\n")
    assert _rules(lint_source(src, "src/repro/core/foo.py")) == ["L1"]
    halo = ("from jax.experimental import pallas as pl\n"
            "spec = pl.BlockSpec((8,), lambda i: (i,),\n"
            "                    indexing_mode=pl.Unblocked())\n")
    assert _rules(lint_source(halo, "src/repro/kernels/foo.py")) == ["L1"]


def test_l1_flags_direct_mesh_construction():
    """jax.make_mesh (Explicit axes by default) and a called Mesh(...) go
    through substrate.make_mesh; Mesh as a type annotation stays legal."""
    direct = "import jax\nmesh = jax.make_mesh((2, 4), ('a', 'b'))\n"
    called = ("from jax.sharding import Mesh\n"
              "mesh = Mesh(devs, ('data', 'model'))\n")
    for src in (direct, called):
        bad = lint_source(src, "src/repro/launch/foo.py")
        assert _rules(bad) == ["L1"] and bad[0].line == 2
        assert "substrate.make_mesh" in bad[0].hint
    typed = ("from jax.sharding import Mesh\n"
             "def f(mesh: Mesh) -> Mesh:\n    return mesh\n")
    assert lint_source(typed, "src/repro/launch/foo.py") == []
    assert lint_source(called, "src/repro/substrate.py") == []


# ---------------------------------------------------------------------------
# L2 — x64 flips + import-time env mutation in tests
# ---------------------------------------------------------------------------

L2_BAD = """\
import jax
jax.config.update("jax_enable_x64", True)
"""

L2_ENV_BAD = """\
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=64"
"""


def test_l2_fires_outside_x64_module_only():
    assert _rules(lint_source(L2_BAD, "src/repro/sim/foo.py")) == ["L2"]
    assert lint_source(L2_BAD, "src/repro/testing/x64.py") == []


def test_l2_env_mutation_in_test_modules():
    assert _rules(lint_source(L2_ENV_BAD, "tests/test_foo.py")) == ["L2"]
    # conftest is the sanctioned bootstrap
    assert lint_source(L2_ENV_BAD, "tests/conftest.py") == []
    # inside a function (not import time) is a runtime concern, not L2's
    fn = "import os\ndef setup():\n    os.environ[\"XLA_FLAGS\"] = \"x\"\n"
    assert lint_source(fn, "tests/test_foo.py") == []
    # and library code setting env at import time is L2-exempt (the rule
    # targets the test suite, where jax may already be initialised)
    assert lint_source(L2_ENV_BAD, "examples/foo.py") == []


# ---------------------------------------------------------------------------
# L3 — BENCH_*.json writes
# ---------------------------------------------------------------------------

L3_BAD = """\
import json

def save(results):
    with open("BENCH_sim.json", "w") as f:
        json.dump(results, f)
"""


def test_l3_fires_outside_benchmarks_run():
    assert _rules(lint_source(L3_BAD, "src/repro/launch/foo.py")) == ["L3"]
    assert lint_source(L3_BAD, "benchmarks/run.py") == []
    # reading the artifact is always fine
    ok = 'import json\nd = json.load(open("BENCH_sim.json"))\n'
    assert lint_source(ok, "tests/test_foo.py") == []


# ---------------------------------------------------------------------------
# L4 — wall-clock timing
# ---------------------------------------------------------------------------

L4_BAD = """\
import time

def bench(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0
"""


def test_l4_fires_outside_timing_module():
    bad = lint_source(L4_BAD, "benchmarks/foo.py")
    assert _rules(bad) == ["L4"] and [f.line for f in bad] == [4, 6]
    assert lint_source(L4_BAD, "src/repro/testing/timing.py") == []
    ok = ("from repro.testing.timing import now\n"
          "def bench(fn):\n    t0 = now()\n    fn()\n    return now() - t0\n")
    assert lint_source(ok, "benchmarks/foo.py") == []


def test_noqa_suppression_is_per_rule_and_per_line():
    src = ("import time\n"
           "t = time.time()  # boot stamp, not a measurement"
           "  # repro: noqa(L4)\n")
    assert lint_source(src, "src/repro/ft/foo.py") == []
    # a noqa for a different rule does not silence L4
    other = "import time\nt = time.time()  # repro: noqa(L1)\n"
    assert _rules(lint_source(other, "src/repro/ft/foo.py")) == ["L4"]


def test_l4_sanctioned_monotonic_facade():
    """Raw ``time.monotonic`` is still a finding, but the supervisor's
    sanctioned spelling — ``repro.testing.timing.monotonic()`` for
    liveness deadlines — passes under every aliasing."""
    raw = ("import time\n"
           "def watchdog(deadline):\n"
           "    return time.monotonic() > deadline\n")
    bad = lint_source(raw, "src/repro/ft/foo.py")
    assert _rules(bad) == ["L4"] and [f.line for f in bad] == [3]
    assert "timing.monotonic" in bad[0].hint     # hint names the facade

    direct = ("from repro.testing.timing import monotonic\n"
              "def watchdog(deadline):\n"
              "    return monotonic() > deadline\n")
    assert lint_source(direct, "src/repro/ft/foo.py") == []

    # the adversarial alias: the facade imported *as* ``time`` must not
    # fire, and a real ``time`` aliased to something else still must
    aliased = ("from repro.testing import timing as time\n"
               "def watchdog(deadline):\n"
               "    return time.monotonic() > deadline\n")
    assert lint_source(aliased, "src/repro/ft/foo.py") == []
    sneaky = ("import time as clock\n"
              "def watchdog(deadline):\n"
              "    return clock.monotonic() > deadline\n")
    assert _rules(lint_source(sneaky, "src/repro/ft/foo.py")) == ["L4"]


# ---------------------------------------------------------------------------
# S1 — collective pricing coverage
# ---------------------------------------------------------------------------

def _psum_jaxpr(mesh):
    def f(x):
        return substrate.shard_map(
            lambda v: substrate.psum(v, "cluster"), mesh=mesh,
            in_specs=P("cluster", "lane"), out_specs=P(None, "lane"))(x)
    return jax.make_jaxpr(f)(jnp.zeros((2, 4), jnp.float32))


def test_s1_fires_on_unpriced_axis_and_passes_on_declared():
    mesh = substrate.make_mesh((2, 4), ("cluster", "lane"))
    closed = _psum_jaxpr(mesh)
    # the topology only declares the lane level: a psum over "cluster"
    # would be priced by the flat fallback -> finding
    topo_bad = Topology.from_levels([("lane", 4, 2.0)])
    bad = check_collective_pricing(closed, topo_bad, "fixture:s1")
    assert _rules(bad) == ["S1"] and "cluster" in bad[0].message
    # declaring both levels resolves every replica group
    topo_good = Topology.from_levels([("cluster", 2, 4.0),
                                      ("lane", 4, 2.0)])
    assert check_collective_pricing(closed, topo_good, "fixture:s1") == []


def test_s1_fires_on_mesh_topology_size_mismatch():
    mesh = substrate.make_mesh((2, 4), ("cluster", "lane"))
    closed = _psum_jaxpr(mesh)
    topo = Topology.from_levels([("cluster", 4, 4.0), ("lane", 2, 2.0)])
    bad = check_collective_pricing(closed, topo, "fixture:s1")
    assert _rules(bad) == ["S1"] and "mismatch" in bad[0].message


# ---------------------------------------------------------------------------
# S2 — ring schedules + aliasing
# ---------------------------------------------------------------------------

def test_s2_permutation_checker():
    n = 8
    for shift in (1, 2, 4, 7):      # recursive doubling's gcd>1 shifts pass
        assert check_ring_permutation(_shift_perm(n, shift), n) == []
    assert any("partial ring" in p
               for p in check_ring_permutation([(0, 1)], n))
    # pairwise swap: bijective and full-ring, but shifts {1, 7} mix
    assert any("non-uniform" in p for p in check_ring_permutation(
        [(p, p ^ 1) for p in range(n)], n))
    assert any("zero shift" in p
               for p in check_ring_permutation(_shift_perm(n, 0), n))
    assert any("duplicate" in p for p in check_ring_permutation(
        [(0, 1), (0, 2)], n))


def test_s2_fires_on_partial_ring_ppermute_and_not_on_full_shift():
    mesh = substrate.make_mesh((8,), ("lane",))

    def traced(perm):
        def f(x):
            return substrate.shard_map(
                lambda v: substrate.ppermute(v, "lane", perm), mesh=mesh,
                in_specs=P("lane"), out_specs=P("lane"))(x)
        return jax.make_jaxpr(f)(jnp.zeros((8,), jnp.float32))

    bad = check_ppermute_schedules(traced([(0, 1)]), "fixture:s2")
    assert _rules(bad) == ["S2"] and "deadlock" in bad[0].message
    assert check_ppermute_schedules(traced(_shift_perm(8, 1)),
                                    "fixture:s2") == []


def _copy_call(x, donate):
    def k(x_ref, o_ref):
        o_ref[...] = x_ref[...]
    return pl.pallas_call(
        k, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        input_output_aliases={0: 0} if donate else {},
        interpret=True)(x)


def test_s2_aliasing_race_detector():
    x = jnp.zeros((8, 8), jnp.float32)
    # donated input read again after the call -> in-flight race
    bad = jax.make_jaxpr(lambda x: _copy_call(x, True) + x)(x)
    fnd = check_aliasing(bad, "fixture:s2")
    assert _rules(fnd) == ["S2"] and "race" in fnd[0].message
    # same double read without donation is fine...
    assert check_aliasing(
        jax.make_jaxpr(lambda x: _copy_call(x, False) + x)(x),
        "fixture:s2") == []
    # ...and so is donation with a single consumer
    assert check_aliasing(
        jax.make_jaxpr(lambda x: _copy_call(x, True))(x),
        "fixture:s2") == []


# ---------------------------------------------------------------------------
# S3 — Pallas divisibility + VRF budget
# ---------------------------------------------------------------------------

def _block_call(x, block, grid=(1,)):
    def k(x_ref, o_ref):
        o_ref[...] = x_ref[...]
    return pl.pallas_call(
        k, grid=grid,
        in_specs=[pl.BlockSpec(block, lambda i: (0, 0))],
        out_specs=pl.BlockSpec(block, lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(block, x.dtype),
        interpret=True)(x)


def test_s3_fires_on_ragged_blocks():
    p = araxl_params(8)
    x = jnp.zeros((64, 64), jnp.float32)
    closed = jax.make_jaxpr(lambda x: _block_call(x, (48, 64)))(x)
    bad = check_pallas_budget(closed, p, "fixture:s3")
    assert _rules(bad) == ["S3"]
    assert any("not divisible" in f.message for f in bad)
    assert check_pallas_budget(
        jax.make_jaxpr(lambda x: _block_call(x, (32, 64)))(x), p,
        "fixture:s3") == []


def test_s3_fires_on_vrf_budget_busts():
    p = araxl_params(8)                       # 64 Kibit/vreg -> 64 KiB group
    x = jnp.zeros((8, 8192), jnp.float32)     # 256 KiB block: 4x the group
    bad = check_pallas_budget(
        jax.make_jaxpr(lambda x: _block_call(x, (8, 8192)))(x), p,
        "fixture:s3")
    assert _rules(bad) == ["S3"]
    assert any("register group" in f.message for f in bad)
    assert any("VRF" in f.message for f in bad)
    # the repo's wide-row LM kernel runs on the TPU: S3 holds it to the
    # (8, 128) tiling rule, not to the register group its block busts
    from repro.kernels.rmsnorm import rmsnorm
    wide = jnp.zeros((64, 4096), jnp.float32)
    closed = jax.make_jaxpr(
        lambda x, g: rmsnorm(x, g, interpret=True))(wide, jnp.ones((4096,)))
    assert check_tpu_tiling(closed, "entry:rmsnorm") == []
    assert any("register group" in f.message
               for f in check_pallas_budget(closed, p, "entry:rmsnorm"))


@pytest.mark.parametrize("block,ok", [((8, 128), True), ((64, 64), True),
                                      ((4, 128), False), ((8, 96), False)])
def test_s3_tpu_tiling_rule(block, ok):
    """Blocks are multiples of (8, 128) or the whole array dim: (64, 64)
    equals the array, (4, 128) and (8, 96) are neither."""
    x = jnp.zeros((64, 64 if block[1] == 64 else 384), jnp.float32)

    def call(x):
        def k(x_ref, o_ref):
            o_ref[...] = x_ref[...]
        return pl.pallas_call(
            k, grid=(x.shape[0] // block[0], x.shape[1] // block[1]),
            in_specs=[pl.BlockSpec(block, lambda i, j: (i, j))],
            out_specs=pl.BlockSpec(block, lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x)

    found = check_tpu_tiling(jax.make_jaxpr(call)(x), "fixture:s3")
    assert (found == []) == ok, found
    assert all("TPU" in f.message for f in found)


# ---------------------------------------------------------------------------
# bench schema validator
# ---------------------------------------------------------------------------

def test_bench_validator_accepts_recorded_artifact():
    bench = json.loads((ROOT / "BENCH_sim.json").read_text())
    for name, value in bench.items():
        assert validate_section(name, value) == [], name


def test_bench_validator_rejects_mutations():
    bench = json.loads((ROOT / "BENCH_sim.json").read_text())
    broken = copy.deepcopy(bench["coll"])
    del broken["C2L4"]["reduce"]["xla"]
    assert any("missing" in p for p in validate_section("coll", broken))
    ov = copy.deepcopy(bench["fig6_overlap_64"])
    ov["softmax"]["overlap"] = ov["softmax"]["baseline"] - 0.5
    assert any("overlap" in p
               for p in validate_section("fig6_overlap_64", ov))
    assert validate_section("mystery_section", {}) != []


# ---------------------------------------------------------------------------
# BENCH_kernels.json autotune-record schema
# ---------------------------------------------------------------------------

def test_kernels_bench_validator_accepts_recorded_artifact():
    from repro.analysis.bench import load_kernels_bench, validate_kernels_bench
    doc = load_kernels_bench(ROOT)
    assert doc is not None, "BENCH_kernels.json missing — run " \
                            "`python -m benchmarks.run kernels`"
    assert validate_kernels_bench(doc) == []


def test_kernels_bench_validator_fires():
    from repro.analysis.bench import validate_kernels_bench
    doc = json.loads((ROOT / "BENCH_kernels.json").read_text())

    # wrong schema pin
    bad = {"schema": 99, "records": doc["records"]}
    assert any("schema" in p for p in validate_kernels_bench(bad))

    # winner must be the measured_rank-0 candidate's config
    broken = copy.deepcopy(doc)
    sig, rec = sorted(broken["records"].items())[0]
    rec["winner"] = {"bogus": 1}
    assert any("winner" in p for p in validate_kernels_bench(broken))

    # model ranks must form a permutation of 0..n-1
    broken = copy.deepcopy(doc)
    sig, rec = sorted(broken["records"].items())[0]
    rec["candidates"][0]["model_rank"] = 999
    assert any("permutation" in p for p in validate_kernels_bench(broken))

    # coverage floor: >=3 kernels x >=2 shapes each
    lone = {"schema": 1, "records": {sig: copy.deepcopy(doc["records"][sig])}}
    assert any("coverage" in p for p in validate_kernels_bench(lone))


# ---------------------------------------------------------------------------
# BENCH_serve.json open-loop serving schema
# ---------------------------------------------------------------------------

def test_serve_bench_validator_accepts_recorded_artifact():
    from repro.analysis.bench import load_serve_bench, validate_serve_bench
    doc = load_serve_bench(ROOT)
    assert doc is not None, "BENCH_serve.json missing — run " \
                            "`python -m benchmarks.run serve`"
    assert validate_serve_bench(doc) == []


def test_serve_bench_validator_fires():
    from repro.analysis.bench import validate_serve_bench
    doc = json.loads((ROOT / "BENCH_serve.json").read_text())

    # wrong schema pin
    bad = {"schema": 99, "open_loop": doc["open_loop"]}
    assert any("schema" in p for p in validate_serve_bench(bad))

    # all three ablation arms are mandatory
    broken = copy.deepcopy(doc)
    del broken["open_loop"]["paged"]
    assert any("missing" in p for p in validate_serve_bench(broken))

    # percentiles must be ordered
    broken = copy.deepcopy(doc)
    rec = broken["open_loop"]["dense"]
    rec["ttft_p99_ms"] = rec["ttft_p50_ms"] - 1.0
    assert any("p99" in p for p in validate_serve_bench(broken))

    # occupancy is a fraction of slots
    broken = copy.deepcopy(doc)
    broken["open_loop"]["paged"]["occupancy"] = 1.5
    assert any("occupancy" in p for p in validate_serve_bench(broken))

    # resident KV can never exceed the declared capacity
    broken = copy.deepcopy(doc)
    rec = broken["open_loop"]["paged"]
    rec["kv_bytes_resident_peak"] = rec["kv_bytes_capacity"] + 1
    assert any("capacity" in p for p in validate_serve_bench(broken))

    # a paged arm must declare its block size
    broken = copy.deepcopy(doc)
    broken["open_loop"]["paged_chunked"]["config"]["block_tokens"] = 0
    assert any("block_tokens" in p for p in validate_serve_bench(broken))


# ---------------------------------------------------------------------------
# catalogue + repo-wide clean run
# ---------------------------------------------------------------------------

def test_rule_catalogue_and_finding_formatting():
    assert set(RULES) == {"L1", "L2", "L3", "L4", "S1", "S2", "S3"}
    f = Finding("L4", "src/x.py", 7, "boom", "use now()")
    assert str(f) == "src/x.py:7: L4: boom  [fix: use now()]"
    assert str(Finding("S1", "entry:e", 0, "m")) == "entry:e: S1: m"


def test_repo_analyzes_clean():
    """The acceptance gate: both fronts over this checkout, zero findings
    (same invocation scripts/ci.sh runs)."""
    out = run_check("repro.analysis", devices=8)
    assert "repro.analysis: clean" in out
