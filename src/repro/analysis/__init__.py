"""repro.analysis — the repo's load-bearing conventions, machine-checked.

AraXL's scaling argument only holds because *every* wire crossing is
accounted for by the hierarchical interconnect; the software analogue in
this repo is that every SPMD-surface jax call routes through
:mod:`repro.substrate` and every collective prices onto the declared
:class:`repro.topology.Topology`.  This package turns those prose rules
(ROADMAP) into a static-analysis pass with two fronts:

* **AST lint** (:mod:`repro.analysis.lint`) — stdlib-``ast``, no jax
  import, runs anywhere:

  =====  ==================================================================
  L1     substrate-only: no direct ``shard_map`` / ``lax.ppermute`` /
         ``axis_index`` / ``axis_size`` / halo-``BlockSpec`` spellings,
         and no ``jax.make_mesh`` / ``Mesh(...)`` construction, outside
         ``src/repro/substrate.py``
  L2     import hygiene: no x64 flag flips outside
         ``src/repro/testing/x64.py``; no import-time ``XLA_FLAGS`` /
         ``JAX_PLATFORMS`` mutation in test modules outside
         ``tests/conftest.py``
  L3     no ad-hoc ``BENCH_*.json`` writes outside the pinned-schema merge
         helpers in ``benchmarks/run.py``
  L4     no wall-clock timing outside ``repro.testing.timing``
  =====  ==================================================================

* **semantic analyzer** (:mod:`repro.analysis.jaxpr_check` +
  :mod:`repro.analysis.schedule_check`) — traces the public entry points
  (ring collectives, ring attention, MoE ep_a2a, Pallas kernels) to closed
  jaxprs on 8 fake CPU devices:

  =====  ==================================================================
  S1     pricing coverage: every collective's replica group must resolve
         through ``roofline.analysis.group_level_extents`` for the
         declared Topology without hitting the conservative flat fallback
  S2     ring-schedule safety: every ``ppermute`` is a full-ring uniform
         circular shift (deadlock check) and no donated / aliased Pallas
         buffer is read while in flight
  S3     Pallas blocks: grid/BlockSpec divisibility; the static VRF
         budget against the RVV 64 Kibit/vreg ceiling of ``AraXLParams``
         for the paper's kernels, the TPU (8, 128) block tiling for the
         language-model kernels
  =====  ==================================================================

Suppression: append ``# repro: noqa(RULE)`` (comma-separated rules) to the
offending line, with a comment saying why the rule is inapplicable there.

Run ``python -m repro.analysis`` (exits non-zero on any finding; gated in
``scripts/ci.sh``) and ``python -m repro.analysis.bench`` for the
``BENCH_sim.json`` pinned-schema validation.
"""
from __future__ import annotations

import dataclasses
import pathlib

#: rule id -> one-line description (the catalogue docs/ANALYSIS.md renders)
RULES = {
    "L1": "substrate-only: jax SPMD APIs and mesh construction route "
          "through repro.substrate",
    "L2": "import hygiene: x64 flips only in repro.testing.x64; no "
          "import-time XLA_FLAGS/JAX_PLATFORMS mutation in test modules "
          "outside tests/conftest.py",
    "L3": "BENCH_*.json writes only through benchmarks/run.py merge helpers",
    "L4": "wall-clock timing only through repro.testing.timing",
    "S1": "collective pricing coverage: replica groups resolve on the "
          "declared Topology without the flat fallback",
    "S2": "ring-schedule safety: full-ring uniform-shift ppermutes; no "
          "aliased in-flight buffer reads",
    "S3": "Pallas blocks: divisibility; 64 Kibit/vreg ceiling for the "
          "paper's kernels, TPU (8, 128) tiling for the LM kernels",
}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One violation: rule id, location, what, and how to fix it."""
    rule: str                    # "L1".."L4" / "S1".."S3"
    path: str                    # repo-relative file, or entry-point label
    line: int                    # 1-based source line; 0 for traced entries
    message: str
    hint: str = ""

    def __str__(self) -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        s = f"{loc}: {self.rule}: {self.message}"
        if self.hint:
            s += f"  [fix: {self.hint}]"
        return s


def repo_root() -> pathlib.Path:
    """The repo root this installation lives in (src/repro/analysis/..)."""
    return pathlib.Path(__file__).resolve().parents[3]


def run_repo_analysis(root: pathlib.Path | None = None,
                      semantic: bool = True) -> list[Finding]:
    """Both fronts over the repo.  The semantic front imports jax and needs
    >= 8 (fake) devices; set ``semantic=False`` for the lint-only pass."""
    from repro.analysis import lint
    root = pathlib.Path(root) if root is not None else repo_root()
    findings = lint.lint_repo(root)
    if semantic:
        from repro.analysis import jaxpr_check
        findings += jaxpr_check.semantic_findings()
    return findings
