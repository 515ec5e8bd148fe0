"""Front 1 — the AST lint (rules L1-L4).

Pure stdlib ``ast``: no jax import, so the lint runs in any environment
(including ones with no fake devices).  Names are resolved through the
module's import aliases — ``from jax import lax as L; L.ppermute`` and
``from jax.lax import ppermute`` both resolve to ``jax.lax.ppermute`` —
so the rules fire on what the code *means*, not on how it spells it.

Suppression: a trailing ``# repro: noqa(L1)`` (or ``noqa(L1,L4)``) on the
offending line drops those rules for that line only.
"""
from __future__ import annotations

import ast
import pathlib
import re

from repro.analysis import Finding

#: directories swept by :func:`lint_repo`, relative to the repo root
LINT_DIRS = ("src", "tests", "benchmarks", "examples")

# --- per-rule allow-lists (repo-relative posix paths) ----------------------
L1_ALLOWED = ("src/repro/substrate.py",)
L2_ALLOWED = ("src/repro/testing/x64.py",)
L2_ENV_ALLOWED = ("tests/conftest.py",)
L3_ALLOWED = ("benchmarks/run.py",)
L4_ALLOWED = ("src/repro/testing/timing.py",)

#: L1 — jax SPMD surface that must route through the substrate.
#: Matched by exact resolved name or dotted prefix (so the module spelling
#: ``jax.experimental.shard_map`` catches ``....shard_map.shard_map`` too).
L1_BANNED = {
    "jax.shard_map": "substrate.shard_map",
    "jax.experimental.shard_map": "substrate.shard_map",
    "jax.lax.ppermute": "substrate.ppermute",
    "jax.lax.axis_index": "substrate.axis_index",
    "jax.lax.axis_size": "substrate.axis_size",
    "jax.experimental.pallas.Element": "substrate.halo_block_spec",
    "jax.experimental.pallas.Unblocked": "substrate.halo_block_spec",
    "jax.make_mesh": "substrate.make_mesh",
}

#: L1 — constructors that are legal as types (annotations, isinstance) but
#: must not be *called* outside the substrate: a direct ``Mesh(...)`` gets
#: the jax default axis types instead of the repo's Auto axes.
L1_BANNED_CALLS = {
    "jax.sharding.Mesh": "substrate.make_mesh",
}

#: L4 — wall-clock sources (time.sleep stays legal: it waits, not measures)
L4_BANNED = {
    "time.time", "time.time_ns", "time.perf_counter", "time.perf_counter_ns",
    "time.monotonic", "time.monotonic_ns", "time.process_time",
    "time.process_time_ns", "timeit.default_timer",
}

#: L4 — the sanctioned façades.  Calls that *resolve into*
#: ``repro.testing.timing`` are the point of the rule, never findings —
#: this guards the carve-out against spellings where the alias table makes
#: the façade look raw (``from repro.testing import timing as time;
#: time.monotonic()`` resolves to ``repro.testing.timing.monotonic``).
L4_SANCTIONED_PREFIX = "repro.testing.timing"

#: L2 env sub-rule — keys a test module must not touch at import time
L2_ENV_KEYS = ("XLA_FLAGS", "JAX_PLATFORMS")

_NOQA = re.compile(r"#\s*repro:\s*noqa\(\s*([A-Z0-9,\s]+?)\s*\)")


def _noqa_map(source: str) -> dict[int, frozenset[str]]:
    out = {}
    for i, text in enumerate(source.splitlines(), start=1):
        m = _NOQA.search(text)
        if m:
            out[i] = frozenset(r.strip() for r in m.group(1).split(",")
                               if r.strip())
    return out


def _package_of(relpath: str) -> str:
    """Dotted package of a repo-relative module path (for relative imports):
    ``src/repro/core/ring.py`` -> ``repro.core``."""
    parts = pathlib.PurePosixPath(relpath).parts
    if parts and parts[0] == "src":
        parts = parts[1:]
    return ".".join(parts[:-1])


def _collect_aliases(tree: ast.AST, relpath: str) -> dict[str, str]:
    """Local name -> fully dotted import path, module-wide."""
    pkg = _package_of(relpath)
    aliases: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    aliases[a.asname] = a.name
                else:
                    head = a.name.split(".")[0]
                    aliases[head] = head
        elif isinstance(node, ast.ImportFrom):
            module = node.module or ""
            if node.level:                     # relative import
                base = pkg.split(".") if pkg else []
                base = base[: max(0, len(base) - (node.level - 1))]
                module = ".".join(base + ([module] if module else []))
            for a in node.names:
                if a.name == "*":
                    continue
                full = f"{module}.{a.name}" if module else a.name
                aliases[a.asname or a.name] = full
    return aliases


def _resolve(node: ast.AST, aliases: dict[str, str]) -> str | None:
    """Dotted name of an attribute chain rooted at an imported name."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    base = aliases.get(node.id)
    if base is None:
        return None
    parts.append(base)
    return ".".join(reversed(parts))


def _matches(resolved: str, banned: str) -> bool:
    return resolved == banned or resolved.startswith(banned + ".")


def _str_consts(node: ast.AST):
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
            yield sub.value


def _is_environ(node: ast.AST, aliases: dict[str, str]) -> bool:
    resolved = _resolve(node, aliases)
    return resolved in ("os.environ", "os.environb")


class _Linter:
    def __init__(self, tree: ast.AST, relpath: str, aliases: dict[str, str]):
        self.relpath = relpath
        self.aliases = aliases
        self.findings: list[Finding] = []
        self.in_tests = relpath.startswith("tests/")
        self._walk(tree, depth=0)

    def _add(self, rule: str, node: ast.AST, message: str, hint: str):
        line = getattr(node, "lineno", 0)
        for f in self.findings:           # one finding per (rule, line)
            if f.rule == rule and f.line == line:
                return
        self.findings.append(Finding(rule, self.relpath, line, message, hint))

    # -- rules --------------------------------------------------------------

    def _check_l1_name(self, node: ast.AST):
        if self.relpath in L1_ALLOWED:
            return
        resolved = _resolve(node, self.aliases)
        if resolved is None:
            return
        for banned, repl in L1_BANNED.items():
            if _matches(resolved, banned):
                self._add("L1", node,
                          f"direct use of `{resolved}`",
                          f"route through repro.{repl} (the one point "
                          f"of contact with JAX's SPMD surface)")
                return

    def _check_l1_call(self, node: ast.Call):
        if self.relpath in L1_ALLOWED:
            return
        resolved = _resolve(node.func, self.aliases)
        if resolved in L1_BANNED_CALLS:
            self._add("L1", node, f"direct `{resolved}(...)` construction",
                      f"route through repro.{L1_BANNED_CALLS[resolved]} "
                      f"(Auto mesh axes)")

    def _check_l1_import(self, node: ast.Import | ast.ImportFrom):
        if self.relpath in L1_ALLOWED:
            return
        if isinstance(node, ast.Import):
            fulls = [a.name for a in node.names]
        else:
            if node.level:
                return                          # relative: repo-internal
            mod = node.module or ""
            fulls = [f"{mod}.{a.name}" if mod else a.name
                     for a in node.names]
            fulls.append(mod)
        for full in fulls:
            for banned, repl in L1_BANNED.items():
                if full and _matches(full, banned):
                    self._add("L1", node,
                              f"imports `{full}` directly",
                              f"route through repro.{repl}")
                    return

    def _check_l2_call(self, node: ast.Call):
        if self.relpath in L2_ALLOWED:
            return
        func = node.func
        if not (isinstance(func, ast.Attribute) and func.attr == "update"):
            return
        owner = _resolve(func.value, self.aliases)
        if owner is None or not (owner == "jax.config"
                                 or owner.endswith(".config")):
            return
        if node.args and isinstance(node.args[0], ast.Constant) \
                and node.args[0].value == "jax_enable_x64":
            self._add("L2", node,
                      "x64 flag flip outside repro.testing.x64 (the PR 5 "
                      "flag-leak class)",
                      "use repro.testing.x64.x64_mode(...) as a context "
                      "manager")

    def _check_l2_env(self, node: ast.stmt, depth: int):
        """Import-time XLA_FLAGS/JAX_PLATFORMS mutation in a test module."""
        if not self.in_tests or depth > 0 or self.relpath in L2_ENV_ALLOWED:
            return
        mutating: ast.AST | None = None
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                if isinstance(t, ast.Subscript) \
                        and _is_environ(t.value, self.aliases):
                    mutating = node
        elif isinstance(node, ast.Delete):
            for t in node.targets:
                if isinstance(t, ast.Subscript) \
                        and _is_environ(t.value, self.aliases):
                    mutating = node
        elif isinstance(node, ast.Expr) and isinstance(node.value, ast.Call):
            call = node.value
            if isinstance(call.func, ast.Attribute) \
                    and call.func.attr in ("setdefault", "update", "pop") \
                    and _is_environ(call.func.value, self.aliases):
                mutating = call
        if mutating is None:
            return
        keys = [k for k in L2_ENV_KEYS
                if any(k in s for s in _str_consts(mutating))]
        if keys:
            self._add("L2", node,
                      f"test module mutates {'/'.join(keys)} at import "
                      f"time (device-count races with the shared "
                      f"conftest bootstrap)",
                      "rely on tests/conftest.py (idempotent fake-device "
                      "env) or mutate a subprocess env copy")

    def _check_l3(self, node: ast.Call):
        if self.relpath in L3_ALLOWED:
            return
        func = node.func
        is_write = (isinstance(func, ast.Attribute)
                    and func.attr in ("write_text", "write_bytes"))
        resolved = _resolve(func, self.aliases)
        if resolved == "json.dump":
            is_write = True
        if isinstance(func, ast.Name) and func.id == "open" \
                and func.id not in self.aliases:
            mode = None
            if len(node.args) > 1 and isinstance(node.args[1], ast.Constant):
                mode = node.args[1].value
            for kw in node.keywords:
                if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
                    mode = kw.value.value
            if isinstance(mode, str) and any(c in mode for c in "wa+"):
                is_write = True
        if not is_write:
            return
        if any("BENCH_" in s for s in _str_consts(node)):
            self._add("L3", node,
                      "ad-hoc BENCH_*.json write bypasses the pinned-schema "
                      "merge helpers",
                      "record through benchmarks/run.py (BENCH dict + "
                      "_deep_merge) so repro.analysis.bench can validate it")

    def _check_l4(self, node: ast.Call):
        if self.relpath in L4_ALLOWED:
            return
        resolved = _resolve(node.func, self.aliases)
        if resolved is None or _matches(resolved, L4_SANCTIONED_PREFIX):
            return
        if resolved in L4_BANNED:
            self._add("L4", node,
                      f"wall-clock timing via `{resolved}` outside "
                      f"repro.testing.timing",
                      "use repro.testing.timing.now() for intervals, "
                      "timing.monotonic() for liveness deadlines, or "
                      "median_time_us() for measurements")

    # -- walk ---------------------------------------------------------------

    def _walk(self, node: ast.AST, depth: int):
        for child in ast.iter_child_nodes(node):
            child_depth = depth
            if isinstance(child, (ast.Import, ast.ImportFrom)):
                self._check_l1_import(child)
            elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.Lambda)):
                child_depth = depth + 1
            elif isinstance(child, ast.Call):
                self._check_l1_call(child)
                self._check_l2_call(child)
                self._check_l3(child)
                self._check_l4(child)
            elif isinstance(child, (ast.Attribute, ast.Name)) \
                    and isinstance(getattr(child, "ctx", None), ast.Load):
                self._check_l1_name(child)
            if isinstance(child, ast.stmt):
                self._check_l2_env(child, depth)
            self._walk(child, child_depth)


def lint_source(source: str, relpath: str) -> list[Finding]:
    """Lint one module given its repo-relative posix path (the path decides
    which allow-list applies)."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding("L1", relpath, e.lineno or 0,
                        f"syntax error: {e.msg}", "fix the parse error")]
    aliases = _collect_aliases(tree, relpath)
    findings = _Linter(tree, relpath, aliases).findings
    noqa = _noqa_map(source)
    kept = [f for f in findings if f.rule not in noqa.get(f.line, ())]
    return sorted(kept, key=lambda f: (f.path, f.line, f.rule))


def lint_file(path: pathlib.Path, root: pathlib.Path) -> list[Finding]:
    relpath = path.resolve().relative_to(root.resolve()).as_posix()
    return lint_source(path.read_text(), relpath)


def lint_repo(root: pathlib.Path,
              dirs: tuple[str, ...] = LINT_DIRS) -> list[Finding]:
    """Sweep every ``*.py`` under the linted directories."""
    findings: list[Finding] = []
    for d in dirs:
        base = root / d
        if not base.is_dir():
            continue
        for path in sorted(base.rglob("*.py")):
            if "__pycache__" in path.parts:
                continue
            findings += lint_file(path, root)
    return findings
