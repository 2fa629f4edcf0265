"""Family 4 — kernel oracle contract (ECO401-ECO405).

Every Pallas kernel package ``kernels/<name>/`` ships as: ``__init__.py``
(importable without path tricks), ``ops.py`` (the dispatching public
surface), ``ref.py`` (the jnp-only oracle the parity tests compare
against), and at least one test under ``tests/`` that references it.  A
kernel without an oracle or without a parity test is unverifiable; an
oracle that imports pallas can no longer disagree with the kernel.
ECO405 (per-file) keeps ops.py honest the other way: a shape-guarded
branch that silently rewrites dispatch to the oracle hides exactly the
frames the kernel exists to accelerate — fall-backs must carry a
``# repro-lint`` justification or be deleted.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, List, Sequence, Tuple

from repro.analysis.engine import SourceFile, Violation, match_path
from repro.analysis.registry import Rule, register


def kernel_packages(sources: Sequence[SourceFile]
                    ) -> Dict[Tuple[str, str], Dict[str, SourceFile]]:
    """``(pkg_dir, name) -> {filename: SourceFile}`` for the immediate
    children of each ``kernels/<name>/`` directory in the collected set."""
    pkgs: Dict[Tuple[str, str], Dict[str, SourceFile]] = {}
    for src in sources:
        parts = src.path.split("/")
        if "kernels" not in parts:
            continue
        i = parts.index("kernels")
        if len(parts) != i + 3:  # exactly kernels/<name>/<file>.py
            continue
        name = parts[i + 1]
        pkg_dir = "/".join(parts[:i + 2])
        pkgs.setdefault((pkg_dir, name), {})[parts[-1]] = src
    return pkgs


def test_sources(sources: Sequence[SourceFile]) -> List[SourceFile]:
    return [s for s in sources if match_path(s.path, ("*/tests/*.py",))]


class _KernelRule(Rule):
    project_level = True


@register
class KernelMissingInit(_KernelRule):
    id = "ECO401"
    name = "kernel-missing-init"
    description = ("kernels/<name>/ without __init__.py — the package must "
                   "import as repro.kernels.<name> without path tricks")

    def check_project(self, sources):
        for (pkg_dir, name), files in sorted(kernel_packages(sources)
                                             .items()):
            if "__init__.py" not in files:
                yield Violation(self.id, f"{pkg_dir}/__init__.py", 1, 0,
                                f"kernel package {name!r} has no "
                                "__init__.py — add one re-exporting the "
                                "ops entry points")


@register
class KernelMissingContract(_KernelRule):
    id = "ECO402"
    name = "kernel-missing-contract"
    description = ("kernels/<name>/ must expose ops.py (public dispatch "
                   "surface) and ref.py (jnp oracle)")

    def check_project(self, sources):
        for (pkg_dir, name), files in sorted(kernel_packages(sources)
                                             .items()):
            for required in ("ops.py", "ref.py"):
                if required not in files:
                    role = ("public dispatch surface"
                            if required == "ops.py" else "jnp oracle")
                    yield Violation(self.id, f"{pkg_dir}/{required}", 1, 0,
                                    f"kernel {name!r} is missing "
                                    f"{required} (its {role})")


@register
class KernelUntested(_KernelRule):
    id = "ECO403"
    name = "kernel-untested"
    description = ("kernel not referenced by any test under tests/ — every "
                   "kernel needs a parity test against its ref.py oracle")

    def check_project(self, sources):
        tests = test_sources(sources)
        if not tests:
            return  # tests/ not in the linted set: nothing to assert
        for (pkg_dir, name), files in sorted(kernel_packages(sources)
                                             .items()):
            pat = re.compile(r"kernels[./]" + re.escape(name) + r"\b")
            if any(pat.search(t.text) for t in tests):
                continue
            anchor = files.get("ops.py") or next(iter(sorted(
                files.items())))[1]
            yield Violation(self.id, anchor.path, 1, 0,
                            f"kernel {name!r} is not referenced by any "
                            "file under tests/ — add a parity test "
                            f"importing repro.kernels.{name}")


_LIMIT_NAME = re.compile(r"^[A-Z][A-Z0-9_]*$")
_LIMIT_HINT = re.compile(r"MAX|WIDTH|HEIGHT|LIMIT|CAP|SIZE")


def _shape_guard(test: ast.expr) -> bool:
    """Does this ``if`` test consult the input's geometry — a
    ``.shape``/``.size``/``.ndim`` attribute or an ALL-CAPS limit
    constant (``MAX_WIDTH``-style)?"""
    for node in ast.walk(test):
        if isinstance(node, ast.Attribute) and node.attr in ("shape",
                                                             "size", "ndim"):
            return True
        if isinstance(node, ast.Name) and _LIMIT_NAME.match(node.id) \
                and _LIMIT_HINT.search(node.id):
            return True
    return False


def _falls_back_to_oracle(body: List[ast.stmt]) -> bool:
    """Does the guarded branch reroute dispatch to the oracle — assign an
    ``impl``-style variable a string constant, or return/call ``ref.*``?"""
    for stmt in body:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Assign):
                targets = [t.id for t in node.targets
                           if isinstance(t, ast.Name)]
                consts = [n.value for n in ast.walk(node.value)
                          if isinstance(n, ast.Constant)
                          and isinstance(n.value, str)]
                if any("impl" in t for t in targets) and consts:
                    return True
            if isinstance(node, ast.Attribute) \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id == "ref":
                return True
    return False


@register
class KernelSilentFallback(Rule):
    id = "ECO405"
    name = "kernel-silent-fallback"
    description = ("ops.py silently reroutes dispatch to the oracle behind "
                   "a shape guard — the kernel quietly stops serving "
                   "exactly the inputs it exists for; delete the guard or "
                   "justify it with a # repro-lint disable")
    include = ("*/kernels/*/ops.py",)

    def check(self, src: SourceFile):
        for node in src.nodes:
            if not isinstance(node, ast.If) or not _shape_guard(node.test):
                continue
            if _falls_back_to_oracle(node.body):
                yield self.hit(
                    node, src.path,
                    "shape-guarded branch silently falls back to the "
                    "oracle — every input the kernel claims to serve must "
                    "reach it, or the fallback needs a # repro-lint "
                    "justification naming why")


@register
class KernelImpureOracle(_KernelRule):
    id = "ECO404"
    name = "kernel-impure-oracle"
    description = ("ref.py imports pallas — an oracle that shares the "
                   "kernel's machinery can no longer disagree with it; "
                   "oracles are jnp-only")

    def check_project(self, sources):
        for (pkg_dir, name), files in sorted(kernel_packages(sources)
                                             .items()):
            ref = files.get("ref.py")
            if ref is None:
                continue
            for node in ast.walk(ref.tree):
                if isinstance(node, ast.Import):
                    for alias in node.names:
                        if "pallas" in alias.name:
                            yield Violation(
                                self.id, ref.path, node.lineno,
                                node.col_offset,
                                f"oracle for kernel {name!r} imports "
                                f"{alias.name} — ref.py must stay jnp-only")
                elif isinstance(node, ast.ImportFrom):
                    module = node.module or ""
                    hits = [a.name for a in node.names
                            if "pallas" in a.name]
                    if "pallas" in module or hits:
                        what = module or ", ".join(hits)
                        yield Violation(
                            self.id, ref.path, node.lineno,
                            node.col_offset,
                            f"oracle for kernel {name!r} imports "
                            f"{what} — ref.py must stay jnp-only")
