"""Family 5 — one call site per environment API (ECO501/502/503).

Meshes are built in one place, ``launch.mesh.make_mesh`` (the only user of
``jax.make_mesh`` and ``jax.sharding.AxisType``), and hypothesis is imported
in one place, ``tests/_propcheck.py``.  Both carry inline justified
suppressions; everything else routes through them, so a change to either
API is a one-file change.
"""
from __future__ import annotations

import ast

from repro.analysis.registry import Rule, register
from repro.analysis.rules.common import call_name, dotted_name


@register
class AxisTypePin(Rule):
    id = "ECO501"
    name = "axistype-pin"
    description = ("direct jax.sharding.AxisType access — "
                   "launch.mesh.make_mesh is the one call site that picks "
                   "mesh axis types")

    def check(self, src):
        for node in src.nodes:
            if (isinstance(node, ast.Attribute)
                    and dotted_name(node) == "jax.sharding.AxisType"):
                yield self.hit(node, src.path,
                               "jax.sharding.AxisType outside its one call "
                               "site — go through "
                               "repro.launch.mesh.make_mesh")
            elif (isinstance(node, ast.ImportFrom)
                  and node.module == "jax.sharding"
                  and any(a.name == "AxisType" for a in node.names)):
                yield self.hit(node, src.path,
                               "importing AxisType outside its one call "
                               "site — go through "
                               "repro.launch.mesh.make_mesh")


@register
class BareMakeMesh(Rule):
    id = "ECO502"
    name = "bare-make-mesh"
    description = ("bare jax.make_mesh call — repro.launch.mesh.make_mesh "
                   "is the one call site that builds meshes")

    def check(self, src):
        for node in src.nodes:
            if (isinstance(node, ast.Call)
                    and call_name(node) == "jax.make_mesh"):
                yield self.hit(node, src.path,
                               "bare jax.make_mesh(...) — call "
                               "repro.launch.mesh.make_mesh, the one call "
                               "site")
            elif (isinstance(node, ast.ImportFrom)
                  and node.module == "jax"
                  and any(a.name == "make_mesh" for a in node.names)):
                yield self.hit(node, src.path,
                               "importing make_mesh from jax bypasses the "
                               "one call site — use "
                               "repro.launch.mesh.make_mesh")


@register
class HypothesisImport(Rule):
    id = "ECO503"
    name = "hypothesis-import"
    description = ("direct hypothesis import — tests/_propcheck.py is "
                   "the one import site")

    def check(self, src):
        for node in src.nodes:
            if isinstance(node, ast.Import):
                for alias in node.names:
                    root = alias.name.split(".", 1)[0]
                    if root == "hypothesis":
                        yield self.hit(node, src.path,
                                       f"import {alias.name} outside the one "
                                       "import site — import from "
                                       "tests/_propcheck.py instead")
            elif (isinstance(node, ast.ImportFrom)
                  and (node.module or "").split(".", 1)[0] == "hypothesis"):
                yield self.hit(node, src.path,
                               f"from {node.module} import ... outside "
                               "the one import site — import from "
                               "tests/_propcheck.py instead")
