"""Family 2 — hot-path discipline (ECO201/202/203).

ECORE's wins live or die on routing staying O(1) per frame: the closed
loop is ONE jitted lax.scan, routing is a masked argmin, and dispatch is
the single DispatchQueue plane.  A Python per-frame loop, a ProfileTable
facade call, or a forked serving loop re-introduces exactly the overhead
PRs 3-5 removed.
"""
from __future__ import annotations

import ast

from repro.analysis.registry import Rule, register
from repro.analysis.rules.common import MUTATORS, dotted_name

_HOT_MODULES = ("*/repro/core/closed_loop.py", "*/repro/core/router.py",
                "*/repro/core/profiles.py")


@register
class HotPathLoop(Rule):
    id = "ECO201"
    name = "hot-python-loop"
    description = ("Python for/while in a hot routing function — per-frame "
                   "work belongs inside the jitted scan/argmin, not the "
                   "interpreter")
    include = _HOT_MODULES

    hot = ()

    def configure(self, options):
        self.hot = tuple(options.get("hot-functions") or ())

    def check(self, src):
        for node in src.nodes:
            if not (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and node.name in self.hot):
                continue
            for sub in ast.walk(node):
                if not isinstance(sub, (ast.For, ast.While)):
                    continue
                if (isinstance(sub, ast.For)
                        and isinstance(sub.iter, (ast.Tuple, ast.List))):
                    continue  # literal unroll: length fixed at write time
                yield self.hit(sub, src.path,
                               "Python loop in hot function "
                               f"{node.name!r} runs once per frame — move "
                               "the work into the jitted scan or hoist it "
                               "out of the streaming path")


@register
class HotProfileMutation(Rule):
    id = "ECO202"
    name = "hot-profile-mutation"
    description = ("ProfileTable facade traffic in a hot module — the scan "
                   "folds observations into the ProfileState pytree; the "
                   "scalar mirrors (.observe/.observe_pair/.load_state) "
                   "are for the eager edges only")
    include = ("*/repro/core/closed_loop.py", "*/repro/core/router.py")

    _CALLS = frozenset({"observe", "observe_pair", "load_state"})

    def check(self, src):
        for node in src.nodes:
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                if node.func.attr in self._CALLS:
                    yield self.hit(node, src.path,
                                   f".{node.func.attr}(...) drives the "
                                   "mutable ProfileTable facade from a hot "
                                   "module — fold through observe_state/"
                                   "ProfileState inside the scan")
                elif (node.func.attr in MUTATORS
                      and (dotted_name(node.func.value) or ""
                           ).endswith("entries")):
                    yield self.hit(node, src.path,
                                   f".entries.{node.func.attr}(...) "
                                   "mutates profile rows in a hot module")
            elif isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if self._entries_target(tgt):
                        yield self.hit(node, src.path,
                                       "assignment into ProfileTable"
                                       ".entries in a hot module — profile "
                                       "state is the scanned pytree here")

    @staticmethod
    def _entries_target(tgt) -> bool:
        if isinstance(tgt, ast.Attribute) and tgt.attr == "entries":
            return True
        return (isinstance(tgt, ast.Subscript)
                and isinstance(tgt.value, ast.Attribute)
                and tgt.value.attr == "entries")


@register
class ForkedServingLoop(Rule):
    id = "ECO203"
    name = "forked-serving-loop"
    description = ("direct .serve_batch(...) outside the dispatch plane — "
                   "submit through EcoreService so batching, observation, "
                   "and accounting stay on one path")
    include = ("*/repro/*.py", "*/benchmarks/*.py", "*/examples/*.py")
    # tests exercise backends directly by design
    exclude = ("*/tests/*",)

    def configure(self, options):
        plane = tuple(options.get("dispatch-plane") or ())
        self.exclude = tuple(ForkedServingLoop.exclude) + plane

    def check(self, src):
        for node in src.nodes:
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "serve_batch"):
                yield self.hit(node, src.path,
                               "direct serve_batch(...) call forks a "
                               "serving loop — route it through the "
                               "EcoreService dispatch plane")
