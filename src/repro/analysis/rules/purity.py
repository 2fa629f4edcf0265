"""Family 1 — scan/jit purity (ECO101/102/103/110).

jax traces a jit scope once; Python side effects inside it either force a
device->host sync per call (stalling the stream the closed loop is trying
to keep cheap) or run at trace time only and silently bake stale state
into the compiled program.  The scanned closed loop (PR 5) depends on
``observe_state``/``decide_state`` staying pure, so those names are jit
scopes even without a decorator (``pure-functions`` in pyproject).
"""
from __future__ import annotations

import ast

from repro.analysis.registry import Rule, register
from repro.analysis.rules.common import (MUTATORS, NP_NAMES, REDUCERS,
                                         annotate_parents, call_name,
                                         dotted_name, in_loop,
                                         jit_entry_functions)

_HOST_CASTS = frozenset({"float", "int", "bool", "complex"})
_HOST_METHODS = frozenset({"item", "tolist"})
_IMPURE_ROOTS = ("random.", "time.", "os.")


class _JitScopeRule(Rule):
    """Base: run ``check_node`` over every node of every jit entry."""

    pure = ()

    def configure(self, options):
        self.pure = tuple(options.get("pure-functions") or ())

    def check(self, src):
        for entry in jit_entry_functions(src.tree, self.pure):
            for node in ast.walk(entry):
                yield from self.check_node(node, src, entry)

    def check_node(self, node, src, entry):
        return ()


@register
class HostSyncInJit(_JitScopeRule):
    id = "ECO101"
    name = "jit-host-sync"
    description = ("host synchronisation inside a jit/scan scope: "
                   "float()/int()/bool() on tracers, .item()/.tolist(), "
                   "or np.* calls materialise device values per trace")

    def check_node(self, node, src, entry):
        if not isinstance(node, ast.Call):
            return
        func = node.func
        if (isinstance(func, ast.Name) and func.id in _HOST_CASTS
                and node.args):
            yield self.hit(node, src.path,
                           f"{func.id}(...) in jit scope {entry.name!r} "
                           "forces a host sync on traced values")
        elif isinstance(func, ast.Attribute) and func.attr in _HOST_METHODS:
            yield self.hit(node, src.path,
                           f".{func.attr}() in jit scope {entry.name!r} "
                           "pulls the array to host")
        else:
            name = call_name(node) or ""
            if name.split(".", 1)[0] in NP_NAMES:
                yield self.hit(node, src.path,
                               f"{name}(...) in jit scope {entry.name!r} "
                               "is a host-side numpy call — use jnp")


@register
class ImpureCallInJit(_JitScopeRule):
    id = "ECO102"
    name = "jit-impure-call"
    description = ("print/random./time./os. inside a jit scope runs at "
                   "trace time only — the compiled program replays a "
                   "stale value (or nothing at all)")

    def check_node(self, node, src, entry):
        if not isinstance(node, ast.Call):
            return
        if isinstance(node.func, ast.Name) and node.func.id == "print":
            yield self.hit(node, src.path,
                           f"print(...) in jit scope {entry.name!r} fires "
                           "once at trace time — use jax.debug.print")
            return
        name = call_name(node) or ""
        if any(name.startswith(root) for root in _IMPURE_ROOTS):
            yield self.hit(node, src.path,
                           f"{name}(...) in jit scope {entry.name!r} is "
                           "trace-time-only impurity — thread randomness/"
                           "clocks in as arguments")


@register
class MutationInJit(_JitScopeRule):
    id = "ECO103"
    name = "jit-python-mutation"
    description = ("in-place Python mutation inside a jit scope (dict/list "
                   "writes, global/nonlocal rebinding) is invisible to the "
                   "trace — return new values or use .at[] updates")
    # pallas kernel bodies assign o_ref[...] by design: that is the
    # sanctioned mutation surface, so the kernel tree is out of scope
    exclude = ("*/repro/kernels/*",)

    def check_node(self, node, src, entry):
        if isinstance(node, (ast.Global, ast.Nonlocal)):
            kind = "global" if isinstance(node, ast.Global) else "nonlocal"
            yield self.hit(node, src.path,
                           f"{kind} rebinding inside jit scope "
                           f"{entry.name!r} leaks trace-time state")
        elif isinstance(node, ast.Assign):
            for tgt in node.targets:
                yield from self._target(tgt, node, src, entry)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            yield from self._target(node.target, node, src, entry)
        elif (isinstance(node, ast.Call)
              and isinstance(node.func, ast.Attribute)
              and node.func.attr in MUTATORS
              and not self._is_at_update(node.func.value)):
            yield self.hit(node, src.path,
                           f".{node.func.attr}(...) mutates a Python "
                           f"container inside jit scope {entry.name!r}")

    def _target(self, tgt, node, src, entry):
        if isinstance(tgt, (ast.Subscript, ast.Attribute)):
            yield self.hit(node, src.path,
                           "subscript/attribute assignment inside jit "
                           f"scope {entry.name!r} mutates in place — "
                           "build a new value or use .at[] updates")
        elif isinstance(tgt, (ast.Tuple, ast.List)):
            for el in tgt.elts:
                yield from self._target(el, node, src, entry)

    @staticmethod
    def _is_at_update(receiver) -> bool:
        # x.at[i].<op>(...) is jax's functional update — never a mutation
        return (isinstance(receiver, ast.Subscript)
                and isinstance(receiver.value, ast.Attribute)
                and receiver.value.attr == "at")


@register
class LoopHostScalarize(Rule):
    id = "ECO110"
    name = "loop-host-scalarize"
    description = ("per-item host scalarisation in a loop — int(x.sum()) "
                   "and friends sync once per iteration; batch the "
                   "reduction (np.count_nonzero / one vectorised call) or "
                   "make the host-side contract explicit")
    include = ("*/repro/core/*.py", "*/repro/serving/*.py")

    def check(self, src):
        annotate_parents(src.tree)
        for node in src.nodes:
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in ("int", "float", "bool")
                    and node.args):
                continue
            red = self._find_reduction(node.args[0])
            if red is None or not in_loop(node):
                continue
            yield self.hit(node, src.path,
                           f"{node.func.id}(….{red}()) scalarises one "
                           "item per loop iteration — hoist the reduction "
                           "out of the loop or use an explicitly host-side "
                           "form (np.count_nonzero)")

    @staticmethod
    def _find_reduction(expr):
        for sub in ast.walk(expr):
            if (isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in REDUCERS):
                root = (dotted_name(sub.func.value) or "").split(".", 1)[0]
                if root not in NP_NAMES:
                    return sub.func.attr
        return None
