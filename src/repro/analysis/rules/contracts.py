"""Family 7 — protocol contract conformance (ECO701..704, ``--project``).

The extension points are protocols, not base classes: ``ExecutionBackend``
(``serving/backend.py`` registry) and ``RoutingPolicy`` (``core/policy.py``)
are satisfied structurally, so a drifted method name or arity only fails at
dispatch time deep inside a serving thread.  These rules check the protocol
surface statically: every registered or duck-typed backend/policy exposes
the required methods with compatible arity, a literal ``batchable = True``
is honest (``decide_batch`` must not degrade to a per-request
``self.decide`` loop), and every public ``kernels/<name>/ops.py`` entry
point dispatches to a ``ref.py`` oracle whose signature accepts the call.

Emission is limited to ``src/repro`` — test doubles are intentionally
partial.
"""
from __future__ import annotations

import ast
from typing import List, Optional, Set, Tuple

from repro.analysis.project import module_name
from repro.analysis.registry import Rule, register
from repro.analysis.rules.common import dotted_name
from repro.analysis.rules.kernels import kernel_packages

_FUNCS = (ast.FunctionDef, ast.AsyncFunctionDef)


def _arity_ok(fnode, expected: int) -> bool:
    """Can the method be called with ``expected`` positional args (self
    included)?"""
    a = fnode.args
    pos = len(getattr(a, "posonlyargs", ())) + len(a.args)
    required = pos - len(a.defaults)
    if a.vararg is not None:
        return required <= expected
    return required <= expected <= pos


class _ContractRule(Rule):
    requires_project = True
    project_level = True
    include = ("*/repro/*.py",)
    exclude = ("*/repro/analysis/*",)

    def _classes(self, sources):
        proj = self.project
        if proj is None:
            return
        linted = {s.path for s in sources}
        for mod in proj.modules.values():
            if mod.path not in linted or not self.applies_to(mod.path):
                continue
            for ci in mod.classes.values():
                yield mod, ci

    def _missing_method(self, ci, name: str, expected: int
                        ) -> Optional[str]:
        m = self.project.method(ci, name)
        if m is None:
            return f"has no {name}() method"
        if not _arity_ok(m.node, expected):
            return (f"{name}() cannot be called with {expected - 1} "
                    f"argument{'s' if expected != 2 else ''} (plus self)")
        return None


@register
class BackendConformance(_ContractRule):
    id = "ECO701"
    name = "backend-conformance"
    description = ("a registered or duck-typed ExecutionBackend must expose "
                   "serve_batch(self, requests), profile_row(self), and "
                   "name/max_batch attributes — a drifted surface only "
                   "fails at dispatch time inside a serving thread "
                   "(--project)")

    def check_project(self, sources):
        proj = self.project
        if proj is None:
            return
        registered: Set[Tuple[str, str]] = set()
        for src in sources:
            mod = proj.modules.get(module_name(src.path))
            if mod is None:
                continue
            for node in src.nodes:
                if not isinstance(node, ast.Call):
                    continue
                fname = (dotted_name(node.func) or "").rsplit(".", 1)[-1]
                if fname == "register_backend" and len(node.args) >= 2:
                    cls_name = dotted_name(node.args[1])
                    if cls_name and cls_name in mod.classes:
                        registered.add((mod.name, cls_name))
        for mod, ci in self._classes(sources):
            is_registered = (mod.name, ci.name) in registered
            duck = "serve_batch" in ci.methods and "profile_row" in ci.methods
            if not (is_registered or duck):
                continue
            problems: List[str] = []
            for meth, expected in (("serve_batch", 2), ("profile_row", 1)):
                msg = self._missing_method(ci, meth, expected)
                if msg:
                    problems.append(msg)
            for attr in ("name", "max_batch"):
                if not proj.has_attr(ci, attr):
                    problems.append(f"defines no {attr!r} attribute")
            for p in problems:
                yield self.hit(ci.node, mod.path,
                               f"backend {ci.name!r} {p} — the "
                               "ExecutionBackend surface is serve_batch/"
                               "profile_row/name/max_batch")


@register
class PolicyConformance(_ContractRule):
    id = "ECO702"
    name = "policy-conformance"
    description = ("a RoutingPolicy face must expose decide(self, request), "
                   "decide_batch(self, requests), observe(self, "
                   "observation), reset(self), and a batchable attribute "
                   "(--project)")

    def check_project(self, sources):
        proj = self.project
        if proj is None:
            return
        for mod, ci in self._classes(sources):
            if "decide" not in ci.methods:
                continue
            if not ("decide_batch" in ci.methods or "observe" in ci.methods):
                continue  # a lone decide() is not a policy face
            problems: List[str] = []
            for meth, expected in (("decide", 2), ("decide_batch", 2),
                                   ("observe", 2), ("reset", 1)):
                msg = self._missing_method(ci, meth, expected)
                if msg:
                    problems.append(msg)
            if not proj.has_attr(ci, "batchable"):
                problems.append("defines no 'batchable' attribute")
            for p in problems:
                yield self.hit(ci.node, mod.path,
                               f"policy {ci.name!r} {p} — the "
                               "RoutingPolicy surface is decide/"
                               "decide_batch/observe/reset/batchable")


@register
class BatchableHonesty(_ContractRule):
    id = "ECO703"
    name = "batchable-honesty"
    description = ("batchable = True but decide_batch loops self.decide "
                   "per request — callers batch on that promise and get "
                   "serialized per-item routing (--project)")

    _LOOPS = (ast.For, ast.AsyncFor, ast.While, ast.ListComp, ast.SetComp,
              ast.DictComp, ast.GeneratorExp)

    def check_project(self, sources):
        for mod, ci in self._classes(sources):
            flag = ci.class_assigns.get("batchable")
            if not (isinstance(flag, ast.Constant) and flag.value is True):
                continue
            db = ci.methods.get("decide_batch")
            if db is None:
                continue
            for loop in ast.walk(db.node):
                if not isinstance(loop, self._LOOPS):
                    continue
                for call in ast.walk(loop):
                    if (isinstance(call, ast.Call)
                            and dotted_name(call.func) == "self.decide"):
                        yield self.hit(
                            call, mod.path,
                            f"{ci.name}.decide_batch loops self.decide "
                            "per request while advertising batchable = "
                            "True — vectorise it or set batchable = False")
                        break
                else:
                    continue
                break


@register
class KernelOracleSignature(_ContractRule):
    id = "ECO704"
    name = "kernel-oracle-signature"
    description = ("every public ops.py entry point must dispatch to a "
                   "ref.py oracle with a signature that accepts the call — "
                   "an entry without a matching oracle is unverifiable "
                   "(--project)")
    include = ("*/repro/kernels/*.py",)
    exclude = ()

    def check_project(self, sources):
        for (pkg_dir, name), files in sorted(kernel_packages(sources)
                                             .items()):
            ops, ref = files.get("ops.py"), files.get("ref.py")
            if ops is None or ref is None:
                continue  # ECO402's finding, not ours
            ref_defs = {n.name: n for n in ref.tree.body
                        if isinstance(n, _FUNCS)}
            for node in ops.tree.body:
                if isinstance(node, _FUNCS):
                    if node.name.startswith("_"):
                        continue
                    refs = [(sub.func.attr, sub)
                            for sub in ast.walk(node)
                            if isinstance(sub, ast.Call)
                            and isinstance(sub.func, ast.Attribute)
                            and isinstance(sub.func.value, ast.Name)
                            and sub.func.value.id == "ref"]
                    if not refs:
                        yield self.hit(
                            node, ops.path,
                            f"kernel {name!r} entry point {node.name}() "
                            "never dispatches to a ref.* oracle — parity "
                            "is unverifiable")
                        continue
                    for fn, call in refs:
                        yield from self._check_call(name, node.name, fn,
                                                    call, ref_defs,
                                                    ops.path)
                elif isinstance(node, ast.Assign):
                    # module-level alias: entry = jax.jit(ref.fn)
                    for sub in ast.walk(node.value):
                        d = dotted_name(sub) if isinstance(
                            sub, ast.Attribute) else None
                        if d and d.startswith("ref."):
                            fn = d.split(".", 1)[1]
                            if fn not in ref_defs:
                                yield self.hit(
                                    node, ops.path,
                                    f"kernel {name!r} aliases ref.{fn} "
                                    "which does not exist in ref.py")

    def _check_call(self, kernel, entry, fn, call, ref_defs, path):
        if fn not in ref_defs:
            yield self.hit(call, path,
                           f"kernel {kernel!r} entry point {entry}() "
                           f"dispatches to ref.{fn} which does not exist "
                           "in ref.py")
            return
        a = ref_defs[fn].args
        if any(isinstance(x, ast.Starred) for x in call.args) or any(
                kw.arg is None for kw in call.keywords):
            return  # *args/**kwargs forwarding: not statically checkable
        pos_params = [p.arg for p in
                      (list(getattr(a, "posonlyargs", ())) + list(a.args))]
        given_pos = len(call.args)
        kw_names = {kw.arg for kw in call.keywords}
        if given_pos > len(pos_params) and a.vararg is None:
            yield self.hit(call, path,
                           f"ref.{fn} takes {len(pos_params)} positional "
                           f"argument(s) but {entry}() passes {given_pos}")
            return
        if a.kwarg is None:
            valid = set(pos_params) | {p.arg for p in a.kwonlyargs}
            for kw in sorted(kw_names - valid):
                yield self.hit(call, path,
                               f"ref.{fn} has no parameter {kw!r} "
                               f"(passed by {entry}())")
        required_pos = pos_params[:len(pos_params) - len(a.defaults)]
        for p in required_pos[given_pos:]:
            if p not in kw_names:
                yield self.hit(call, path,
                               f"ref.{fn} requires argument {p!r} which "
                               f"{entry}() does not pass")
        required_kwonly = {p.arg for p, d in zip(a.kwonlyargs, a.kw_defaults)
                           if d is None}
        for p in sorted(required_kwonly - kw_names):
            yield self.hit(call, path,
                           f"ref.{fn} requires keyword argument {p!r} "
                           f"which {entry}() does not pass")
