"""Family 3 — serving thread/async safety (ECO301/302/303/304).

The serving plane runs a background flusher thread plus caller threads
plus (behind the asyncio facade) an event loop.  The historical failure
shapes: blocking while holding the service lock (stalls every submitter),
completing an asyncio future from a foreign thread (corrupts loop state),
blind exception handlers that let the flusher die silently, and wall-clock
sleeps / unbounded spin loops that bypass the injectable clock the whole
fault plane is tested against.
"""
from __future__ import annotations

import ast
import re

from repro.analysis.registry import Rule, register
from repro.analysis.rules.common import (annotate_parents, dotted_name,
                                         enclosing_function)

_LOCKISH = re.compile(r"lock|cond|mutex|sem", re.I)
_QUEUEISH = frozenset({"q", "_q", "queue", "_queue"})


@register
class BlockingUnderLock(Rule):
    id = "ECO301"
    name = "lock-blocking-call"
    description = ("blocking call (.result()/.join()/sleep()/queue .get()) "
                   "while holding a lock stalls every submitter — "
                   "Condition.wait, which releases the lock, is the "
                   "sanctioned way to sleep")
    include = ("*/repro/serving/*.py",)

    def check(self, src):
        for node in src.nodes:
            if not isinstance(node, ast.With):
                continue
            if not any(self._lockish(item.context_expr)
                       for item in node.items):
                continue
            for stmt in node.body:
                for sub in ast.walk(stmt):
                    if isinstance(sub, ast.Call):
                        what = self._blocking(sub)
                        if what:
                            yield self.hit(sub, src.path,
                                           f"{what} while holding a lock "
                                           "blocks every other submitter "
                                           "— release first (Condition"
                                           ".wait releases and is fine)")

    @staticmethod
    def _lockish(expr) -> bool:
        name = dotted_name(expr.func) if isinstance(expr, ast.Call) \
            else dotted_name(expr)
        last = (name or "").rsplit(".", 1)[-1]
        return bool(_LOCKISH.search(last))

    @staticmethod
    def _blocking(call):
        f = call.func
        if isinstance(f, ast.Name) and f.id == "sleep":
            return "sleep(...)"
        if not isinstance(f, ast.Attribute):
            return None
        if dotted_name(f) == "time.sleep":
            return "time.sleep(...)"
        if f.attr in ("result", "join"):
            return f".{f.attr}(...)"
        if f.attr == "get":
            recv = (dotted_name(f.value) or "").rsplit(".", 1)[-1]
            if recv in _QUEUEISH or recv.endswith("_queue"):
                return f"{recv}.get(...)"
        return None


@register
class CrossThreadFutureCompletion(Rule):
    id = "ECO302"
    name = "cross-thread-future"
    description = ("asyncio future completed outside a "
                   "call_soon_threadsafe-scheduled callback — asyncio "
                   "futures are not thread-safe; a foreign-thread "
                   "set_result/set_exception corrupts loop state")
    include = ("*/repro/serving/*.py",)

    def check(self, src):
        annotate_parents(src.tree)
        afut_names = set()
        scheduled_fns = set()
        for node in src.nodes:
            value = getattr(node, "value", None)
            if (isinstance(node, (ast.Assign, ast.AnnAssign))
                    and isinstance(value, ast.Call)
                    and isinstance(value.func, ast.Attribute)
                    and value.func.attr == "create_future"):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for tgt in targets:
                    if isinstance(tgt, ast.Name):
                        afut_names.add(tgt.id)
            elif (isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Attribute)
                  and node.func.attr == "call_soon_threadsafe"):
                for arg in node.args:
                    if isinstance(arg, ast.Name):
                        scheduled_fns.add(arg.id)
        if not afut_names:
            return
        for node in src.nodes:
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("set_result", "set_exception")
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in afut_names):
                continue
            fn = enclosing_function(node)
            if fn is None or fn.name not in scheduled_fns:
                yield self.hit(node, src.path,
                               f"{node.func.value.id}."
                               f"{node.func.attr}(...) completes an "
                               "asyncio future outside a callback handed "
                               "to call_soon_threadsafe — unsafe unless "
                               "already on the loop thread")


@register
class BlindExcept(Rule):
    id = "ECO303"
    name = "blind-except"
    description = ("bare except / except BaseException / pass-only handler "
                   "in the serving plane lets the flusher thread die "
                   "silently — name the exception and surface it")
    include = ("*/repro/serving/*.py",)

    def check(self, src):
        for node in src.nodes:
            if not isinstance(node, ast.ExceptHandler):
                continue
            if node.type is None:
                yield self.hit(node, src.path,
                               "bare except: swallows everything, "
                               "including the flusher thread's death — "
                               "catch Exception at most and record it")
            elif dotted_name(node.type) == "BaseException":
                yield self.hit(node, src.path,
                               "except BaseException traps "
                               "KeyboardInterrupt/SystemExit in serving "
                               "code — catch Exception")
            elif len(node.body) == 1 and isinstance(node.body[0], ast.Pass):
                yield self.hit(node, src.path,
                               "exception silently dropped (pass-only "
                               "handler) — record it or re-raise so "
                               "serving failures stay observable")


@register
class WallClockRetry(Rule):
    id = "ECO304"
    name = "wall-clock-retry"
    description = ("time.sleep or an unbounded ``while True`` loop in the "
                   "serving plane bypasses the injectable clock — retries "
                   "and backoff must condition-wait on the clock the fault "
                   "tests control, and every spin loop needs an exit")
    # the traffic plane (arrivals, LoadDriver, autoscaler episodes) is
    # virtual-time by contract: a wall-clock sleep there silently turns a
    # millisecond replay into real seconds, so it gets the same rule
    include = ("*/repro/serving/*.py", "*/repro/traffic/*.py")

    def check(self, src):
        for node in src.nodes:
            if isinstance(node, ast.Call) and self._is_sleep(node):
                yield self.hit(node, src.path,
                               "wall-clock sleep in the serving plane — "
                               "backoff/polling must ride the injectable "
                               "clock (Condition.wait with a timeout "
                               "derived from it), or fault tests that "
                               "drive a fake clock hang for real seconds")
            elif (isinstance(node, ast.While)
                    and isinstance(node.test, ast.Constant)
                    and node.test.value is True
                    and not self._has_exit(node)):
                yield self.hit(node, src.path,
                               "while True with no break/return — an "
                               "unbounded retry/poll loop cannot be "
                               "drained or closed; bound it (attempt "
                               "budget, _closed flag, or an explicit "
                               "break on the empty condition)")

    @staticmethod
    def _is_sleep(call) -> bool:
        f = call.func
        if isinstance(f, ast.Name):
            return f.id == "sleep"
        return isinstance(f, ast.Attribute) and dotted_name(f) == "time.sleep"

    @staticmethod
    def _has_exit(loop) -> bool:
        """break/return anywhere in the loop body, not counting nested
        functions (their control flow cannot exit THIS loop) or nested
        loops' own breaks (a break there exits the inner loop only)."""
        stack = list(loop.body)
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.Break, ast.Return)):
                return True
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue
            if isinstance(node, (ast.While, ast.For)):
                # the inner loop's orelse runs in OUR scope; its body's
                # breaks do not — but a return inside still exits us
                stack.extend(node.orelse)
                stack.extend(n for b in node.body for n in ast.walk(b)
                             if isinstance(n, ast.Return))
                continue
            stack.extend(ast.iter_child_nodes(node))
        return False
