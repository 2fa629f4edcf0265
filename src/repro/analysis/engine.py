"""repro.analysis engine: source loading, suppressions, rule execution.

The engine is deliberately stdlib-only (``ast`` + ``re`` + ``fnmatch``): it
runs in CI before anything heavy is importable, and it must never import
jax — the linted tree includes modules whose import would initialise
device state.

Suppression grammar (free-text justification may follow the id list)::

    x = risky()  # repro-lint: disable=ECO101
    # repro-lint: disable=ECO101, ECO110 -- why this is sanctioned
    x = risky()
    # repro-lint: disable-file=ECO503

An inline marker suppresses its own line; a standalone comment marker
suppresses the next non-comment line (so a justification block may follow
it), and when that line starts a decorator stack the decorated ``def`` /
``class`` line is covered too; ``disable-file`` suppresses the whole file.
``all`` (or ``*``) as an id disables every rule.  Markers are read from
real COMMENT tokens only — marker-shaped text inside docstrings or string
literals is inert (it used to register phantom suppressions).
"""
from __future__ import annotations

import ast
import dataclasses
import fnmatch
import io
import os
import re
import tokenize
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

_SUPPRESS_RE = re.compile(
    r"#\s*repro-lint:\s*(disable|disable-file)\s*=\s*"
    r"([A-Za-z0-9_*]+(?:\s*,\s*[A-Za-z0-9_*]+)*)")

#: always skipped during file collection (config ``exclude`` adds to this)
DEFAULT_EXCLUDE = ("*/__pycache__/*", "*/.git/*", "*/build/*", "*/dist/*",
                   "*.egg-info/*")


def norm_path(path) -> str:
    p = str(path).replace(os.sep, "/")
    while p.startswith("./"):
        p = p[2:]
    return p


def match_path(path, patterns: Sequence[str]) -> bool:
    """fnmatch against both the path and a ``/``-anchored form, so
    ``*/core/*.py`` patterns match repo-relative paths (``src/repro/core/
    x.py`` and ``core/x.py`` alike) as well as absolute ones."""
    p = norm_path(path)
    anchored = p if p.startswith("/") else "/" + p
    return any(fnmatch.fnmatch(anchored, pat) or fnmatch.fnmatch(p, pat)
               for pat in patterns)


@dataclasses.dataclass(frozen=True)
class Violation:
    rule: str
    path: str
    line: int
    col: int
    message: str

    def sort_key(self) -> Tuple[str, int, int, str]:
        return (self.path, self.line, self.col, self.rule)

    def as_dict(self) -> Dict[str, object]:
        return dataclasses.asdict(self)

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: "
                f"{self.rule} {self.message}")


@dataclasses.dataclass
class Marker:
    """One ``# repro-lint: disable[-file]=...`` comment marker."""
    lineno: int
    ids: Tuple[str, ...]
    file_level: bool
    targets: Set[int]                   # lines this marker covers
    used_for: Set[str] = dataclasses.field(default_factory=set)

    def names(self, rule_id: str) -> bool:
        return rule_id in self.ids or "all" in self.ids or "*" in self.ids


def _comment_tokens(text: str):
    """(lineno, col, comment-text) for every real COMMENT token.  The text
    already parsed under ``ast``, so tokenize errors are tail-only; comments
    gathered before one are kept."""
    out = []
    try:
        for tok in tokenize.generate_tokens(io.StringIO(text).readline):
            if tok.type == tokenize.COMMENT:
                out.append((tok.start[0], tok.start[1], tok.string))
    except (tokenize.TokenError, IndentationError, SyntaxError):
        pass
    return out


class SourceFile:
    """A parsed source file plus its suppression markers."""

    def __init__(self, path: str, text: str):
        self.path = norm_path(path)
        self.text = text
        self.lines = text.splitlines()
        self.tree = ast.parse(text)  # caller converts SyntaxError to E001
        #: every node of the tree in ``ast.walk`` order, walked once for
        #: all the rules that scan the whole file
        self.nodes = list(ast.walk(self.tree))
        #: decorator-stack start line -> decorated def/class line, so a
        #: standalone marker above ``@decorator`` also covers the def line
        #: (rules report at the def, not the decorator).
        dec_spans = {}
        for node in self.nodes:
            decs = getattr(node, "decorator_list", None)
            if decs:
                first = min(d.lineno for d in decs)
                for ln in range(first, node.lineno):
                    dec_spans[ln] = node.lineno
        self.markers: List[Marker] = []
        for lineno, col, comment in _comment_tokens(text):
            m = _SUPPRESS_RE.search(comment)
            if m is None:
                continue
            ids = tuple(dict.fromkeys(
                s.strip() for s in m.group(2).split(",") if s.strip()))
            if m.group(1) == "disable-file":
                self.markers.append(Marker(lineno, ids, True, set()))
                continue
            targets = {lineno}
            standalone = self.lines[lineno - 1][:col].strip() == ""
            if standalone:
                targets = set()
                for nxt in range(lineno + 1, len(self.lines) + 1):
                    stripped = self.lines[nxt - 1].strip()
                    if stripped and not stripped.startswith("#"):
                        targets = {nxt}
                        if nxt in dec_spans:
                            targets.add(dec_spans[nxt])
                        break
                if not targets:
                    continue  # trailing comment block: nothing to suppress
            self.markers.append(Marker(lineno, ids, False, targets))

    def suppressed(self, rule_id: str, line: int,
                   explicit_only: bool = False) -> bool:
        """True when a marker covers (rule, line); records marker usage so
        the ECO900 meta-rule can flag markers that never matched.  With
        ``explicit_only`` (used for ECO900's own findings) blanket
        ``all``/``*`` markers do not match — a stale blanket marker must
        not be able to swallow its own audit."""
        hit = False
        for m in self.markers:
            if not (m.file_level or line in m.targets):
                continue
            if rule_id in m.ids or (not explicit_only and m.names(rule_id)):
                m.used_for.add(rule_id)
                hit = True
        return hit


@dataclasses.dataclass
class Report:
    files: int
    rules: List[str]
    violations: List[Violation]
    suppressed: int

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for v in self.violations:
            out[v.rule] = out.get(v.rule, 0) + 1
        return out

    def as_dict(self) -> Dict[str, object]:
        """JSON schema v1 — stable; covered by tests/test_analysis.py."""
        return {"version": 1,
                "files": self.files,
                "rules": list(self.rules),
                "violations": [v.as_dict() for v in self.violations],
                "counts": self.counts(),
                "suppressed": self.suppressed}


def parse_source(path: str, text: str):
    """-> ``(SourceFile, None)`` or ``(None, E001 Violation)``."""
    try:
        return SourceFile(path, text), None
    except SyntaxError as e:
        return None, Violation("E001", norm_path(path), e.lineno or 1,
                               max((e.offset or 1) - 1, 0),
                               f"syntax error: {e.msg}")


def collect_paths(paths: Sequence[str],
                  exclude: Sequence[str] = DEFAULT_EXCLUDE) -> List[str]:
    out: List[str] = []
    for p in paths:
        p = norm_path(p)
        if os.path.isfile(p):
            if p.endswith(".py") and not match_path(p, exclude):
                out.append(p)
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = sorted(d for d in dirnames
                                 if d != "__pycache__"
                                 and not d.startswith("."))
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                fp = norm_path(os.path.join(dirpath, fn))
                if not match_path(fp, exclude):
                    out.append(fp)
    seen: Set[str] = set()
    uniq = []
    for p in out:
        if p not in seen:
            seen.add(p)
            uniq.append(p)
    return uniq


def run_rules(sources: Sequence[SourceFile], rules,
              extra_violations: Iterable[Violation] = ()):
    """-> (sorted violations, suppressed count).

    Rules flagged ``runs_after`` (the ECO900 suppression audit) execute
    once every other rule has consulted the suppression maps.  Rules
    flagged ``requires_project`` share one lazily-built ``Project`` graph
    — the single whole-tree parse pass the interprocedural families run
    on.
    """
    by_path = {s.path: s for s in sources}
    violations = list(extra_violations)
    suppressed = 0
    project = None
    enabled = frozenset(r.id for r in rules)
    ordered = ([r for r in rules if not r.runs_after]
               + [r for r in rules if r.runs_after])
    for rule in ordered:
        rule.enabled_ids = enabled
        if rule.requires_project:
            if project is None:
                from repro.analysis.project import build_project
                project = build_project(sources)
            rule.project = project
        targets = [s for s in sources if rule.applies_to(s.path)]
        if rule.project_level:
            found = list(rule.check_project(targets))
        else:
            found = [v for src in targets for v in rule.check(src)]
        for v in found:
            src = by_path.get(v.path)
            if src is not None and src.suppressed(
                    v.rule, v.line, explicit_only=rule.runs_after):
                suppressed += 1
            else:
                violations.append(v)
    violations.sort(key=Violation.sort_key)
    return violations, suppressed


def run_paths(paths: Sequence[str], *, select: Optional[Sequence[str]] = None,
              ignore: Optional[Sequence[str]] = None,
              config: Optional[Dict[str, object]] = None,
              project: bool = False) -> Report:
    """Lint files/directories on disk (the CLI entry point)."""
    from repro.analysis.config import load_config
    from repro.analysis.registry import make_rules
    cfg = dict(config) if config is not None else load_config(
        paths[0] if paths else ".")
    exclude = tuple(DEFAULT_EXCLUDE) + tuple(cfg.get("exclude") or ())
    files = collect_paths(paths, exclude)
    sources, errors = [], []
    loaded = 0
    for fp in files:
        try:
            with open(fp, "r", encoding="utf-8") as fh:
                text = fh.read()
        except (UnicodeDecodeError, OSError):
            continue  # binary / non-UTF8 / unreadable: not lintable source
        loaded += 1
        src, err = parse_source(fp, text)
        if src is not None:
            sources.append(src)
        else:
            errors.append(err)
    rules = make_rules(select=list(select or ()) or None,
                       ignore=list(ignore or ()) + list(cfg.get("ignore")
                                                        or ()),
                       options=cfg, project=project)
    violations, suppressed = run_rules(sources, rules, errors)
    return Report(files=loaded, rules=[r.id for r in rules],
                  violations=violations, suppressed=suppressed)


def check_sources(named: Dict[str, str], *,
                  select: Optional[Sequence[str]] = None,
                  ignore: Optional[Sequence[str]] = None,
                  options: Optional[Dict[str, object]] = None,
                  project: bool = False) -> Report:
    """Lint in-memory sources (``{path: text}``) — the fixture-test surface.

    Paths are virtual but still drive per-rule include/exclude matching, so
    fixtures choose which plane they pretend to live in (e.g.
    ``src/repro/core/x.py``).
    """
    from repro.analysis.config import DEFAULTS
    from repro.analysis.registry import make_rules
    cfg = {k: (list(v) if isinstance(v, list) else v)
           for k, v in DEFAULTS.items()}
    cfg.update(options or {})
    sources, errors = [], []
    for path, text in named.items():
        src, err = parse_source(path, text)
        if src is not None:
            sources.append(src)
        else:
            errors.append(err)
    rules = make_rules(select=list(select or ()) or None,
                       ignore=list(ignore or ()) or None, options=cfg,
                       project=project)
    violations, suppressed = run_rules(sources, rules, errors)
    return Report(files=len(named), rules=[r.id for r in rules],
                  violations=violations, suppressed=suppressed)


def check_source(text: str, path: str = "src/repro/core/snippet.py",
                 **kw) -> List[Violation]:
    """Lint one in-memory snippet; returns the violation list."""
    return check_sources({path: text}, **kw).violations
