"""CLI: ``python -m repro.analysis [paths] [--project]
[--format text|json|github] [--select/--ignore IDS] [--report FILE]
[--list-rules]``.

``--project`` enables the interprocedural families (ECO6xx concurrency,
ECO7xx contracts, ECO12x transitive purity, ECO900 suppression audit) on
top of the per-file rules, sharing one whole-tree symbol graph.
``--format github`` emits workflow-command annotations so findings land on
the PR diff; ``--report FILE`` writes the JSON report (schema v1)
regardless of the display format, for upload as a CI artifact.

Exit codes: 0 clean, 1 violations found, 2 usage error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

from repro.analysis.engine import run_paths
from repro.analysis.registry import all_rules

DEFAULT_PATHS = ("src", "tests", "benchmarks", "examples")


def _split(values: List[str]) -> List[str]:
    out: List[str] = []
    for v in values:
        out.extend(s.strip() for s in v.split(",") if s.strip())
    return out


def catalogue_markdown() -> str:
    """The generated rule catalogue — ``docs/RULES.md`` is exactly this
    (a drift-check test keeps them in sync)."""
    lines = ["# repro-lint rule catalogue",
             "",
             "Generated by `python -m repro.analysis --list-rules "
             "--format markdown > docs/RULES.md` — do not edit by hand.",
             "",
             "Rules marked **project** need the interprocedural graph and "
             "only run under `--project`.",
             "",
             "| id | name | scope | description |",
             "| --- | --- | --- | --- |"]
    for rid, cls in all_rules().items():
        scope = "project" if cls.requires_project else (
            "cross-file" if cls.project_level else "file")
        desc = " ".join(cls.description.split()).replace("|", "\\|")
        lines.append(f"| {rid} | {cls.name} | {scope} | {desc} |")
    lines.append("")
    return "\n".join(lines)


def _github_annotations(report) -> None:
    for v in report.violations:
        message = v.message.replace("%", "%25").replace(
            "\r", "%0D").replace("\n", "%0A")
        print(f"::error file={v.path},line={v.line},col={v.col + 1},"
              f"title={v.rule} {message.split(' — ')[0][:60]}::"
              f"{v.rule} {message}")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="repro architectural lint: scan/jit purity (ECO1xx), "
                    "hot-path discipline (ECO2xx), serving thread safety "
                    "(ECO3xx), kernel oracle contract (ECO4xx), "
                    "one call site per environment API (ECO5xx); with "
                    "--project also "
                    "concurrency (ECO6xx), protocol contracts (ECO7xx), "
                    "transitive purity (ECO12x) and the suppression audit "
                    "(ECO900).")
    ap.add_argument("paths", nargs="*",
                    help="files/directories to lint (default: whichever of "
                         f"{'/'.join(DEFAULT_PATHS)} exist)")
    ap.add_argument("--format", choices=("text", "json", "github",
                                         "markdown"),
                    default="text", dest="fmt",
                    metavar="text|json|github|markdown")
    ap.add_argument("--project", action="store_true",
                    help="also run the interprocedural rule families on a "
                         "shared whole-tree symbol graph")
    ap.add_argument("--select", action="append", default=[], metavar="IDS",
                    help="only run rules matching these comma-separated id "
                         "prefixes or names (e.g. ECO1,ECO302)")
    ap.add_argument("--ignore", action="append", default=[], metavar="IDS",
                    help="skip rules matching these id prefixes or names")
    ap.add_argument("--report", metavar="FILE", default=None,
                    help="also write the JSON report (schema v1) to FILE")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalogue and exit (--format "
                         "markdown emits the docs/RULES.md form)")
    args = ap.parse_args(argv)

    if args.list_rules:
        if args.fmt == "markdown":
            print(catalogue_markdown(), end="")
            return 0
        for rid, cls in all_rules().items():
            tag = "  [--project]" if cls.requires_project else ""
            print(f"{rid}  {cls.name}{tag}")
            print(f"       {cls.description}")
        return 0
    if args.fmt == "markdown":
        print("repro-lint: --format markdown is only valid with "
              "--list-rules", file=sys.stderr)
        return 2

    paths = args.paths or [p for p in DEFAULT_PATHS if os.path.exists(p)]
    if not paths:
        print("repro-lint: no paths given and none of "
              f"{', '.join(DEFAULT_PATHS)} exist here", file=sys.stderr)
        return 2
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        print(f"repro-lint: no such path: {', '.join(missing)}",
              file=sys.stderr)
        return 2

    report = run_paths(paths, select=_split(args.select) or None,
                       ignore=_split(args.ignore) or None,
                       project=args.project)

    if args.report:
        with open(args.report, "w", encoding="utf-8") as fh:
            json.dump(report.as_dict(), fh, indent=2, sort_keys=True)
            fh.write("\n")

    if args.fmt == "json":
        print(json.dumps(report.as_dict(), indent=2, sort_keys=True))
    elif args.fmt == "github":
        _github_annotations(report)
        n = len(report.violations)
        print(f"repro-lint: {report.files} files, {len(report.rules)} "
              f"rules, {n} violation{'' if n == 1 else 's'} "
              f"({report.suppressed} suppressed)")
    else:
        for v in report.violations:
            print(v.render())
        n = len(report.violations)
        print(f"repro-lint: {report.files} files, {len(report.rules)} "
              f"rules, {n} violation{'' if n == 1 else 's'} "
              f"({report.suppressed} suppressed)")
    return 1 if report.violations else 0
