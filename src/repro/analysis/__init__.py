"""repro.analysis: the AST-based architectural lint plane.

PRs 1-5 concentrated the ECORE reproduction into a few load-bearing
invariants — a pure scanned closed loop, one serving dispatch plane,
bit-exact jnp oracles per kernel, and one call site per environment API.
This package turns those prose rules into enforced ones:

* family ECO1xx — scan/jit purity (host syncs, impure calls, mutation)
* family ECO2xx — hot-path discipline (loops, profile facade, forked
  serving loops)
* family ECO3xx — serving thread/async safety
* family ECO4xx — kernel oracle contract (ops.py + ref.py + parity test)
* family ECO5xx — one call site each for AxisType / make_mesh / hypothesis

CLI: ``python -m repro.analysis [paths] [--format text|json]``.
Suppress one finding with ``# repro-lint: disable=<rule>`` (justification
text after the ids is encouraged); configure via ``[tool.repro-lint]`` in
pyproject.toml.  Library surface: ``run_paths`` (disk), ``check_source``/
``check_sources`` (in-memory fixtures, used by tests/test_analysis.py).
"""
from repro.analysis.engine import (Report, Violation,  # noqa: F401
                                   check_source, check_sources, run_paths)

__all__ = ["Report", "Violation", "check_source", "check_sources",
           "run_paths"]
