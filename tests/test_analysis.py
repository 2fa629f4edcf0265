"""repro.analysis: per-rule good/bad fixtures + CLI surface.

Every rule family gets at least one snippet it must flag and one it must
not (the sanctioned idiom).  Fixtures are in-memory sources pushed through
``check_source``/``check_sources`` with virtual paths, so each one chooses
which plane it pretends to live in.  The CLI tests cover the acceptance
surface: JSON schema stability, nonzero exit on violation, zero exit on
the clean tree, and the suppression round-trip.

Stdlib-only: nothing here imports jax.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import check_source, check_sources
from repro.analysis.cli import main
from repro.analysis.engine import match_path

pytestmark = pytest.mark.analysis

REPO = Path(__file__).resolve().parent.parent

CORE = "src/repro/core/mod.py"
SERVING = "src/repro/serving/mod.py"


def rules_of(violations):
    return [v.rule for v in violations]


def src(text):
    return textwrap.dedent(text)


# ---------------------------------------------------------------- engine


def test_match_path_anchors_relative_and_absolute():
    assert match_path("src/repro/core/router.py", ("*/repro/core/*.py",))
    assert match_path("/abs/src/repro/core/router.py",
                      ("*/repro/core/*.py",))
    assert not match_path("src/repro/serving/engine.py",
                          ("*/repro/core/*.py",))


def test_syntax_error_becomes_e001():
    vs = check_source("def broken(:\n")
    assert rules_of(vs) == ["E001"]
    assert "syntax error" in vs[0].message


# ------------------------------------------------- family 1: jit purity


def test_eco101_flags_host_sync_in_jit_scope():
    vs = check_source(src("""
        import jax

        @jax.jit
        def f(x):
            y = float(x)
            z = x.item()
            w = np.asarray(x)
            return y + z + w
    """), select=["ECO101"])
    assert rules_of(vs) == ["ECO101", "ECO101", "ECO101"]


def test_eco101_pure_function_names_are_jit_scopes():
    vs = check_source(src("""
        def decide_state(state, count):
            return int(count)
    """), select=["ECO101"])
    assert rules_of(vs) == ["ECO101"]


def test_eco101_clean_outside_jit_scope():
    vs = check_source(src("""
        def helper(x):
            return float(x.sum())
    """), select=["ECO101"])
    assert vs == []


def test_eco101_partial_jit_decorator_detected():
    vs = check_source(src("""
        import functools, jax

        @functools.partial(jax.jit, static_argnames=("n",))
        def f(x, n):
            return int(x) + n
    """), select=["ECO101"])
    assert rules_of(vs) == ["ECO101"]


def test_eco102_flags_impure_calls_in_jit_scope():
    vs = check_source(src("""
        import jax, random, time

        @jax.jit
        def f(x):
            print(x)
            t = time.time()
            r = random.random()
            return x + t + r
    """), select=["ECO102"])
    assert rules_of(vs) == ["ECO102", "ECO102", "ECO102"]


def test_eco103_flags_python_mutation_in_jit_scope():
    vs = check_source(src("""
        import jax

        @jax.jit
        def f(x, d, xs):
            global g
            d["k"] = 1
            xs.append(x)
            return x
    """), select=["ECO103"])
    assert rules_of(vs) == ["ECO103", "ECO103", "ECO103"]


def test_eco103_at_updates_and_kernel_refs_are_sanctioned():
    good = src("""
        import jax

        @jax.jit
        def f(x, i):
            return x.at[i].add(1)
    """)
    assert check_source(good, select=["ECO103"]) == []
    # pallas kernels assign o_ref[...] by design: path-exempt
    kernel = src("""
        import jax

        @jax.jit
        def kernel(o_ref, x):
            o_ref[...] = x
    """)
    assert check_source(kernel, path="src/repro/kernels/foo/foo.py",
                        select=["ECO103"]) == []


def test_eco110_flags_per_item_scalarization_in_loop():
    vs = check_source(src("""
        def f(items):
            out = []
            for s in items:
                out.append(int((s >= 0.5).sum()))
            return out
    """), path=CORE, select=["ECO110"])
    assert rules_of(vs) == ["ECO110"]


def test_eco110_np_rooted_and_unlooped_reductions_are_fine():
    vs = check_source(src("""
        import numpy as np

        def f(items, x):
            out = [int(np.count_nonzero(s >= 0.5)) for s in items]
            depths = [int(np.argmin(s)) for s in items]
            total = int(x.sum())
            return out, depths, total
    """), path=CORE, select=["ECO110"])
    assert vs == []


# ------------------------------------------ family 2: hot-path discipline


def test_eco201_flags_python_loop_in_hot_function():
    vs = check_source(src("""
        def route_batch(counts):
            out = []
            for c in counts:
                out.append(c)
            return out
    """), path="src/repro/core/router.py", select=["ECO201"])
    assert rules_of(vs) == ["ECO201"]


def test_eco201_literal_unrolls_and_cold_functions_are_fine():
    vs = check_source(src("""
        def route_batch(x):
            for name in ("a", "b"):
                x += len(name)
            return x

        def cold_helper(xs):
            for x in xs:
                pass
    """), path="src/repro/core/router.py", select=["ECO201"])
    assert vs == []


def test_eco202_flags_profile_facade_in_hot_module():
    vs = check_source(src("""
        def f(table, state):
            table.observe("pair", 1, time_ms=2.0)
            table.entries[0] = None
            return table.load_state(state)
    """), path="src/repro/core/closed_loop.py", select=["ECO202"])
    assert rules_of(vs) == ["ECO202", "ECO202", "ECO202"]


def test_eco203_flags_serve_batch_outside_dispatch_plane():
    snippet = "def f(be, reqs):\n    return be.serve_batch(reqs)\n"
    assert rules_of(check_source(snippet, path="src/repro/core/driver.py",
                                 select=["ECO203"])) == ["ECO203"]
    # the dispatch plane itself and tests/ are sanctioned
    assert check_source(snippet, path="src/repro/serving/engine.py",
                        select=["ECO203"]) == []
    assert check_source(snippet, path="tests/test_x.py",
                        select=["ECO203"]) == []


# ------------------------------------------- family 3: thread/async safety


def test_eco301_flags_blocking_calls_under_lock():
    vs = check_source(src("""
        import time

        def f(self, fut):
            with self._lock:
                r = fut.result()
                time.sleep(0.1)
            return r
    """), path=SERVING, select=["ECO301"])
    assert rules_of(vs) == ["ECO301", "ECO301"]


def test_eco301_condition_wait_is_sanctioned():
    vs = check_source(src("""
        def f(self):
            with self._cond:
                self._cond.wait(0.5)
    """), path=SERVING, select=["ECO301"])
    assert vs == []


def test_eco302_flags_future_completion_off_loop():
    vs = check_source(src("""
        def f(loop):
            afut = loop.create_future()
            afut.set_result(1)
            return afut
    """), path=SERVING, select=["ECO302"])
    assert rules_of(vs) == ["ECO302"]


def test_eco302_call_soon_threadsafe_callback_is_sanctioned():
    vs = check_source(src("""
        def bridge(loop, cfut):
            afut = loop.create_future()

            def _copy():
                afut.set_result(cfut.result())

            loop.call_soon_threadsafe(_copy)
            return afut
    """), path=SERVING, select=["ECO302"])
    assert vs == []


def test_eco303_flags_blind_except_shapes():
    vs = check_source(src("""
        def f():
            try:
                g()
            except:
                h()
            try:
                g()
            except BaseException:
                h()
            try:
                g()
            except ValueError:
                pass
    """), path=SERVING, select=["ECO303"])
    assert rules_of(vs) == ["ECO303", "ECO303", "ECO303"]
    good = src("""
        def f(log):
            try:
                g()
            except Exception as exc:
                log(exc)
    """)
    assert check_source(good, path=SERVING, select=["ECO303"]) == []


def test_eco304_flags_wall_clock_sleep_and_unbounded_spin():
    vs = check_source(src("""
        import time
        from time import sleep

        def retry(fn):
            while True:
                try:
                    return fn()
                except Exception:
                    time.sleep(0.5)

        def poll(q):
            while True:
                sleep(0.01)
                q.flush()
    """), path=SERVING, select=["ECO304"])
    # retry's loop has a return (bounded); poll's does not — plus the two
    # sleeps themselves
    assert rules_of(vs) == ["ECO304", "ECO304", "ECO304"]


def test_eco304_condition_wait_loop_with_exit_is_sanctioned():
    vs = check_source(src("""
        def retry_loop(self):
            while True:
                with self._cond:
                    if self._closed:
                        return
                    self._cond.wait(0.05)
    """), path=SERVING, select=["ECO304"])
    assert vs == []


def test_eco304_nested_loop_break_does_not_bound_outer():
    vs = check_source(src("""
        def pump(self):
            while True:
                for item in self._queue:
                    if item is None:
                        break
    """), path=SERVING, select=["ECO304"])
    assert rules_of(vs) == ["ECO304"]


def test_eco304_covers_traffic_plane():
    # the traffic plane is virtual-time by contract: wall-clock sleeps are
    # flagged there exactly like in serving, with the same suppression
    TRAFFIC = "src/repro/traffic/mod.py"
    sleepy = src("""
        import time

        def pace(self, dt):
            time.sleep(dt)
    """)
    assert rules_of(check_source(sleepy, path=TRAFFIC,
                                 select=["ECO304"])) == ["ECO304"]
    suppressed = src("""
        import time

        def pace(self, dt):
            # repro-lint: disable=ECO304 -- wall-clock pacing demo
            time.sleep(dt)
    """)
    assert check_source(suppressed, path=TRAFFIC, select=["ECO304"]) == []
    # the OTHER serving rules stay serving-only: the traffic plane has no
    # flusher thread to protect
    assert check_source(src("""
        def f():
            try:
                g()
            except:
                pass
    """), path=TRAFFIC, select=["ECO303"]) == []


def test_eco304_only_applies_to_serving_and_suppression_works():
    sleepy = src("""
        import time

        def bench():
            time.sleep(1.0)
    """)
    assert check_source(sleepy, path=CORE, select=["ECO304"]) == []
    suppressed = src("""
        import time

        def simulate(self, ms):
            # repro-lint: disable=ECO304 -- simulated device busy time
            time.sleep(ms / 1e3)
    """)
    assert check_source(suppressed, path=SERVING, select=["ECO304"]) == []


# ---------------------------------------------- family 4: kernel contract


def _kernel_files(**overrides):
    files = {
        "src/repro/kernels/foo/__init__.py": "from .ops import foo\n",
        "src/repro/kernels/foo/ops.py": "def foo(x):\n    return x\n",
        "src/repro/kernels/foo/ref.py": "import jax.numpy as jnp\n",
        "tests/test_foo.py": "import repro.kernels.foo\n",
    }
    files.update(overrides)
    return {k: v for k, v in files.items() if v is not None}


def test_eco4xx_complete_kernel_package_is_clean():
    report = check_sources(_kernel_files(), select=["ECO4"])
    assert report.violations == []


def test_eco401_missing_init():
    report = check_sources(
        _kernel_files(**{"src/repro/kernels/foo/__init__.py": None}),
        select=["ECO401"])
    assert rules_of(report.violations) == ["ECO401"]
    assert report.violations[0].path.endswith("foo/__init__.py")


def test_eco402_missing_ref():
    report = check_sources(
        _kernel_files(**{"src/repro/kernels/foo/ref.py": None}),
        select=["ECO402"])
    assert rules_of(report.violations) == ["ECO402"]
    assert "ref.py" in report.violations[0].message


def test_eco403_kernel_without_parity_test():
    report = check_sources(
        _kernel_files(**{"tests/test_foo.py": "import repro.core\n"}),
        select=["ECO403"])
    assert rules_of(report.violations) == ["ECO403"]
    # no tests collected at all -> nothing to assert, no violation
    report = check_sources(
        _kernel_files(**{"tests/test_foo.py": None}), select=["ECO403"])
    assert report.violations == []


def test_eco404_oracle_importing_pallas():
    ref = "from jax.experimental import pallas as pl\n"
    report = check_sources(
        _kernel_files(**{"src/repro/kernels/foo/ref.py": ref}),
        select=["ECO404"])
    assert rules_of(report.violations) == ["ECO404"]


def test_eco405_flags_shape_guarded_impl_rewrite():
    ops = src("""
        from . import ref

        def foo(img, *, impl="auto"):
            if impl == "auto":
                from .kern import MAX_WIDTH
                impl = "pallas"
                if img.shape[-1] > MAX_WIDTH:
                    impl = "xla"
            if impl == "xla":
                return ref.foo(img)
            return img
    """)
    report = check_sources(
        _kernel_files(**{"src/repro/kernels/foo/ops.py": ops}),
        select=["ECO405"])
    assert rules_of(report.violations) == ["ECO405"]
    assert "silently falls back" in report.violations[0].message


def test_eco405_flags_shape_guarded_oracle_return():
    ops = src("""
        from . import ref

        def foo(img):
            if img.shape[-1] > 4096:
                return ref.foo(img)
            return img
    """)
    report = check_sources(
        _kernel_files(**{"src/repro/kernels/foo/ops.py": ops}),
        select=["ECO405"])
    assert rules_of(report.violations) == ["ECO405"]


def test_eco405_clean_dispatch_and_justified_fallback_pass():
    # backend choice alone (no geometry in the test) is sanctioned...
    ops = src("""
        from . import ref
        import jax

        def foo(img, *, impl="auto"):
            if impl == "auto":
                impl = "pallas" if jax.default_backend() == "tpu" else "xla"
            if impl == "xla":
                return ref.foo(img)
            return img
    """)
    report = check_sources(
        _kernel_files(**{"src/repro/kernels/foo/ops.py": ops}),
        select=["ECO405"])
    assert report.violations == []
    # ...and a justified shape fallback is suppressed, not silent
    ops = src("""
        from . import ref

        def foo(img, *, impl="auto"):
            # repro-lint: disable=ECO405 -- interpret mode cannot fit 8K
            if img.shape[-1] > 8192:
                return ref.foo(img)
            return img
    """)
    report = check_sources(
        _kernel_files(**{"src/repro/kernels/foo/ops.py": ops}),
        select=["ECO405"])
    assert report.violations == []
    assert report.suppressed == 1


# --------------------------------------------- family 5: environment pins


def test_eco501_axistype_access_and_import():
    vs = check_source(src("""
        import jax
        from jax.sharding import AxisType

        def f():
            return jax.sharding.AxisType.Auto
    """), select=["ECO501"])
    assert rules_of(vs) == ["ECO501", "ECO501"]
    # only attribute access and imports are flagged, not a getattr probe
    good = "import jax\nx = getattr(jax.sharding, 'AxisType', None)\n"
    assert check_source(good, select=["ECO501"]) == []


def test_eco502_bare_make_mesh():
    vs = check_source(src("""
        import jax
        from jax import make_mesh

        def f():
            return jax.make_mesh((1,), ("x",))
    """), select=["ECO502"])
    assert rules_of(vs) == ["ECO502", "ECO502"]


def test_eco503_hypothesis_imports():
    vs = check_source(src("""
        import hypothesis
        import hypothesis.strategies as st
        from hypothesis import given
    """), select=["ECO503"])
    assert rules_of(vs) == ["ECO503", "ECO503", "ECO503"]


# ------------------------------------------------------------ suppressions


def test_suppression_inline_and_standalone_roundtrip():
    bad = "from hypothesis import given\n"
    assert rules_of(check_source(bad, select=["ECO503"])) == ["ECO503"]

    inline = "from hypothesis import given  # repro-lint: disable=ECO503\n"
    report = check_sources({"x.py": inline}, select=["ECO503"])
    assert report.violations == [] and report.suppressed == 1

    standalone = src("""
        # repro-lint: disable=ECO503 -- exercising the shim fallback;
        # a justification block may run on before the flagged line
        from hypothesis import given
    """)
    report = check_sources({"x.py": standalone}, select=["ECO503"])
    assert report.violations == [] and report.suppressed == 1


def test_suppression_is_per_rule_and_file_wide_forms():
    # suppressing a DIFFERENT rule must not hide the finding
    wrong = "from hypothesis import given  # repro-lint: disable=ECO502\n"
    assert rules_of(check_source(wrong, select=["ECO503"])) == ["ECO503"]

    file_wide = ("# repro-lint: disable-file=ECO503\n"
                 "from hypothesis import given\n"
                 "import hypothesis\n")
    report = check_sources({"x.py": file_wide}, select=["ECO503"])
    assert report.violations == [] and report.suppressed == 2


# -------------------------------------------------------------------- CLI


def _write(tmp_path, name, text):
    p = tmp_path / name
    p.write_text(text)
    return p


def test_cli_nonzero_exit_and_text_output(tmp_path, capsys):
    bad = _write(tmp_path, "bad.py", "from hypothesis import given\n")
    assert main([str(bad)]) == 1
    out = capsys.readouterr().out
    assert "ECO503" in out and "bad.py:1:" in out


def test_cli_zero_exit_on_clean_file(tmp_path, capsys):
    clean = _write(tmp_path, "clean.py", "x = 1\n")
    assert main([str(clean)]) == 0
    assert "0 violations" in capsys.readouterr().out


def test_cli_json_schema(tmp_path, capsys):
    bad = _write(tmp_path, "bad.py", "from hypothesis import given\n")
    assert main([str(bad), "--format", "json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert set(doc) == {"version", "files", "rules", "violations",
                        "counts", "suppressed"}
    assert doc["version"] == 1
    assert doc["files"] == 1
    assert doc["counts"] == {"ECO503": 1}
    assert doc["suppressed"] == 0
    (v,) = doc["violations"]
    assert set(v) == {"rule", "path", "line", "col", "message"}
    assert (v["rule"], v["line"]) == ("ECO503", 1)


def test_cli_suppression_roundtrip(tmp_path, capsys):
    _write(tmp_path, "bad.py",
           "from hypothesis import given  # repro-lint: disable=ECO503\n")
    assert main([str(tmp_path), "--format", "json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["violations"] == [] and doc["suppressed"] == 1


def test_cli_select_and_ignore(tmp_path, capsys):
    bad = _write(tmp_path, "bad.py", "from hypothesis import given\n")
    assert main([str(bad), "--select", "ECO1"]) == 0
    capsys.readouterr()
    assert main([str(bad), "--ignore", "ECO503"]) == 0
    capsys.readouterr()
    assert main([str(bad), "--select", "ECO5"]) == 1
    capsys.readouterr()


def test_cli_list_rules_and_usage_errors(tmp_path, capsys):
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for family_rep in ("ECO101", "ECO201", "ECO301", "ECO401", "ECO501"):
        assert family_rep in out
    assert main([str(tmp_path / "nope")]) == 2


def test_cli_clean_on_this_repo(capsys):
    """The acceptance gate, in-process: the final tree lints clean."""
    paths = [str(REPO / d) for d in ("src", "tests", "benchmarks",
                                     "examples") if (REPO / d).exists()]
    assert main(paths) == 0, capsys.readouterr().out


def test_module_entrypoint_subprocess(tmp_path):
    """``python -m repro.analysis`` works and exit codes propagate."""
    bad = _write(tmp_path, "bad.py", "import jax\nx = jax.make_mesh((1,))\n")
    env = {"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"}
    r = subprocess.run([sys.executable, "-m", "repro.analysis", str(bad)],
                       capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode == 1, r.stderr
    assert "ECO502" in r.stdout


# --------------------------------------- project graph (src/repro/analysis)


def _project(named):
    from repro.analysis.engine import parse_source
    from repro.analysis.project import build_project
    sources = []
    for path, text in named.items():
        s, err = parse_source(path, textwrap.dedent(text))
        assert err is None, err
        sources.append(s)
    return build_project(sources)


def test_project_call_cycles_terminate():
    proj = _project({"src/repro/core/mod.py": """
        import threading
        _lock = threading.Lock()

        def a():
            with _lock:
                pass
            return b()

        def b():
            return a()
    """})
    fa = proj.functions["repro.core.mod:a"]
    fb = proj.functions["repro.core.mod:b"]
    reach = proj.reachable([fa])
    assert set(reach) == {"repro.core.mod:a", "repro.core.mod:b"}
    # fix-points terminate on the a <-> b cycle and still see a's lock
    assert "repro.core.mod._lock" in proj.acquired_closure(fb)
    assert proj.may_block(fa) is None


def test_project_resolves_aliased_imports():
    proj = _project({
        "src/repro/pkgx/util.py": """
            def helper():
                return 1
        """,
        "src/repro/pkgx/mainmod.py": """
            from repro.pkgx.util import helper as h

            def run():
                return h()
        """})
    (call,) = [c for c in
               proj.functions["repro.pkgx.mainmod:run"].calls
               if c.target is not None]
    assert call.target.qualname == "repro.pkgx.util:helper"


def test_project_resolves_self_methods_and_opaque_calls():
    proj = _project({"src/repro/serving/mod.py": """
        class Svc:
            def top(self):
                self.unknown_external.thing()
                return self.inner()

            def inner(self):
                return 1
    """})
    calls = proj.functions["repro.serving.mod:Svc.top"].calls
    resolved = [c.target.qualname for c in calls if c.target is not None]
    assert resolved == ["repro.serving.mod:Svc.inner"]
    # the unresolved receiver stays opaque: a call site with no edge
    assert any(c.target is None for c in calls)


# ----------------------------- family 12: transitive purity (ECO120/121)


def test_eco120_host_sync_reached_through_call_chain():
    bad = src("""
        import jax
        import numpy as np

        @jax.jit
        def entry(x):
            return helper(x)

        def helper(x):
            return np.sum(x)
    """)
    report = check_sources({CORE: bad}, select=["ECO120"], project=True)
    assert rules_of(report.violations) == ["ECO120"]
    assert "entry -> " in report.violations[0].message

    good = src("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def entry(x):
            return helper(x)

        def helper(x):
            return jnp.sum(x)
    """)
    assert check_sources({CORE: good}, select=["ECO120"],
                         project=True).violations == []


def test_eco120_without_project_flag_stays_silent():
    bad = "import numpy as np\n\ndef add_pair(s):\n    return np.sum(s)\n"
    assert check_sources({CORE: bad}, select=["ECO120"]).violations == []


def test_eco120_follows_factory_and_scan_step_chain():
    # the scan_stream shape: a factory returns a jit kernel whose step
    # function is passed to lax.scan by VALUE — a deferred edge the walk
    # must still follow into the helper
    bad = src("""
        import jax
        from jax import lax

        def _factory():
            @jax.jit
            def kernel(xs):
                def step(c, x):
                    return helper(c), x
                return lax.scan(step, 0, xs)
            return kernel

        def helper(c):
            return int(c)
    """)
    report = check_sources({CORE: bad}, select=["ECO120"], project=True)
    assert rules_of(report.violations) == ["ECO120"]
    assert "kernel -> " in report.violations[0].message


def test_eco120_transitive_root_bodies_are_scanned():
    bad = "def add_pair(state):\n    return int(state.max())\n"
    report = check_sources({CORE: bad}, select=["ECO120"], project=True)
    assert rules_of(report.violations) == ["ECO120"]


def test_eco121_impure_call_reached_through_call_chain():
    bad = src("""
        import jax
        import time

        @jax.jit
        def entry(x):
            return helper(x)

        def helper(x):
            return x * time.time()
    """)
    report = check_sources({CORE: bad}, select=["ECO121"], project=True)
    assert rules_of(report.violations) == ["ECO121"]

    good = bad.replace("time.time()", "2.0")
    assert check_sources({CORE: good}, select=["ECO121"],
                         project=True).violations == []


# ------------------------------- family 6: concurrency (ECO601/602/603)


def test_eco601_lock_order_inversion_across_calls():
    bad = src("""
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self._cond = threading.Condition()

            def a(self):
                with self._lock:
                    self.takes_cond()

            def takes_cond(self):
                with self._cond:
                    pass

            def b(self):
                with self._cond:
                    with self._lock:
                        pass
    """)
    report = check_sources({SERVING: bad}, select=["ECO601"], project=True)
    assert rules_of(report.violations) == ["ECO601"]
    assert "inversion" in report.violations[0].message

    good = bad.replace("with self._cond:\n            with self._lock:",
                       "with self._lock:\n            with self._cond:")
    assert good != bad
    assert check_sources({SERVING: good}, select=["ECO601"],
                         project=True).violations == []


def test_eco602_blocking_reachable_under_lock():
    bad = src("""
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()

            def close(self):
                with self._lock:
                    self._stop()

            def _stop(self):
                self.fut.result()
    """)
    report = check_sources({SERVING: bad}, select=["ECO602"], project=True)
    assert rules_of(report.violations) == ["ECO602"]
    assert "_stop" in report.violations[0].message

    good = src("""
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()

            def close(self):
                with self._lock:
                    fut = self.fut
                self._stop(fut)

            def _stop(self, fut):
                fut.result()
    """)
    assert check_sources({SERVING: good}, select=["ECO602"],
                         project=True).violations == []


def test_eco602_lexical_drain_under_lock_and_sanctioned_wait():
    bad = src("""
        class Cluster:
            def retire(self):
                with self._lock:
                    self.pod.drain()
    """)
    report = check_sources({SERVING: bad}, select=["ECO602"], project=True)
    assert rules_of(report.violations) == ["ECO602"]

    # Condition.wait on the lock being held is the consumer idiom
    good = src("""
        class Svc:
            def wait_done(self):
                with self._cond:
                    while not self.done:
                        self._cond.wait(0.1)
    """)
    assert check_sources({SERVING: good}, select=["ECO602"],
                         project=True).violations == []


def test_eco603_future_completed_from_thread_entry():
    bad = src("""
        import threading

        class Bridge:
            def __init__(self, loop):
                self.loop = loop
                self.fut = loop.create_future()
                threading.Thread(target=self._worker).start()

            def _worker(self):
                self._finish()

            def _finish(self):
                self.fut.set_result(1)
    """)
    report = check_sources({SERVING: bad}, select=["ECO603"], project=True)
    assert rules_of(report.violations) == ["ECO603"]
    assert "_worker" in report.violations[0].message

    good = bad.replace("self._finish()",
                       "self.loop.call_soon_threadsafe(self._finish)")
    assert check_sources({SERVING: good}, select=["ECO603"],
                         project=True).violations == []


# ------------------------------ family 7: contracts (ECO701/702/703/704)


def test_eco701_backend_conformance():
    bad = src("""
        from repro.serving.backend import register_backend

        class Bad:
            name = "bad"
            max_batch = 4

            def serve_batch(self):
                return []

            def profile_row(self):
                return {}

        register_backend("bad", Bad)
    """)
    report = check_sources({SERVING: bad}, select=["ECO701"], project=True)
    assert rules_of(report.violations) == ["ECO701"]
    assert "serve_batch" in report.violations[0].message

    duck = src("""
        class Duck:
            def serve_batch(self, requests):
                return list(requests)

            def profile_row(self):
                return {}
    """)
    report = check_sources({SERVING: duck}, select=["ECO701"], project=True)
    assert sorted(rules_of(report.violations)) == ["ECO701", "ECO701"]
    assert {("name" in v.message or "max_batch" in v.message)
            for v in report.violations} == {True}

    good = src("""
        class Good:
            def __init__(self, name):
                self.name = name
                self.max_batch = 8

            def serve_batch(self, requests):
                return list(requests)

            def profile_row(self):
                return {"name": self.name}
    """)
    assert check_sources({SERVING: good}, select=["ECO701"],
                         project=True).violations == []


def test_eco702_policy_conformance():
    bad = src("""
        class HalfPolicy:
            batchable = False

            def decide(self, request):
                return request

            def observe(self, observation):
                pass
    """)
    report = check_sources({CORE: bad}, select=["ECO702"], project=True)
    assert rules_of(report.violations) == ["ECO702", "ECO702"]

    good = src("""
        class FullPolicy:
            batchable = False

            def decide(self, request):
                return request

            def decide_batch(self, requests):
                return [None for _ in requests]

            def observe(self, observation):
                pass

            def reset(self):
                pass
    """)
    assert check_sources({CORE: good}, select=["ECO702"],
                         project=True).violations == []


def test_eco703_batchable_honesty():
    looped = src("""
        class P:
            batchable = %s

            def decide(self, request):
                return request

            def decide_batch(self, requests):
                return [self.decide(r) for r in requests]

            def observe(self, observation):
                pass

            def reset(self):
                pass
    """)
    report = check_sources({CORE: looped % "True"}, select=["ECO703"],
                           project=True)
    assert rules_of(report.violations) == ["ECO703"]
    # an honest batchable = False may loop all it wants
    assert check_sources({CORE: looped % "False"}, select=["ECO703"],
                         project=True).violations == []


def _contract_kernel(ops):
    return {
        "src/repro/kernels/foo/__init__.py": "",
        "src/repro/kernels/foo/ops.py": src(ops),
        "src/repro/kernels/foo/ref.py": src("""
            def run(x, scale=1.0):
                return x * scale
        """),
    }


def test_eco704_entry_without_oracle_dispatch():
    report = check_sources(_contract_kernel("""
        from . import ref

        def run(x):
            return x + 1
    """), select=["ECO704"], project=True)
    assert rules_of(report.violations) == ["ECO704"]
    assert "never dispatches" in report.violations[0].message


def test_eco704_signature_mismatches():
    report = check_sources(_contract_kernel("""
        from . import ref

        def run(x):
            return ref.run(x, mode=3)

        def gone(x):
            return ref.vanished(x)
    """), select=["ECO704"], project=True)
    assert rules_of(report.violations) == ["ECO704", "ECO704"]
    msgs = " | ".join(v.message for v in report.violations)
    assert "mode" in msgs and "vanished" in msgs


def test_eco704_conforming_dispatch_and_jit_alias():
    report = check_sources(_contract_kernel("""
        import jax
        from . import ref

        def run(x, scale=1.0):
            return ref.run(x, scale=scale)

        run_fast = jax.jit(ref.run)
    """), select=["ECO704"], project=True)
    assert report.violations == []

    report = check_sources(_contract_kernel("""
        import jax
        from . import ref

        def run(x):
            return ref.run(x)

        broken = jax.jit(ref.vanished)
    """), select=["ECO704"], project=True)
    assert rules_of(report.violations) == ["ECO704"]


# ----------------------------- family 9: suppression hygiene (ECO900)


def test_eco900_flags_unused_suppression():
    report = check_sources(
        {"x.py": "x = 1  # repro-lint: disable=ECO503\n"},
        select=["ECO900", "ECO503"], project=True)
    assert rules_of(report.violations) == ["ECO900"]
    assert "no ECO503 finding" in report.violations[0].message


def test_eco900_used_suppression_is_silent():
    report = check_sources(
        {"x.py": "from hypothesis import given"
                 "  # repro-lint: disable=ECO503\n"},
        select=["ECO900", "ECO503"], project=True)
    assert report.violations == [] and report.suppressed == 1


def test_eco900_unknown_id_and_blanket_marker():
    report = check_sources(
        {"x.py": "# repro-lint: disable=ECO999 -- typo\nx = 1\n"},
        select=["ECO900"], project=True)
    assert rules_of(report.violations) == ["ECO900"]
    assert "ECO999" in report.violations[0].message

    report = check_sources(
        {"x.py": "x = 1  # repro-lint: disable=all\n"},
        select=["ECO900", "ECO503"], project=True)
    assert rules_of(report.violations) == ["ECO900"]


def test_eco900_skips_ids_of_disabled_rules():
    # under --select there is no way to judge a marker for a rule that
    # did not run, so it must not be called unused
    report = check_sources(
        {"x.py": "x = 1  # repro-lint: disable=ECO503\n"},
        select=["ECO900"], project=True)
    assert report.violations == []


# ------------------------------------------- suppression parsing edges


def test_suppression_standalone_above_decorated_def():
    # ECO702 reports at the class line; the marker sits above the
    # decorator stack and must cover the decorated line too
    fixture = src("""
        import dataclasses

        # repro-lint: disable=ECO702 -- intentionally partial face
        @dataclasses.dataclass
        class Partial:
            batchable: bool = False

            def decide(self, request):
                return request

            def observe(self, observation):
                pass
    """)
    report = check_sources({CORE: fixture}, select=["ECO702"], project=True)
    assert report.violations == [] and report.suppressed == 2


def test_suppression_multiple_ids_in_one_marker():
    fixture = src("""
        import jax
        import time

        @jax.jit
        def f(x):
            # repro-lint: disable=ECO101, ECO102 -- fixture for both
            y = float(time.time())
            return x + y
    """)
    report = check_sources({CORE: fixture}, select=["ECO101", "ECO102"])
    assert report.violations == [] and report.suppressed == 2


def test_suppression_disable_file_mid_file():
    fixture = ("import hypothesis\n"
               "# repro-lint: disable-file=ECO503\n"
               "from hypothesis import given\n")
    report = check_sources({"x.py": fixture}, select=["ECO503"])
    assert report.violations == [] and report.suppressed == 2


def test_suppression_marker_inside_string_is_inert():
    fixture = ('"""docs quoting the grammar:\n\n'
               "    # repro-lint: disable-file=ECO503\n"
               '"""\n'
               "import hypothesis\n")
    report = check_sources({"x.py": fixture}, select=["ECO503"])
    assert rules_of(report.violations) == ["ECO503"]
    assert report.suppressed == 0


# ------------------------------------------------------- CLI (project era)


def test_run_paths_skips_pycache_hidden_and_non_utf8(tmp_path, capsys):
    pkg = tmp_path / "pkg"
    cache = pkg / "__pycache__"
    hidden = tmp_path / ".hidden"
    for d in (pkg, cache, hidden):
        d.mkdir()
    (pkg / "good.py").write_text("x = 1\n")
    (cache / "stale.py").write_text("from hypothesis import given\n")
    (hidden / "secret.py").write_text("from hypothesis import given\n")
    (pkg / "blob.py").write_bytes(b"\xff\xfe\x00 not utf8")
    assert main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "1 files" in out


def test_cli_format_github_annotations(tmp_path, capsys):
    bad = _write(tmp_path, "bad.py", "from hypothesis import given\n")
    assert main([str(bad), "--format", "github"]) == 1
    out = capsys.readouterr().out
    assert "::error file=" in out
    assert "line=1," in out and "ECO503" in out


def test_cli_report_file_written_regardless_of_format(tmp_path, capsys):
    bad = _write(tmp_path, "bad.py", "from hypothesis import given\n")
    dest = tmp_path / "lint-report.json"
    assert main([str(bad), "--format", "github",
                 "--report", str(dest)]) == 1
    capsys.readouterr()
    doc = json.loads(dest.read_text())
    assert doc["version"] == 1
    assert doc["counts"] == {"ECO503": 1}


def test_cli_project_flag_enables_interprocedural_rules(tmp_path, capsys):
    bad = _write(tmp_path, "mod.py", src("""
        import jax
        import numpy as np

        @jax.jit
        def entry(x):
            return helper(x)

        def helper(x):
            return np.sum(x)
    """))
    assert main([str(bad)]) == 0
    capsys.readouterr()
    assert main([str(bad), "--project"]) == 1
    assert "ECO120" in capsys.readouterr().out


def test_cli_project_clean_and_fast_on_this_repo(capsys):
    """The acceptance gate: the whole-tree interprocedural pass is clean
    and completes well inside the 5 s budget."""
    import time
    paths = [str(REPO / d) for d in ("src", "tests", "benchmarks",
                                     "examples") if (REPO / d).exists()]
    t0 = time.monotonic()
    rc = main(["--project", *paths])
    elapsed = time.monotonic() - t0
    assert rc == 0, capsys.readouterr().out
    assert elapsed < 5.0, f"--project pass took {elapsed:.2f}s"


def test_cli_list_rules_markdown_and_rules_md_drift(capsys):
    from repro.analysis.cli import catalogue_markdown
    assert main(["--list-rules", "--format", "markdown"]) == 0
    out = capsys.readouterr().out
    assert out == catalogue_markdown()
    for rid in ("ECO120", "ECO601", "ECO701", "ECO900"):
        assert rid in out
    # docs/RULES.md is generated from this exact output
    assert (REPO / "docs" / "RULES.md").read_text() == out
    # --format markdown without --list-rules is a usage error
    assert main([str(REPO / "src" / "repro" / "analysis"),
                 "--format", "markdown"]) == 2
    capsys.readouterr()
