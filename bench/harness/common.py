"""What every cell's run shares: the device check, the compile cache, the
set-up clock, compile counting, the result line, and the per-layer
metric readers found by name."""
from __future__ import annotations

import importlib.util
import json
import math
import os
import pathlib
import sys
import time
from typing import Dict, Optional

#: the checkout's root (this file is bench/harness/common.py)
ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"

#: where a run keeps what it writes (traces); listed in .gitignore
OUT = BENCH / ".out"

#: the persistent compile cache: a fixed path inside the checkout, handed to
#: the program through the variable its ``enable_compile_cache`` reads
CACHE_DIR = ROOT / "artifacts" / "jax_cache"


class NoDevice(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_json(path: pathlib.Path):
    with open(path) as f:
        return json.load(f)


def find_cell(name: str):
    """(cell, configuration entry, configuration file, traffic file) for
    the workload ``name`` in BENCHMARK.json."""
    manifest = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json; "
                         f"have {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = load_json(ROOT / conf["file"])
    traffic = load_json(BENCH / "workloads" / f"{cell['traffic']}.json")
    return manifest, cell, config, traffic


def set_up_jax(chips: int, rehearse: bool):
    """Import JAX with the in-checkout compile cache, and check the device.

    A measuring run needs a TPU with at least ``chips`` chips and exits
    with code 3, printing no result, otherwise.  ``rehearse`` runs on the
    CPU at a tiny size and says so in every line it prints."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    if rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program goes to the cache, also those that compile in under the
    # default second, so that a warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # no eviction: with a size limit set from outside, JAX's LRU cache scans
    # every entry on each write, and one entry left without its access-time
    # file by a killed process makes every later write fail
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    if not rehearse:
        if devices[0].platform != "tpu":
            print(f"bench: JAX found no TPU (platform "
                  f"{devices[0].platform!r}); a measuring run does not fall "
                  f"back to the CPU", file=sys.stderr)
            raise NoDevice(3)
        if len(devices) < chips:
            print(f"bench: the cell asks for {chips} chips, JAX found "
                  f"{len(devices)}", file=sys.stderr)
            raise NoDevice(3)
    return jax, devices[:chips]


class CompileCounter:
    """Counts backend compilations and persistent-cache loads, so a run can
    say how many programs its set-up built and that none was built inside
    the window."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def device_info(devices) -> Dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices),
            "memory_peak_bytes": memory_peak_bytes(devices)}


def percentile(values, q: float) -> float:
    """Exact percentile over all values (linear between order statistics);
    ``inf`` counts as a request that missed every limit."""
    import numpy as np

    v = np.sort(np.asarray(values, np.float64))
    if len(v) == 0:
        return math.inf
    return float(np.percentile(v, q, method="linear"))


def metric_readers(manifest, cell_name: str, end_to_end: set):
    """The per-layer metrics this cell reports: those that list the cell
    under ``workloads``, or that have no such key and move an end-to-end
    metric the cell reports.  Each is read by ``bench/metrics/<name>.py``."""
    out = []
    for m in manifest["per_layer"]:
        cells = m.get("workloads")
        if cells is None and m["moves"] not in end_to_end:
            continue
        if cells is not None and cell_name not in cells:
            continue
        path = BENCH / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + m["name"].replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out.append((m, mod.read))
    return out


def cell_end_to_end(manifest, cell_name: str):
    """The end-to-end metrics this cell reports."""
    return [m for m in manifest["end_to_end"]
            if m.get("workloads") is None or cell_name in m["workloads"]]


def emit(*, correct: bool, attempted: int, failed: int,
         metrics: Dict[str, Dict], device: Dict,
         compared: Dict[str, Dict], breakdown: Optional[Dict] = None) -> None:
    """Print the compared numbers as the last lines of standard error, then
    the result as the last line of standard output, ``compared`` last."""
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    print(json.dumps(out), flush=True)


class Clock:
    """The run's set-up clock, started when ``bench/run.py`` is imported."""

    def __init__(self, t0: float):
        self.t0 = t0

    def since_start(self) -> float:
        return time.perf_counter() - self.t0


def setup(cell, args):
    """JAX, checked for the cell's chips (or the CPU in a rehearsal)."""
    return set_up_jax(cell["chips"], args.rehearse)


class span:
    """A ``bench.*`` host span: a profiler annotation, so a traced run can
    name what the host did in each idle gap on the device."""

    def __init__(self, name: str):
        import jax

        self._a = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self._a.__enter__()
        return self

    def __exit__(self, *exc):
        return self._a.__exit__(*exc)


class Profiler:
    """The traced sub-window: JAX's profiler on, and a ``bench.window``
    span around it, both opened and closed from the same thread."""

    def __init__(self, trace_dir: str):
        self.dir = trace_dir
        self.running = False
        self.done = False
        self._win = None

    def start(self) -> None:
        import shutil

        import jax

        if self.running or self.done:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self._win = span("bench.window").__enter__()
        self.running = True
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        import jax

        if not self.running:
            return
        self.t_stop = time.perf_counter()
        self._win.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.running, self.done = False, True


def read_per_layer(manifest, cell, e2e, trace, counters, shape, device):
    """Each per-layer metric of the cell, from its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    from types import SimpleNamespace

    from . import counts, peaks

    ctx = SimpleNamespace(trace=trace, counters=counters, shape=shape,
                          device=device, counts=counts,
                          peaks=peaks.peaks_for(device["kind"])
                          if device["platform"] == "tpu" else None)
    out = {}
    for m, read in metric_readers(manifest, cell["name"], set(e2e)):
        v = read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
