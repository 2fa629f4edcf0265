"""From a profiler trace to the numbers the per-layer readers take.

A traced run wraps its traced sub-window in the host span ``bench.window``
and its own steps in other ``bench.*`` spans.  ``load`` reads the
``.xplane.pb`` file into plain event lists (``Trace``), which can also be
saved as JSON; everything after that is plain Python over those lists, so
``bench/tests/test_trace.py`` checks it on a small recorded trace.

* device ops: the events of the ``XLA Ops`` line of every ``/device:TPU:n``
  plane; the busy time is the union of their intervals;
* device modules: the ``XLA Modules`` line, one event per program launch;
* host spans: the ``bench.*`` events of the host planes.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Tuple

Event = Tuple[str, float, float]  # (name, start_ns, duration_ns)


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]       # device id -> XLA ops
    modules: Dict[int, List[Event]]   # device id -> program launches
    spans: List[Event]                # bench.* host spans

    def to_json(self) -> Dict:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "modules": {str(k): v for k, v in self.modules.items()},
                "spans": self.spans}

    @classmethod
    def from_json(cls, d: Dict) -> "Trace":
        def conv(m):
            return {int(k): [tuple(e) for e in v] for k, v in m.items()}
        return cls(conv(d["ops"]), conv(d["modules"]),
                   [tuple(e) for e in d["spans"]])


_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m is not None and line.name in ("XLA Ops", "XLA Modules"):
                dst = ops if line.name == "XLA Ops" else modules
                dst.setdefault(int(m.group(1)), []).extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events)
            elif m is None and plane.name.startswith("/host"):
                spans.extend((e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events
                             if e.name.startswith("bench."))
    return Trace(ops, modules, spans)


def window(tr: Trace) -> Tuple[float, float]:
    """(start_ns, end_ns) of the traced sub-window (``bench.window``)."""
    w = [s for s in tr.spans if s[0] == "bench.window"]
    if not w:
        raise ValueError("the trace holds no bench.window span")
    _, t, d = max(w, key=lambda s: s[2])
    return t, t + d


def clip(events: List[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to [lo, hi]; those wholly outside dropped."""
    out = []
    for name, t, d in events:
        a, b = max(t, lo), min(t + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events: List[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals covered by the events."""
    iv = sorted((t, t + d) for _, t, d in events if d > 0)
    out: List[List[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(tr: Trace) -> Tuple[float, float]:
    """(busy ns averaged over the devices, window ns)."""
    lo, hi = window(tr)
    devs = sorted(tr.ops) or [0]
    busy = [sum(b - a for a, b in union(clip(tr.ops.get(d, []), lo, hi)))
            for d in devs]
    return sum(busy) / len(busy), hi - lo


def idle_gaps(tr: Trace, device: int = 0, top: int = 10):
    """The longest gaps with no op on ``device``, each named by the
    ``bench.*`` host span (other than ``bench.window``) that overlaps it
    most, or ``no span``."""
    lo, hi = window(tr)
    busy = union(clip(tr.ops.get(device, []), lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = [s for s in tr.spans if s[0] != "bench.window"]
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, over = "no span", 0.0
        for name, t0, d in spans:
            o = min(b, t0 + d) - max(a, t0)
            if o > over:
                best, over = name, o
        named.append([best, (b - a) / 1e9])
    return named


def top_ops(tr: Trace, device: int = 0, top: int = 10, width: int = 72):
    """The device ops that took most time in the window, grouped by the
    first ``width`` characters of their names, in seconds."""
    lo, hi = window(tr)
    tot: Dict[str, float] = {}
    for name, _, d in clip(tr.ops.get(device, []), lo, hi):
        k = name[:width]
        tot[k] = tot.get(k, 0.0) + d
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def launches(tr: Trace, device: int = 0) -> List[Event]:
    """Program launches on ``device`` inside the window, in time order."""
    lo, hi = window(tr)
    return sorted(clip(tr.modules.get(device, []), lo, hi),
                  key=lambda e: e[1])


def llm_step_kinds(tr: Trace, device: int = 0) -> Dict[str, str]:
    """Tell the model's prefill programs from its decode programs.

    A program whose name says ``prefill`` or ``decode`` is that.  Today
    both steps are jitted lambdas (``jit__lambda(<fingerprint>)``), so the
    rule falls back on the order of launches: a decode program runs once
    per generated token, so two of its launches follow each other; a
    prefill program runs once per batch, between decode steps.  Programs
    named ``argmax`` (the host's token pick) are neither.  Needs at least
    three new tokens per request."""
    seq = [name for name, _, _ in launches(tr, device)
           if "argmax" not in name]
    kinds: Dict[str, str] = {}
    for name in set(seq):
        low = name.lower()
        if "prefill" in low:
            kinds[name] = "prefill"
        elif "decode" in low:
            kinds[name] = "decode"
    for a, b in zip(seq, seq[1:]):
        if a == b and a not in kinds:
            kinds[a] = "decode"
    for name in set(seq):
        kinds.setdefault(name, "prefill")
    return kinds


def step_time(tr: Trace, kind: str, device: int = 0) -> Tuple[float, int]:
    """(device seconds, launches) of the model's ``kind`` programs."""
    kinds = llm_step_kinds(tr, device)
    sel = [d for name, _, d in launches(tr, device)
           if kinds.get(name) == kind]
    return sum(sel) / 1e9, len(sel)


def save(tr: Trace, path: str) -> None:
    """Write the event lists as JSON (``Trace.from_json`` reads them)."""
    with open(path, "w") as f:
        json.dump(tr.to_json(), f)
