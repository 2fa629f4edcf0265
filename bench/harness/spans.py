"""The program's own spans in a traced run, and what they say of the
device's idle time.

The served path marks its host work with ``jax.profiler.TraceAnnotation``
spans named ``ecore.*``: ``ecore.submit`` and ``ecore.route`` in the
service, ``ecore.flush`` in the dispatch queue, ``ecore.serve_batch`` and
the engine's host loop inside it (``ecore.pad``, ``ecore.prefill``,
``ecore.decode_step``, ``ecore.decode_wait``, ``ecore.gather``),
``ecore.complete`` while futures resolve, and ``ecore.hold`` while the
flusher holds a partial batch for its deadline.  They are events of the
same ``.xplane.pb`` file as the device's ops, on the same clock.
``trace.load`` keeps only the ``bench.*`` host spans; this module reads the
``ecore.*`` ones from the file a ``Trace`` was loaded from.

A program span is ``(name, start_ns, duration_ns, thread, metadata)``;
``thread`` names the host line (one per thread), and spans on one thread
nest.  In a run of a program without such spans ``for_trace`` finds none
and returns None, and so do the metric readers that use it.

    python3 -m bench.harness.spans bench/.out/<cell>/trace

prints the idle partition, the longest gaps named by program span, and the
spans' counts and times in the window.
"""
from __future__ import annotations

import glob
import os
import sys
from typing import Dict, List, Optional, Tuple

from . import common
from . import trace as tracing

Span = Tuple[str, float, float, str, Dict]
Interval = Tuple[float, float]

ENGINE = "ecore.serve_batch"
HOLD = "ecore.hold"
CLASSES = ("engine", "submit", "held", "other")


def _newest(pattern: str) -> Optional[str]:
    paths = glob.glob(pattern, recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> Tuple[Optional[Interval], List[Span]]:
    """(the ``bench.window`` interval, the ``ecore.*`` spans) of one
    ``.xplane.pb`` file, or of the newest one under a directory."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = _newest(os.path.join(path, "**", "*.xplane.pb"))
        if path is None:
            raise FileNotFoundError("no .xplane.pb under the directory")
    data = ProfileData.from_file(path)
    win, spans = None, []
    for plane in data.planes:
        if not plane.name.startswith("/host"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}#{i}"
            for e in line.events:
                if e.name.startswith("ecore."):
                    spans.append((e.name, float(e.start_ns),
                                  float(e.duration_ns), thread,
                                  dict(e.stats)))
                elif e.name == "bench.window" and (
                        win is None or e.duration_ns > win[1] - win[0]):
                    win = (float(e.start_ns),
                           float(e.start_ns) + float(e.duration_ns))
    return win, spans


_cache: Dict[Tuple[str, float], Tuple[Optional[Interval], List[Span]]] = {}


def for_trace(tr: Optional[tracing.Trace]) -> Optional[List[Span]]:
    """The program spans of the run ``tr`` was loaded from: the newest
    trace file under the benchmark's output directory, if its
    ``bench.window`` is ``tr``'s.  None without a trace, where there is no
    such file, or where it holds no program span."""
    if tr is None:
        return None
    path = _newest(str(common.OUT / "*" / "trace" / "**" / "*.xplane.pb"))
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = load(path)
    win, spans = _cache[key]
    if win != tracing.window(tr) or not spans:
        return None
    return spans


# ------------------------------------------------------------- intervals

def _measure(iv: List[Interval]) -> float:
    return sum(b - a for a, b in iv)


def _intersect(x: List[Interval], y: List[Interval]) -> List[Interval]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if b > a:
            out.append((a, b))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(x: List[Interval], y: List[Interval]) -> List[Interval]:
    """``x`` less ``y``, both sorted lists of disjoint intervals."""
    out, j = [], 0
    for a, b in x:
        while j < len(y) and y[j][1] <= a:
            j += 1
        k = j
        while k < len(y) and y[k][0] < b:
            if y[k][0] > a:
                out.append((a, y[k][0]))
            a = max(a, y[k][1])
            k += 1
        if b > a:
            out.append((a, b))
    return out


def _cover(spans: List[Span], lo: float, hi: float) -> List[Interval]:
    return tracing.union(tracing.clip([s[:3] for s in spans], lo, hi))


def gaps(tr: tracing.Trace, device: int = 0) -> List[Interval]:
    """The window's intervals in which no op ran on ``device``."""
    lo, hi = tracing.window(tr)
    busy = tracing.union(tracing.clip(tr.ops.get(device, []), lo, hi))
    return _subtract([(lo, hi)], busy)


# ------------------------------------------------------------- structure

def _nest(spans: List[Span]):
    """For each span (by index): its class (``engine`` for
    ``ecore.serve_batch`` and what nests in it, ``held`` for ``ecore.hold``,
    ``submit`` for the rest) and its self intervals (its interval less its
    children's on its thread)."""
    kind = [""] * len(spans)
    children: List[List[Interval]] = [[] for _ in spans]
    by_thread: Dict[str, List[int]] = {}
    for i, s in enumerate(spans):
        by_thread.setdefault(s[3], []).append(i)
    for idx in by_thread.values():
        idx.sort(key=lambda i: (spans[i][1], -spans[i][2]))
        stack: List[int] = []
        for i in idx:
            name, t, d = spans[i][:3]
            while stack and spans[stack[-1]][1] + spans[stack[-1]][2] <= t:
                stack.pop()
            parent = stack[-1] if stack else None
            if parent is not None:
                children[parent].append((t, t + d))
            if name == ENGINE or (parent is not None
                                  and kind[parent] == "engine"):
                kind[i] = "engine"
            else:
                kind[i] = "held" if name == HOLD else "submit"
            stack.append(i)
    selfs = [_subtract([(s[1], s[1] + s[2])], tracing.union(
        [("", a, b - a) for a, b in children[i]]))
        for i, s in enumerate(spans)]
    return kind, selfs


# ------------------------------------------------------------- readings

def idle_partition(tr: tracing.Trace, spans: List[Span],
                   device: int = 0) -> Dict[str, float]:
    """The window's idle ns on ``device``, split by the host work that
    overlapped it, in this order of priority: ``engine`` (inside an
    ``ecore.serve_batch`` span), ``submit`` (inside any other program span
    but ``ecore.hold``), ``held`` (inside ``ecore.hold``: the flusher held
    a partial batch for its deadline), ``other``.  The four sum to the
    window's idle time."""
    kind, _ = _nest(spans)
    return _split(gaps(tr, device), _covers(tr, spans, kind))


def _covers(tr: tracing.Trace, spans: List[Span],
            kind: List[str]) -> Dict[str, List[Interval]]:
    """The window's intervals covered by each class's spans."""
    lo, hi = tracing.window(tr)
    return {cls: _cover([s for s, k in zip(spans, kind) if k == cls],
                        lo, hi) for cls in CLASSES[:3]}


def _split(idle: List[Interval],
           covers: Dict[str, List[Interval]]) -> Dict[str, float]:
    """The measure of ``idle`` in each class, in order of priority."""
    out = {}
    for cls in CLASSES[:3]:
        out[cls] = _measure(_intersect(idle, covers[cls]))
        idle = _subtract(idle, covers[cls])
    out["other"] = _measure(idle)
    return out


def idle_shares(tr: tracing.Trace, spans: List[Span]) -> Dict[str, float]:
    """``idle_partition`` in percent of the window."""
    lo, hi = tracing.window(tr)
    return {k: v / (hi - lo) * 100.0
            for k, v in idle_partition(tr, spans).items()}


def named_gaps(tr: tracing.Trace, spans: List[Span], device: int = 0,
               top: int = 10):
    """The longest gaps with no op on ``device``, each named by what took
    most of it.  The gap's time is split as ``idle_partition`` splits the
    window; the largest class names it: within ``engine`` or ``submit``,
    the span with the most self time there (the innermost span at each
    instant), ``ecore.hold`` for ``held``, and for ``other`` the name
    ``trace.idle_gaps`` gives: the ``bench.*`` span (other than
    ``bench.window``) that overlaps the gap most, or ``no span``."""
    kind, selfs = _nest(spans)
    cover = _covers(tr, spans, kind)
    bench = [s for s in tr.spans if s[0] != "bench.window"]
    named = []
    for a, b in sorted(gaps(tr, device), key=lambda g: g[0] - g[1])[:top]:
        part = _split([(a, b)], cover)
        cls = max(CLASSES, key=lambda c: part[c])
        best, over = "no span", 0.0
        if cls == "held":
            best = HOLD
        elif cls == "other":
            for name, t0, d in bench:
                o = min(b, t0 + d) - max(a, t0)
                if o > over:
                    best, over = name, o
        else:
            share: Dict[str, float] = {}
            for s, k, own in zip(spans, kind, selfs):
                if k == cls and s[1] < b and s[1] + s[2] > a:
                    t = _intersect(own, [(a, b)])
                    if k == "submit":
                        t = _subtract(t, cover["engine"])
                    share[s[0]] = share.get(s[0], 0.0) + _measure(t)
            best = max(share, key=share.get)
        named.append([best, (b - a) / 1e9])
    return named


def decode_slots_used(tr: tracing.Trace,
                      spans: List[Span]) -> Optional[float]:
    """Tokens asked for over decode slots run, in percent, of the
    ``serve_batch`` calls that began in the traced window: the engine's
    own counts, which its ``ecore.serve_batch`` span carries (``batch`` x
    ``max_new`` slots, ``tokens_asked``).  None where no call began."""
    lo, hi = tracing.window(tr)
    calls = [s[4] for s in spans if s[0] == ENGINE and lo <= s[1] < hi]
    slots = sum(m["batch"] * m["max_new"] for m in calls)
    if not slots:
        return None
    return sum(m["tokens_asked"] for m in calls) / slots * 100.0


def summary(tr: tracing.Trace, spans: List[Span]) -> Dict[str, List]:
    """Per program span name in the window: [count, total s, self s]."""
    lo, hi = tracing.window(tr)
    _, selfs = _nest(spans)
    out: Dict[str, List] = {}
    for s, own in zip(spans, selfs):
        if lo <= s[1] < hi:
            row = out.setdefault(s[0], [0, 0.0, 0.0])
            row[0] += 1
            row[1] += s[2] / 1e9
            row[2] += _measure(own) / 1e9
    return out


def main(argv=None) -> int:
    import json

    trace_dir = (argv or sys.argv[1:])[0]
    tr = tracing.load(trace_dir)
    _, spans = load(trace_dir)
    busy, win = tracing.busy_ns(tr)
    for k, v in (("window_s", win / 1e9),
                 ("device_idle", (1.0 - busy / win) * 100.0),
                 ("idle_shares", idle_shares(tr, spans)),
                 ("decode_slots_used", decode_slots_used(tr, spans)),
                 ("named_gaps", named_gaps(tr, spans, top=15)),
                 ("spans", summary(tr, spans))):
        print(f"{k}: {json.dumps(v)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
