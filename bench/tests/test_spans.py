"""The program spans' reduction (``bench/harness/spans.py``) and the four
readers built on it, on a synthetic trace with nested program spans on two
threads, on the recorded offline slice (a trace without program spans),
on a recorded slice of the code cell with program spans, and on a
profiler file written here on the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests/test_spans.py -q
"""
from __future__ import annotations

import json
import pathlib
import sys
from types import SimpleNamespace

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench.harness import common, spans  # noqa: E402
from bench.harness import trace as tracing  # noqa: E402

A, B = "/host:CPU#1", "/host:CPU#2"     # the submitting thread, the flusher


def _s(name, t0, t1, thread=A, **meta):
    return (name, float(t0), float(t1 - t0), thread, meta)


#: ns; device busy [0,100] [300,400] [600,700] [900,950] [1050,1070]
#: [1150,1200] in a 1,200 ns window, so the gaps are G1 (100,300),
#: G2 (400,600), G3 (700,900), G4 (950,1050) and G5 (1070,1150): 780 ns
TRACE = tracing.Trace(
    {0: [("op", a, b - a) for a, b in ((0, 100), (300, 400), (600, 700),
                                        (900, 950), (1050, 1070),
                                        (1150, 1200))]},
    {0: []},
    [("bench.window", 0.0, 1200.0), ("bench.await_arrival", 940.0, 120.0),
     ("bench.await_arrival", 1060.0, 100.0)])
PROGRAM = [
    # an inline full-batch flush on the submitting thread, over G1
    _s("ecore.submit", 50, 350, uid=7), _s("ecore.route", 60, 80),
    _s("ecore.flush", 120, 340, trigger="full"),
    _s("ecore.serve_batch", 130, 335, batch=2, max_new=3, tokens_asked=4),
    _s("ecore.pad", 140, 200), _s("ecore.prefill", 200, 310),
    _s("ecore.gather", 315, 330),
    # the flusher holds a partial batch, then flushes it, over G2
    _s("ecore.hold", 380, 450, B), _s("ecore.hold", 450, 560, B),
    _s("ecore.flush", 560, 650, B, trigger="deadline"),
    _s("ecore.serve_batch", 565, 645, B, batch=1, max_new=5,
       tokens_asked=5),
    _s("ecore.decode_step", 570, 580, B),
    # a submit that serves nothing, in G3; a short hold in G4
    _s("ecore.submit", 720, 880, uid=8), _s("ecore.route", 760, 770),
    _s("ecore.hold", 1000, 1040, B),
]
#: engine: G1 [130,300] + G2 [565,600]; submit: G1 [100,130] + G2
#: [560,565] + G3 [720,880]; held: G2 [400,560] + G4 [1000,1040]
PARTS = {"engine": 170 + 35, "submit": 30 + 5 + 160, "held": 160 + 40,
         "other": 40 + 60 + 80}


def test_idle_partition_sums_to_device_idle_by_priority():
    part = spans.idle_partition(TRACE, PROGRAM)
    assert part == pytest.approx(PARTS)
    busy, window = tracing.busy_ns(TRACE)
    idle = (1 - busy / window) * 100
    shares = spans.idle_shares(TRACE, PROGRAM)
    assert abs(sum(shares.values()) - idle) < 0.1
    assert shares["engine"] == pytest.approx(205 / 12)


def test_gaps_are_named_by_the_class_and_innermost_span_that_took_most():
    named = spans.named_gaps(TRACE, PROGRAM)
    assert [n for n, _ in named] == [
        "ecore.prefill",        # G1: engine 170 ns, prefill's self 100
        "ecore.hold",           # G2: held 160 ns against engine 35
        "ecore.submit",         # G3: submit side 160 ns, its self 150
        "bench.await_arrival",  # G4: no program span 60 ns, held 40
        "bench.await_arrival"]  # G5: no program span at all
    assert [s for _, s in named] == pytest.approx(
        [2e-7, 2e-7, 2e-7, 1e-7, 8e-8])
    # without program spans, the gaps read as trace.idle_gaps reads them
    assert spans.named_gaps(TRACE, []) == tracing.idle_gaps(TRACE)


def test_recorded_slice_reads_as_before(monkeypatch, tmp_path):
    with open(ROOT / "bench/tests/data/offline_trace_slice.json") as f:
        tr = tracing.Trace.from_json(json.load(f))
    monkeypatch.setattr(common, "OUT", tmp_path)
    assert spans.for_trace(tr) is None
    assert spans.named_gaps(tr, []) == tracing.idle_gaps(tr)
    busy, window = tracing.busy_ns(tr)
    part = spans.idle_partition(tr, [])
    assert part["engine"] == part["submit"] == part["held"] == 0
    assert part["other"] == pytest.approx(window - busy)


def test_recorded_code_slice_puts_the_wait_for_a_deadline_in_held():
    """490 ms of a traced ``qwen2.5-3b.code`` run on one TPU v5e, around
    its longest gap: the generator sleeps with nothing queued, then one
    request is held for its 100 ms deadline while the chip idles, and the
    deadline flush serves it (busy intervals and the spans near them)."""
    with open(ROOT / "bench/tests/data/code_program_slice.json") as f:
        d = json.load(f)
    tr = tracing.Trace.from_json(d["trace"])
    program = [tuple(s) for s in d["program"]]
    busy, window = tracing.busy_ns(tr)
    part = spans.idle_partition(tr, program)
    assert sum(part.values()) == pytest.approx(window - busy)
    holds = sum(s[2] for s in program if s[0] == "ecore.hold")
    assert part["held"] == pytest.approx(holds, abs=1e6)   # within 1 ms
    flushes = [s[4] for s in program if s[0] == "ecore.flush"]
    assert all(m["trigger"] == "deadline" and m["waited_ms"] >= 100.0
               for m in flushes)
    assert spans.named_gaps(tr, program, top=1)[0][0] == (
        "bench.await_arrival")


READS = {"idle_engine.offline": 205 / 12, "idle_engine.code": 205 / 12,
         "idle_held.code": 200 / 12,
         "decode_slots_used.code": 9 / 11 * 100}


@pytest.mark.parametrize("metric", sorted(READS))
def test_reader(monkeypatch, metric):
    manifest = common.load_json(common.ROOT / "BENCHMARK.json")
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == metric]
    (cell,) = entry["workloads"]
    readers = dict((m["name"], read) for m, read in common.metric_readers(
        manifest, cell, {entry["moves"]}))
    ctx = SimpleNamespace(trace=TRACE, counters={})
    monkeypatch.setattr(spans, "for_trace", lambda tr: PROGRAM)
    assert readers[metric](ctx) == pytest.approx(READS[metric])
    # a program without spans, as the parent of this reduction has none
    monkeypatch.setattr(spans, "for_trace", lambda tr: None)
    assert readers[metric](ctx) is None


def test_for_trace_reads_the_spans_of_the_same_run(monkeypatch, tmp_path):
    import jax

    monkeypatch.setattr(common, "OUT", tmp_path)
    trace_dir = tmp_path / "cell" / "trace"
    jax.profiler.start_trace(str(trace_dir))
    with common.span("bench.window"):
        with jax.profiler.TraceAnnotation("ecore.serve_batch", batch=2,
                                          max_new=4, tokens_asked=5):
            pass
    jax.profiler.stop_trace()
    tr = tracing.load(str(trace_dir))
    (got,) = spans.for_trace(tr)
    assert got[0] == "ecore.serve_batch"
    assert got[4] == {"batch": 2, "max_new": 4, "tokens_asked": 5}
    assert spans.decode_slots_used(tr, [got]) == pytest.approx(62.5)
    other = tracing.Trace({}, {}, [("bench.window", 0.0, 1.0)])
    assert spans.for_trace(other) is None
