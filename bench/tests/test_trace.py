"""The trace reduction on a small recorded trace.

``data/offline_trace_slice.json`` is 188 ms of a traced run of
``qwen2.5-3b.offline`` on one TPU v5e: the end of one batch, its
successor's prefill and four decode steps (the ops line keeps only the
outermost ops; nested ones add nothing to the busy union).  Each check
below recomputes its number by a plainer method.

    JAX_PLATFORMS=cpu python3 -m pytest bench/tests/test_trace.py -q
"""
from __future__ import annotations

import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench.harness import trace as tracing  # noqa: E402

PREFILL = "jit__lambda(17631032556203703466)"
DECODE = "jit__lambda(7341182485874647134)"


@pytest.fixture(scope="module")
def tr():
    with open(ROOT / "bench/tests/data/offline_trace_slice.json") as f:
        return tracing.Trace.from_json(json.load(f))


def test_busy_is_the_union_of_op_intervals(tr):
    lo, hi = tracing.window(tr)
    # a microsecond timeline, marked op by op
    t = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for _, s, d in tr.ops[0]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            t[int((a - lo) // 1000):int((b - lo) // 1000)] = True
    busy, window = tracing.busy_ns(tr)
    assert window == hi - lo
    assert abs(busy / 1e3 - t.sum()) <= len(tr.ops[0]) + 1


def test_prefill_and_decode_told_apart(tr):
    kinds = tracing.llm_step_kinds(tr)
    assert kinds == {PREFILL: "prefill", DECODE: "decode"}
    names = [n for n, _, _ in tracing.launches(tr)]
    assert names.count(PREFILL) == 1
    sec, n = tracing.step_time(tr, "decode")
    assert n == names.count(DECODE) >= 4
    lo, hi = tracing.window(tr)
    want = sum(min(s + d, hi) - max(s, lo) for m, s, d in tr.modules[0]
               if m == DECODE)
    assert sec == pytest.approx(want / 1e9)


def test_named_steps_win_over_the_order_rule():
    tr = tracing.Trace({0: []}, {0: [("jit_decode_step", 10, 1),
                                     ("jit_prefill", 20, 1),
                                     ("jit_decode_step", 30, 1)]},
                       [("bench.window", 0, 100)])
    assert tracing.llm_step_kinds(tr) == {"jit_decode_step": "decode",
                                          "jit_prefill": "prefill"}


def test_idle_gaps_are_named_by_the_host_span(tr):
    gaps = tracing.idle_gaps(tr, top=3)
    assert len(gaps) == 3
    assert gaps[0][1] >= gaps[1][1] >= gaps[2][1] > 0
    busy, window = tracing.busy_ns(tr)
    total = sum(b - a for a, b in _gaps(tr))
    assert total == pytest.approx(window - busy)


def _gaps(tr):
    lo, hi = tracing.window(tr)
    t, out = lo, []
    for a, b in tracing.union(tracing.clip(tr.ops[0], lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def test_top_ops_lead_with_the_decode_loop(tr):
    top = tracing.top_ops(tr, top=3)
    assert top[0][0].startswith("%while")
    assert top[0][1] >= top[1][1] >= top[2][1]
