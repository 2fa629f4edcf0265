"""The served path's ``ecore.*`` profiler spans, their nesting and metadata,
the engine's decode-slot counters, and the step programs' names.

Every test that starts the profiler lives in this file: one process may run
one profiler at a time, and ``--dist loadfile`` gives a file to one worker.
The spans are read back from the ``.xplane.pb`` file the profiler writes,
the same file the benchmark's trace reduction reads on the chip."""
import contextlib
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.policy import PoolPolicy, RouteRequest
from repro.core.profiles import ProfileEntry, ProfileTable
from repro.serving.engine import Backend
from repro.serving.pool import LENGTH_BUCKETS, ServingPool
from repro.serving.service import EcoreService


def _policy():
    # short prompts route to 'small', very long ones to 'big'
    entries = [ProfileEntry(a, "pod", b, score - drop * b, 1.0, energy)
               for a, score, drop, energy in (("small", 80.0, 3.0, 1.0),
                                              ("big", 84.0, 1.0, 5.0))
               for _, _, b in LENGTH_BUCKETS]
    return PoolPolicy(ServingPool(ProfileTable(entries), delta=5.0))


@pytest.fixture(scope="module")
def backend():
    cfg = get_config("qwen2.5-3b").reduced()
    return Backend("qwen", cfg, max_batch=2, max_seq=32)


def _req(uid, plen, new, complexity=64):
    return RouteRequest(uid=uid, complexity=complexity,
                        payload=np.arange(plen) + uid, max_new_tokens=new)


class _Span:
    def __init__(self, thread, event):
        self.thread = thread
        self.name = event.name
        self.start = event.start_ns
        self.end = event.start_ns + event.duration_ns
        self.meta = dict(event.stats)

    def inside(self, other):
        return (self.thread == other.thread and other.start <= self.start
                and self.end <= other.end)


@contextlib.contextmanager
def _traced(tmp_path):
    """Profile the block; afterwards the list holds its ``ecore.*`` spans,
    each tagged with its host thread (one line of a host plane)."""
    spans = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield spans
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(path)
    for p, plane in enumerate(data.planes):
        if plane.name.startswith("/host"):
            for t, line in enumerate(plane.lines):
                spans += [_Span((p, t), e) for e in line.events
                          if e.name.startswith("ecore.")]
    spans.sort(key=lambda s: s.start)


def _named(spans, name):
    return [s for s in spans if s.name == name]


def test_served_path_spans_nest_and_carry_metadata(backend, tmp_path):
    service = EcoreService(_policy(), lambda d: backend)
    with _traced(tmp_path) as spans:
        f0 = service.submit(_req(0, 6, 3))
        f1 = service.submit(_req(1, 6, 2))    # fills the batch: inline flush
    assert f0.done() and f1.done()
    service.close()

    submits = _named(spans, "ecore.submit")
    assert [s.meta["uid"] for s in submits] == [0, 1]
    routes = _named(spans, "ecore.route")
    assert len(routes) == 2
    assert all(r.inside(s) for r, s in zip(routes, submits))

    (flush,) = _named(spans, "ecore.flush")
    assert flush.inside(submits[1])
    assert flush.meta["trigger"] == "full"
    assert flush.meta["requests"] == 2 and flush.meta["sub_batches"] == 1
    assert flush.meta["waited_ms"] >= 0.0

    (serve,) = _named(spans, "ecore.serve_batch")
    assert serve.inside(flush)
    assert serve.meta == {"batch": 2, "prompt_len": 6, "max_new": 3,
                          "tokens_asked": 5}
    # the engine's host loop, in order: one decode_step per token after
    # the first (which prefill gives)
    inner = [s for s in spans if s.inside(serve) and s is not serve]
    assert [s.name for s in inner] == [
        "ecore.pad", "ecore.prefill", "ecore.decode_step",
        "ecore.decode_step", "ecore.decode_wait", "ecore.gather"]
    assert all(a.end <= b.start for a, b in zip(inner, inner[1:]))

    (complete,) = _named(spans, "ecore.complete")
    assert complete.inside(submits[1]) and complete.start >= flush.end
    assert not _named(spans, "ecore.hold")    # no deadline, no flusher


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


@pytest.mark.threads
def test_deadline_flush_runs_on_the_flusher_after_its_hold(backend,
                                                           tmp_path):
    clock = _Clock()
    service = EcoreService(_policy(), lambda d: backend, max_wait_ms=50.0,
                           clock=clock)
    with _traced(tmp_path) as spans:
        fut = service.submit(_req(0, 6, 2))   # 1 of 2: held for the deadline
        t0 = time.monotonic()
        while service.flusher_passes < 3 and time.monotonic() - t0 < 5.0:
            time.sleep(0.002)
        clock.t += 0.0601
        service.wake()
        served = fut.result(timeout=60.0)
    assert served.result.batch_size == 1
    service.close()

    (submit,) = _named(spans, "ecore.submit")
    (flush,) = _named(spans, "ecore.flush")
    assert flush.thread != submit.thread      # the flusher's own thread
    assert flush.meta["trigger"] == "deadline"
    assert flush.meta["requests"] == 1
    assert flush.meta["waited_ms"] == pytest.approx(60.1)
    (serve,) = _named(spans, "ecore.serve_batch")
    assert serve.inside(flush)
    holds = [h for h in _named(spans, "ecore.hold")
             if h.thread == flush.thread]
    assert holds and max(h.end for h in holds) <= flush.start
    assert service.deadline_flushes == 1


def test_decode_slots_and_tokens_asked_are_exact(backend):
    # two routed pairs share the one backend: its counters count once
    service = EcoreService(_policy(), lambda d: backend)
    before = service.stats()
    assert before["decode_slots"] == before["tokens_asked"] == 0
    slots0, asked0 = backend.decode_slots, backend.tokens_asked
    futs = [service.submit(_req(0, 6, 1)),
            service.submit(_req(1, 6, 4)),                       # 2 x 4
            service.submit(_req(2, 5, 3, complexity=600_000)),
            service.submit(_req(3, 5, 2, complexity=600_000))]   # 2 x 3
    assert {f.result().decision.backend for f in futs} == {"small", "big"}
    assert [len(f.result().result.tokens) for f in futs] == [4, 4, 3, 3]
    assert backend.decode_slots - slots0 == 8 + 6
    assert backend.tokens_asked - asked0 == 1 + 4 + 3 + 2
    stats = service.stats()
    assert stats["decode_slots"] == backend.decode_slots
    assert stats["tokens_asked"] == backend.tokens_asked
    assert stats["backends"] == 2
    service.close()


def test_step_programs_are_named_prefill_and_decode(backend):
    tokens = jnp.zeros((2, 6), jnp.int32)
    lowered = backend._prefill.lower(backend.params, tokens, None)
    assert re.search(r"module @jit_\w*prefill", lowered.as_text())
    _, cache = jax.eval_shape(backend._prefill, backend.params, tokens,
                              None)
    token = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    lowered = backend._decode.lower(backend.params, token, cache)
    assert re.search(r"module @jit_\w*decode", lowered.as_text())

