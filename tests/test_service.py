"""EcoreService + policy layer: request-centric serving over RoutingPolicy.

Covers PoolPolicy decide/decide_batch parity, the single Observation plane,
inline full-batch flushes, drain/close semantics, and the threaded
deadline-bounded flusher (fake clock, event ordering, ZERO poll() calls,
bit-for-bit parity with solo serving)."""
import time

import numpy as np
import pytest

from repro.core.policy import Observation, PoolPolicy, RouteRequest
from repro.core.profiles import ProfileEntry, ProfileTable
from repro.serving.engine import Backend, DispatchQueue, Request, Result
from repro.serving.pool import LENGTH_BUCKETS, ServingPool
from repro.serving.service import EcoreService


def _pool(delta=5.0):
    # 'small' degrades with the bucket, 'big' holds: routing varies by length
    entries = [ProfileEntry(a, "pod", b, score - drop * b, 1.0, energy)
               for a, score, drop, energy in (("small", 80.0, 3.0, 1.0),
                                              ("big", 84.0, 1.0, 5.0))
               for _, _, b in LENGTH_BUCKETS]
    return ServingPool(ProfileTable(entries), delta=delta)


class _StubBackend:
    def __init__(self, name="stub", max_batch=4):
        self.name = name
        self.max_batch = max_batch
        self.batch_sizes = []

    def serve_batch(self, requests):
        self.batch_sizes.append(len(requests))
        return [Result(uid=r.uid, tokens=np.zeros(1, np.int32),
                       prefill_s=.01, decode_s=.01, backend=self.name,
                       batch_size=len(requests)) for r in requests]


class ManualClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance_ms(self, ms):
        self.t += ms / 1e3


def _req(uid, plen):
    return RouteRequest(uid=uid, complexity=plen, payload=np.arange(8),
                        max_new_tokens=4)


def _wait_until(pred, timeout_s=5.0):
    t0 = time.monotonic()
    while not pred():
        if time.monotonic() - t0 > timeout_s:
            raise AssertionError("condition not reached in time")
        time.sleep(0.002)


# ---------------------------------------------------------------- policies

def test_pool_policy_batch_matches_scalar():
    policy = PoolPolicy(_pool())
    assert policy.batchable is True
    reqs = [_req(i, plen) for i, plen in enumerate(
        [1, 100, 513, 2049, 8193, 32769, 600_000])]
    batch = policy.decide_batch(reqs)
    scalar = [policy.decide(r) for r in reqs]
    assert batch == scalar
    assert {d.backend for d in batch} == {"small", "big"}  # routing varied
    d = batch[0]
    assert d.pair == ("small", "pod") and d.group == 0
    assert d.energy_mwh == 1.0 and d.score == 80.0


def test_pool_policy_empty_batch():
    assert PoolPolicy(_pool()).decide_batch([]) == []


# ------------------------------------------------------- service, untimed

def test_service_full_batch_flushes_inline():
    built = []

    def factory(decision):
        be = _StubBackend(decision.backend, max_batch=2)
        built.append(be)
        return be

    service = EcoreService(PoolPolicy(_pool()), factory)
    assert service._flusher is None       # no deadline -> no thread
    futs = [service.submit(_req(i, 64)) for i in range(3)]
    assert futs[0].done() and futs[1].done()   # batch of 2 went out inline
    assert not futs[2].done()
    assert [s.result.uid for s in service.results()] == [0, 1]
    drained = service.drain()
    assert [s.result.uid for s in drained] == [2] and futs[2].done()
    assert len(built) == 1 and built[0].batch_sizes == [2, 1]
    service.close()
    with pytest.raises(RuntimeError):
        service.submit(_req(9, 64))


def test_service_submit_batch_routes_in_one_call(monkeypatch):
    scalar_decides = []
    orig = PoolPolicy.decide
    monkeypatch.setattr(PoolPolicy, "decide",
                        lambda self, r: scalar_decides.append(r.uid)
                        or orig(self, r))
    service = EcoreService(PoolPolicy(_pool()),
                           lambda d: _StubBackend(d.backend, 4))
    futs = service.submit_batch([_req(i, 64) for i in range(4)])
    assert all(f.done() for f in futs)    # one full batch, flushed inline
    assert scalar_decides == []           # tensorized path only
    assert service.stats()["serve_calls"] == 1
    service.close()


def test_service_close_flushes_pending_and_is_idempotent():
    service = EcoreService(PoolPolicy(_pool()),
                           lambda d: _StubBackend(d.backend, 8))
    fut = service.submit(_req(0, 64))
    assert not fut.done()
    service.close()
    service.close()
    assert fut.done()                     # no dangling futures
    assert [s.result.uid for s in service.results()] == [0]


def test_service_observe_plane_closes_the_loop():
    entries = [ProfileEntry(a, "pod", b, 80.0, 1.0, energy)
               for a, energy in (("small", 1.0), ("big", 5.0))
               for _, _, b in LENGTH_BUCKETS]
    pool = ServingPool(ProfileTable(entries), delta=5.0)
    service = EcoreService(PoolPolicy(pool, alpha=0.3),
                           lambda d: _StubBackend(d.backend, 1))
    assert service.submit(_req(0, 100)).result().decision.backend == "small"
    for _ in range(30):  # 'small' measured far more expensive than profiled
        service.observe(Observation(pair=("small", "pod"), energy_mwh=50.0))
    assert service.submit(_req(1, 100)).result().decision.backend == "big"
    service.close()


def test_service_duplicate_inflight_uid_is_rejected():
    service = EcoreService(PoolPolicy(_pool()),
                           lambda d: _StubBackend(d.backend, 8))
    service.submit(_req(0, 64))          # stays pending (batch of 8)
    with pytest.raises(ValueError, match="already in flight"):
        service.submit(_req(0, 64))
    service.close()


class _FailingBackend(_StubBackend):
    def serve_batch(self, requests):
        raise RuntimeError("backend exploded")


def test_service_backend_error_fails_futures_not_the_service():
    """A serve_batch error must surface on the affected futures AND the
    direct caller — and must not dangle other backends' requests."""
    def factory(decision):
        cls = _FailingBackend if decision.backend == "small" else _StubBackend
        return cls(decision.backend, max_batch=2)

    service = EcoreService(PoolPolicy(_pool()), factory)
    f0 = service.submit(_req(0, 64))             # 'small', pending
    with pytest.raises(RuntimeError, match="backend exploded"):
        service.submit(_req(1, 64))              # fills the batch -> serve
    assert isinstance(f0.exception(), RuntimeError)
    # the healthy 'big' backend still serves (long prompt -> 'big')
    f2 = service.submit(_req(2, 600_000))
    drained = service.drain()
    assert [s.result.uid for s in drained] == [2] and f2.done()
    service.close()


def test_detection_policy_observe_needs_group_or_true_complexity():
    """A quality observation with no way to place it must fail loudly (and
    group derivation from the true count must work), matching the pool
    face's per-bucket guard."""
    from repro.core.policy import DetectionPolicy
    from repro.core.router import GreedyEstimateRouter

    table = ProfileTable([ProfileEntry("m", "d", g, 50.0, 1.0, 0.1)
                          for g in range(5)])
    policy = DetectionPolicy(GreedyEstimateRouter(table, 5.0), table,
                             alpha=0.5)
    with pytest.raises(ValueError, match="per-group"):
        policy.observe(Observation(pair=("m", "d"), map_pct=10.0))
    policy.observe(Observation(pair=("m", "d"), map_pct=10.0,
                               true_complexity=7))   # -> group 4
    assert policy.table.entry(("m", "d"), 4).map_pct == 30.0
    assert policy.table.entry(("m", "d"), 0).map_pct == 50.0


def test_pool_policy_observe_derives_bucket_from_true_complexity():
    """Observation contract: group may be omitted when true_complexity is
    given — the pool face derives the bucket itself."""
    entries = [ProfileEntry(a, "pod", b, 80.0, 1.0, energy)
               for a, energy in (("small", 1.0), ("big", 5.0))
               for _, _, b in LENGTH_BUCKETS]
    policy = PoolPolicy(ServingPool(ProfileTable(entries)), alpha=0.5)
    policy.observe(Observation(pair=("small", "pod"), map_pct=0.0,
                               true_complexity=1024))
    assert policy.pool.table.entry(("small", "pod"), 1).map_pct == 40.0
    assert policy.pool.table.entry(("small", "pod"), 0).map_pct == 80.0


# --------------------------------------------------- threaded deadline flush

@pytest.mark.threads
def test_threaded_flusher_serves_deadline_expired_partial_batch(monkeypatch):
    """Event ordering under a fake clock: nothing is served before
    max_wait_ms, the partial batch goes out right after the deadline
    expires, and NOBODY calls cooperative poll()."""
    def no_poll(self):
        raise AssertionError("cooperative poll() must never be called")
    monkeypatch.setattr(DispatchQueue, "poll", no_poll)

    clock = ManualClock()
    be = _StubBackend(max_batch=4)
    service = EcoreService(PoolPolicy(_pool()), lambda d: be,
                           max_wait_ms=50.0, clock=clock)
    futs = [service.submit(_req(i, 64)) for i in range(2)]
    assert not any(f.done() for f in futs)  # 2/4: waiting for the batch

    clock.advance_ms(49.9)
    service.wake()
    passes = service.flusher_passes
    _wait_until(lambda: service.flusher_passes > passes + 1)
    assert not any(f.done() for f in futs)  # deadline not reached yet
    assert service.deadline_flushes == 0

    clock.advance_ms(0.2)                   # oldest waited past 50 ms
    service.wake()
    served = [f.result(timeout=5.0) for f in futs]
    assert [s.result.uid for s in served] == [0, 1]
    assert be.batch_sizes == [2]            # ONE partial flush
    assert service.deadline_flushes == 1
    stats = service.stats()
    assert stats["serve_calls"] == 1 and stats["served"] == 2
    service.close()


@pytest.mark.threads
def test_flusher_thread_survives_backend_errors():
    """A backend blowing up during a deadline flush must fail that batch's
    futures, not kill the flusher — later deadlines still get served."""
    def factory(decision):
        cls = _FailingBackend if decision.backend == "small" else _StubBackend
        return cls(decision.backend, max_batch=4)

    clock = ManualClock()
    service = EcoreService(PoolPolicy(_pool()), factory,
                           max_wait_ms=50.0, clock=clock)
    bad = service.submit(_req(0, 64))            # -> failing 'small'
    good = service.submit(_req(1, 600_000))      # -> healthy 'big'
    clock.advance_ms(51)
    service.wake()
    assert isinstance(bad.exception(timeout=5.0), RuntimeError)
    assert good.result(timeout=5.0).result.uid == 1
    assert service.deadline_flushes == 2
    assert service._flusher.is_alive()       # survived the backend error
    # a results()-driven driver must not lose the batch silently: the
    # swallowed background error resurfaces at drain()
    with pytest.raises(RuntimeError, match="backend exploded"):
        service.drain()
    assert [s.result.uid for s in service.results()] == [1]
    service.close()                          # error consumed: closes clean


@pytest.mark.threads
def test_threaded_flush_results_match_solo_serving(monkeypatch):
    """A deadline-flushed batch must return bit-for-bit the tokens solo
    serving returns (equal-length prompts: one homogeneous serve_batch)."""
    def no_poll(self):
        raise AssertionError("cooperative poll() must never be called")
    monkeypatch.setattr(DispatchQueue, "poll", no_poll)

    from repro.configs import get_config
    cfg = get_config("qwen2.5-3b").reduced()
    be = Backend("qwen", cfg, max_batch=4, max_seq=64)
    clock = ManualClock()
    service = EcoreService(PoolPolicy(_pool()), lambda d: be,
                           max_wait_ms=20.0, clock=clock)
    futs = [service.submit(RouteRequest(uid=i, complexity=64,
                                        payload=np.arange(7) * (i + 1),
                                        max_new_tokens=3))
            for i in range(3)]
    assert not any(f.done() for f in futs)
    clock.advance_ms(21)
    service.wake()
    served = [f.result(timeout=120.0) for f in futs]
    assert service.deadline_flushes == 1
    for s in served:
        assert s.result.batch_size == 3
        solo = be.serve_batch([Request(uid=s.request.uid,
                                       prompt=s.request.payload,
                                       max_new_tokens=3)])[0]
        np.testing.assert_array_equal(s.result.tokens, solo.tokens)
    service.close()


# --------------------------------------- structured close + error planes

def test_service_closed_is_structured_and_terminal():
    from repro.serving.service import ServiceClosed

    service = EcoreService(PoolPolicy(_pool()),
                           lambda d: _StubBackend(d.backend, max_batch=4))
    fut = service.submit(_req(0, 64))
    service.close()                      # flushes: the future resolves
    assert fut.result(5.0).result.uid == 0
    service.close()                      # idempotent
    with pytest.raises(ServiceClosed):
        service.submit(_req(1, 64))
    with pytest.raises(ServiceClosed):
        service.submit_batch([_req(1, 64)])
    with EcoreService(PoolPolicy(_pool()),
                      lambda d: _StubBackend(d.backend)) as ctx:
        pass
    with pytest.raises(ServiceClosed):   # __exit__ closed it
        ctx.submit(_req(2, 64))


@pytest.mark.threads
def test_buffer_errors_toggle_controls_drain_reraise():
    """buffer_errors=True (results()-driven drivers): a flusher-swallowed
    backend error resurfaces at drain().  buffer_errors=False (futures-only
    drivers): the futures already carry it — drain stays silent instead of
    double-reporting."""
    def factory(decision):
        cls = _FailingBackend if decision.backend == "small" else _StubBackend
        return cls(decision.backend, max_batch=4)

    for buffered in (True, False):
        clock = ManualClock()
        service = EcoreService(PoolPolicy(_pool()), factory,
                               max_wait_ms=50.0, clock=clock,
                               buffer_errors=buffered)
        bad = service.submit_batch([_req(0, 64), _req(1, 64)])  # 'small'
        clock.advance_ms(51)
        service.wake()
        for f in bad:                      # futures carry it either way
            assert isinstance(f.exception(timeout=5.0), RuntimeError)
        if buffered:
            with pytest.raises(RuntimeError, match="backend exploded"):
                service.drain()
            service.close()                # error consumed: closes clean
        else:
            assert service.drain() == []   # no re-raise, no double report
            service.close()

