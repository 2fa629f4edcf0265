"""EcoreCluster: jitted shard selection (exact parity vs the scalar
reference), observe() fan-in to the owning pod, aggregated stats, and
concurrent drain/close over independent pods."""
import numpy as np
import pytest
from _propcheck import given, settings, st

from repro.core.policy import Observation, PoolPolicy, RouteRequest
from repro.core.profiles import ProfileEntry, ProfileTable
from repro.serving.cluster import (EcoreCluster, select_pods,
                                   select_pods_reference)
from repro.serving.engine import Result
from repro.serving.pool import LENGTH_BUCKETS, ServingPool


def _pool(delta=5.0):
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
        return [Result(uid=r.uid, tokens=np.asarray([r.uid], np.int32),
                       prefill_s=.01, decode_s=.01, backend=self.name,
                       batch_size=len(requests)) for r in requests]

    def profile_row(self):
        return {"kind": "stub", "model": self.name,
                "max_batch": self.max_batch}


def _req(uid, plen=64):
    return RouteRequest(uid=uid, complexity=plen, payload=np.arange(8),
                        max_new_tokens=4)


# --------------------------------------------------- shard-selection parity

def test_shard_selection_batch_matches_scalar_reference():
    rng = np.random.default_rng(0)
    for pods in (1, 2, 4, 7):
        for n in (1, 5, 64):
            uids = rng.integers(0, 2**31, size=n)
            depths = rng.integers(0, 9, size=pods)
            for mode in ("least_loaded", "rendezvous"):
                got = select_pods(uids, depths, mode)
                want = select_pods_reference(uids, depths, mode)
                np.testing.assert_array_equal(got, want), (mode, pods, n)


@settings(max_examples=60, deadline=None)
@given(uids=st.lists(st.integers(0, 2**32 - 1), min_size=1, max_size=24),
       depths=st.lists(st.integers(0, 20), min_size=1, max_size=6),
       mode_idx=st.integers(0, 1))
def test_shard_selection_parity_property(uids, depths, mode_idx):
    mode = ("least_loaded", "rendezvous")[mode_idx]
    np.testing.assert_array_equal(select_pods(uids, depths, mode),
                                  select_pods_reference(uids, depths, mode))


def test_least_loaded_is_sequential_greedy():
    """Each assignment must see the depths the previous ones produced —
    a batch over equal depths round-robins instead of piling on pod 0."""
    picks = select_pods(np.arange(8), np.zeros(4, int), "least_loaded")
    assert picks.tolist() == [0, 1, 2, 3, 0, 1, 2, 3]
    # unequal start: fills the valleys first (ties -> lowest pod index)
    picks = select_pods(np.arange(3), np.asarray([2, 0, 1]), "least_loaded")
    assert picks.tolist() == [1, 1, 2]


def test_rendezvous_is_stable_and_spread():
    uids = np.arange(256)
    first = select_pods(uids, np.zeros(4, int), "rendezvous")
    second = select_pods(uids, np.ones(4, int) * 7, "rendezvous")
    np.testing.assert_array_equal(first, second)   # depth-independent
    counts = np.bincount(first, minlength=4)
    assert (counts > 32).all()                     # no pod starved
    # pod-count change reshuffles only partially (HRW affinity)
    three = select_pods(uids, np.zeros(3, int), "rendezvous")
    moved = (three != first).mean()
    assert moved < 0.5


def test_unknown_shard_mode_is_a_clear_error():
    with pytest.raises(ValueError, match="unknown shard mode"):
        select_pods([1], [0, 0], "hash_ring")
    with pytest.raises(ValueError, match="unknown shard mode"):
        EcoreCluster(lambda i: PoolPolicy(_pool()), lambda d: _StubBackend(),
                     pods=2, shard="hash_ring")


# ------------------------------------------------------------ cluster plane

def test_cluster_serves_across_pods_and_aggregates_stats():
    built = []

    def factory(decision):
        be = _StubBackend(decision.backend, max_batch=2)
        built.append(be)
        return be

    with EcoreCluster(lambda i: PoolPolicy(_pool()), factory,
                      pods=2) as cluster:
        futs = cluster.submit_batch([_req(i) for i in range(8)])
        cluster.drain()
        served = [f.result(timeout=5.0) for f in futs]
        assert [s.result.uid for s in served] == list(range(8))  # req order
        stats = cluster.stats()
    assert stats["pods"] == 2 and stats["served"] == 8
    assert sum(stats["shard_counts"]) == 8
    assert all(c == 4 for c in stats["shard_counts"])   # least-loaded split
    assert len(stats["per_pod"]) == 2
    # pods are independent: each built its own backend for the same pair
    assert len(built) == 2


def test_cluster_pods_build_backends_on_their_device():
    """Pod i builds its backends with jax.devices()[i % device_count] as
    the default device; with one device that is every pod's (unchanged
    single-device behaviour)."""
    import jax

    seen = []

    def factory(decision):
        seen.append(jax.config.jax_default_device)
        return _StubBackend(decision.backend, max_batch=2)

    with EcoreCluster(lambda i: PoolPolicy(_pool()), factory,
                      pods=3) as cluster:
        cluster.submit_batch([_req(i) for i in range(6)])
        cluster.drain()
    n = jax.device_count()
    assert sorted(d.id for d in seen) == sorted(
        jax.devices()[i % n].id for i in range(3))
    assert jax.config.jax_default_device is None   # nothing leaks out


def test_cluster_scalar_submit_matches_batch_sharding():
    """Under rendezvous (assignment depends only on the uid, not on live
    depths) the per-request path (scalar reference) and the batch path
    (jitted) must assign every uid to the SAME pod."""
    def factory(decision):
        return _StubBackend(decision.backend, max_batch=1)

    uids = list(range(9))
    expected = select_pods_reference(uids, np.zeros(3, int), "rendezvous")

    with EcoreCluster(lambda i: PoolPolicy(_pool()), factory,
                      pods=3, shard="rendezvous") as scalar_c:
        for i in uids:
            scalar_c.submit(_req(i)).result(timeout=5.0)
        scalar_owner = dict(scalar_c._owner)
        scalar_counts = scalar_c.stats()["shard_counts"]

    with EcoreCluster(lambda i: PoolPolicy(_pool()), factory,
                      pods=3, shard="rendezvous") as batch_c:
        futs = batch_c.submit_batch([_req(i) for i in uids])
        [f.result(timeout=5.0) for f in futs]
        batch_owner = dict(batch_c._owner)
        batch_counts = batch_c.stats()["shard_counts"]

    want = {u: int(p) for u, p in zip(uids, expected)}
    assert scalar_owner == batch_owner == want
    assert scalar_counts == batch_counts


def test_cluster_observe_folds_into_owning_pod():
    pools = [_pool(), _pool()]

    def factory(decision):
        # deep queues: requests stay IN FLIGHT, so least-loaded sees live
        # depths and spreads uid 0 -> pod 0, uid 1 -> pod 1
        return _StubBackend(decision.backend, max_batch=8)

    with EcoreCluster(lambda i: PoolPolicy(pools[i], alpha=1.0), factory,
                      pods=2) as cluster:
        f0 = cluster.submit(_req(0))         # pod 0 (least loaded, tie -> 0)
        f1 = cluster.submit(_req(1))         # pod 1 (pod 0 busy)
        cluster.drain()
        assert f0.result(5.0) and f1.result(5.0)
        # uid-tagged: folds ONLY into the owning pod's policy
        cluster.observe(Observation(pair=("small", "pod"), uid=1,
                                    energy_mwh=99.0))
        assert pools[1].table.entry(("small", "pod"), 0).energy_mwh == 99.0
        assert pools[0].table.entry(("small", "pod"), 0).energy_mwh == 1.0
        # un-tagged: pair-wide evidence broadcasts to every pod
        cluster.observe(Observation(pair=("small", "pod"), energy_mwh=50.0))
        assert pools[0].table.entry(("small", "pod"), 0).energy_mwh == 50.0
        assert pools[1].table.entry(("small", "pod"), 0).energy_mwh == 50.0
        # uid-tagged but owner unknown: DROPPED (counted), never smeared
        # across every pod as if it were pair-wide evidence
        cluster.observe(Observation(pair=("small", "pod"), uid=999,
                                    energy_mwh=0.001))
        assert pools[0].table.entry(("small", "pod"), 0).energy_mwh == 50.0
        assert pools[1].table.entry(("small", "pod"), 0).energy_mwh == 50.0
        assert cluster.stats()["stale_observations"] == 1


class _FailingBackend(_StubBackend):
    def serve_batch(self, requests):
        raise RuntimeError("backend exploded")


def test_cluster_submit_error_does_not_leak_depth():
    """A failing inline flush on the scalar path must un-count the request,
    or least-loaded routes away from the pod for the cluster's lifetime."""
    with EcoreCluster(lambda i: PoolPolicy(_pool()),
                      lambda d: _FailingBackend(d.backend, max_batch=1),
                      pods=2) as cluster:
        with pytest.raises(RuntimeError, match="backend exploded"):
            cluster.submit(_req(0))
        assert cluster._depth.tolist() == [0, 0]   # no phantom load


def test_cluster_rejects_bad_pod_count():
    with pytest.raises(ValueError, match="at least one pod"):
        EcoreCluster(lambda i: PoolPolicy(_pool()), lambda d: _StubBackend(),
                     pods=0)


def test_cluster_drain_flushes_partial_batches_everywhere():
    def factory(decision):
        return _StubBackend(decision.backend, max_batch=8)

    with EcoreCluster(lambda i: PoolPolicy(_pool()), factory,
                      pods=2) as cluster:
        futs = cluster.submit_batch([_req(i) for i in range(5)])
        assert not any(f.done() for f in futs)   # 8-deep queues: all pending
        drained = cluster.drain()
        assert len(drained) == 5
        assert all(f.done() for f in futs)


# ------------------------------------------------- graceful degradation

from repro.core.policy import RouteDecision  # noqa: E402
from repro.serving.cluster import NoLivePods  # noqa: E402


class _PinnedPolicy:
    """Per-pod policy that routes everything to ONE fixed pair — the model
    name encodes the pod, so a Served's backend identifies who served it."""
    batchable = True

    def __init__(self, pair):
        self.pair = pair
        self.observed = []

    def decide(self, req):
        return RouteDecision(uid=req.uid, pair=self.pair, group=0)

    def decide_batch(self, reqs):
        return [self.decide(r) for r in reqs]

    def observe(self, obs):
        self.observed.append(obs)


def test_shard_selection_masked_parity_and_avoids_dead():
    rng = np.random.default_rng(1)
    for pods in (2, 4, 7):
        alive = np.ones(pods, bool)
        alive[0] = False
        uids = rng.integers(0, 2**31, size=40)
        depths = rng.integers(0, 9, size=pods)
        for mode in ("least_loaded", "rendezvous"):
            got = select_pods(uids, depths, mode, alive=alive)
            want = select_pods_reference(uids, depths, mode, alive=alive)
            np.testing.assert_array_equal(got, want), (mode, pods)
            assert alive[got].all()          # never a dead pod
    # alive=None is the original unmasked kernel, bit-identical to seed
    uids = rng.integers(0, 2**31, size=64)
    for mode in ("least_loaded", "rendezvous"):
        np.testing.assert_array_equal(
            select_pods(uids, np.zeros(4, int), mode, alive=None),
            select_pods(uids, np.zeros(4, int), mode))


def test_mark_pod_failed_masks_shard_selection():
    with EcoreCluster(lambda i: PoolPolicy(_pool()),
                      lambda d: _StubBackend(d.backend, max_batch=1),
                      pods=2) as cluster:
        cluster.mark_pod_failed(0)
        futs = cluster.submit_batch([_req(u) for u in range(6)])
        cluster.drain()
        assert all(f.exception() is None for f in futs)
        stats = cluster.stats()
        assert stats["alive"] == [False, True]
        assert stats["availability"] == 0.5
        assert cluster.shard_counts.tolist()[0] == 0   # all on pod 1


@pytest.mark.threads
def test_cluster_masks_failed_pod_and_resubmits_inflight():
    """Pod 0's device dies outright; after ``pod_fail_after`` consecutive
    errors the pod is masked out, its failed in-flight requests move to
    survivors, and uid-keyed observations follow the move."""
    n, fail_after = 40, 2
    policies = [_PinnedPolicy((f"m{i}", "dead" if i == 0 else "ok"))
                for i in range(3)]

    def backend_factory(decision):
        cls = (_FailingBackend if decision.pair[1] == "dead"
               else _StubBackend)
        return cls(decision.backend, max_batch=1)

    cluster = EcoreCluster(lambda i: policies[i], backend_factory,
                           pods=3, pod_fail_after=fail_after)
    futs = cluster.submit_batch([_req(u) for u in range(n)])
    cluster.drain()
    served = [f.result(5.0) for f in futs if f.exception() is None]
    stats = cluster.stats()
    # at most fail_after - 1 requests may fail before detection trips
    assert len(served) >= n - (fail_after - 1)
    assert stats["alive"] == [False, True, True]
    assert stats["availability"] == pytest.approx(2 / 3)
    assert stats["resubmitted"] >= 1
    assert not any(s.result.backend == "m0" for s in served)
    # Observation fan-in after the move: the owner map follows the
    # resubmission, so uid-keyed evidence folds into the pod that
    # ACTUALLY served — never the dead pod, never dropped as stale
    for s in served:
        cluster.observe(Observation(pair=s.decision.pair,
                                    uid=s.request.uid, time_ms=1.0))
    assert cluster.stats()["stale_observations"] == 0
    assert not policies[0].observed             # dead pod got nothing
    for i in (1, 2):
        got = {o.uid for o in policies[i].observed}
        want = {s.request.uid for s in served
                if s.result.backend == f"m{i}"}
        assert got == want
    cluster.close()


@pytest.mark.threads
def test_cluster_all_pods_dead_raises_no_live_pods():
    cluster = EcoreCluster(lambda i: _PinnedPolicy((f"m{i}", "dead")),
                           lambda d: _FailingBackend(d.backend, max_batch=1),
                           pods=2, pod_fail_after=1)
    futs = cluster.submit_batch([_req(u) for u in range(6)])
    cluster.drain()
    assert all(f.exception() is not None for f in futs)
    assert cluster.stats()["alive"] == [False, False]
    assert cluster.stats()["availability"] == 0.0
    with pytest.raises(NoLivePods):
        cluster.submit(_req(100))
    cluster.close()
