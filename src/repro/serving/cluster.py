"""EcoreCluster: N EcoreService pods behind ONE request plane.

Scaling ECORE out means standing up many (policy + dispatch queues +
backends) pods and sharding the request stream across them — the serving
analog of the paper's multi-gateway deployment and AyE-Edge's
deployment-space search.  The cluster owns:

  * shard selection — a JITTED, tensorized step over the per-pod
    queue-depth array (one XLA call assigns a whole batch), with an
    exact-parity scalar reference (``select_pods_reference``) used on the
    per-request path and in tests.  Two policies:

      - ``least_loaded``: sequential greedy argmin over live depths
        (a ``lax.scan`` — each assignment sees the depths the previous
        ones produced, exactly like the scalar loop);
      - ``rendezvous``: highest-random-weight hashing of (uid, pod) via a
        splitmix-style 32-bit avalanche — stable request->pod affinity
        that survives pod count changes with minimal reshuffling.

  * observe() fan-in — an ``Observation`` carrying the request ``uid`` is
    folded into the OWNING pod's policy (the pod whose decision produced
    the measurement); without a uid it is a pair-wide signal and broadcasts
    to every pod.

  * per-pod ``stats()`` aggregation and concurrent ``drain``/``close``.

Pods are fully independent (own policy, own queues, own backends, own
lock), so ``submit_batch`` fans each pod's shard out on a small thread
pool: pods serve concurrently — XLA releases the GIL during backend
execution — which is where the multi-pod throughput scaling comes from
(``benchmarks/run.py --only cluster``).

Pods map to devices: pod ``i`` builds its backends with
``jax.devices()[i % jax.device_count()]`` as the default device, so on a
four-chip host four pods put their models on four chips (an
``engine.Backend`` commits its weights to that device).  With one device
every pod shares it, as before.
"""
from __future__ import annotations

import collections
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.core.policy import (Observation, RouteDecision, RouteRequest,
                               RoutingPolicy)
from repro.serving.service import EcoreService, Served

SHARD_MODES = ("least_loaded", "rendezvous")

#: bound on the uid -> owning-pod map (a long-lived cluster must not grow
#: per-request state; observations normally arrive right after completion)
OWNER_LIMIT = 8192


# ------------------------------------------------------- shard selection

def _mix32(x, xp):
    """splitmix32-style avalanche on uint32 arrays; ``xp`` is numpy or
    jax.numpy — SAME integer ops in both, so the jitted kernel and the
    scalar reference agree bit for bit."""
    x = x ^ (x >> 16)
    x = x * xp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * xp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


#: a dead pod's masked queue depth: larger than any real depth, far from
#: int32 overflow even after a whole batch of .add(1)s
_DEAD_DEPTH = 2 ** 30


def _masked_scores(scores, alive, xp):
    """Rendezvous scores with dead pods forced to lose: live scores map
    monotonically into [2^31, 2^32) (>> 1 then set the top bit), dead pods
    score 0.  Same uint32 ops for numpy and jnp — no int64, which jax
    would silently downcast with x64 disabled."""
    live = (scores >> xp.uint32(1)) | xp.uint32(0x80000000)
    return xp.where(alive, live, xp.uint32(0))


_kernels = None


def _shard_kernels():
    global _kernels
    if _kernels is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def rendezvous(uids_u32, pod_ids_u32):
            # highest-random-weight: score every (request, pod), argmax rows
            scores = _mix32(uids_u32[:, None] ^ _mix32(pod_ids_u32, jnp)[None, :],
                            jnp)
            return jnp.argmax(scores, axis=1)

        @jax.jit
        def rendezvous_masked(uids_u32, pod_ids_u32, alive):
            scores = _mix32(uids_u32[:, None] ^ _mix32(pod_ids_u32, jnp)[None, :],
                            jnp)
            return jnp.argmax(_masked_scores(scores, alive[None, :], jnp),
                              axis=1)

        @jax.jit
        def least_loaded(uids_u32, depths_i32):
            # sequential greedy: each pick sees the depths the previous
            # picks produced (ties -> lowest pod index, like np.argmin)
            def step(depth, _):
                p = jnp.argmin(depth)
                return depth.at[p].add(1), p
            _, picks = jax.lax.scan(step, depths_i32, uids_u32)
            return picks

        @jax.jit
        def least_loaded_masked(uids_u32, depths_i32, alive):
            dead = jnp.int32(_DEAD_DEPTH)
            def step(depth, _):
                p = jnp.argmin(jnp.where(alive, depth, dead))
                return depth.at[p].add(1), p
            _, picks = jax.lax.scan(step, depths_i32, uids_u32)
            return picks

        _kernels = {"rendezvous": rendezvous, "least_loaded": least_loaded,
                    "rendezvous_masked": rendezvous_masked,
                    "least_loaded_masked": least_loaded_masked}
    return _kernels


def select_pods(uids: Sequence[int], depths: Sequence[int],
                mode: str = "least_loaded",
                alive: Optional[Sequence[bool]] = None) -> np.ndarray:
    """Assign a batch of request uids to pods in ONE jitted XLA call.

    ``depths`` is the live per-pod queue depth (least-loaded consumes it;
    rendezvous ignores it).  ``alive`` (optional bool mask) excludes dead
    pods: least-loaded sees their depth as unbeatable, rendezvous forces
    their score below every live pod's — graceful degradation without a
    separate kernel family; ``None`` runs the original unmasked kernels
    bit-identically.  Exactly matches ``select_pods_reference`` (tested):
    pure uint32/int32 arithmetic on both paths."""
    if mode not in SHARD_MODES:
        raise ValueError(f"unknown shard mode {mode!r}; one of {SHARD_MODES}")
    import jax.numpy as jnp
    uids_u32 = jnp.asarray(np.asarray(uids, np.uint32))
    k = _shard_kernels()[mode if alive is None else mode + "_masked"]
    if mode == "rendezvous":
        pod_ids = jnp.asarray(np.arange(len(depths), dtype=np.uint32))
        if alive is None:
            return np.asarray(k(uids_u32, pod_ids))
        return np.asarray(k(uids_u32, pod_ids,
                            jnp.asarray(np.asarray(alive, bool))))
    depths_i32 = jnp.asarray(np.asarray(depths, np.int32))
    if alive is None:
        return np.asarray(k(uids_u32, depths_i32))
    return np.asarray(k(uids_u32, depths_i32,
                        jnp.asarray(np.asarray(alive, bool))))


def select_pods_reference(uids: Sequence[int], depths: Sequence[int],
                          mode: str = "least_loaded",
                          alive: Optional[Sequence[bool]] = None
                          ) -> np.ndarray:
    """Scalar reference: one request at a time, plain numpy.  The jitted
    ``select_pods`` must match this exactly (masked or not)."""
    if mode not in SHARD_MODES:
        raise ValueError(f"unknown shard mode {mode!r}; one of {SHARD_MODES}")
    uids = list(uids)   # materialize ONCE: a generator must not be exhausted
    depths = np.asarray(depths, np.int32).copy()
    pod_ids = np.arange(len(depths), dtype=np.uint32)
    alive_mask = None if alive is None else np.asarray(alive, bool)
    picks = np.zeros(len(uids), np.int64)
    for i, uid in enumerate(uids):
        if mode == "least_loaded":
            visible = (depths if alive_mask is None
                       else np.where(alive_mask, depths,
                                     np.int32(_DEAD_DEPTH)))
            p = int(np.argmin(visible))
            depths[p] += 1
        else:
            u = np.asarray([uid], np.uint32)  # arrays: silent uint32 wrap
            scores = _mix32(u ^ _mix32(pod_ids, np), np)
            if alive_mask is not None:
                scores = _masked_scores(scores, alive_mask, np)
            p = int(np.argmax(scores))
        picks[i] = p
    return picks


# --------------------------------------------------------------- cluster

class NoLivePods(RuntimeError):
    """Every pod has been marked failed — the cluster cannot place work."""


class EcoreCluster:
    """Shard one request stream over N independent ``EcoreService`` pods.

    ``policy_factory(pod_index)`` builds each pod's OWN policy (adaptive
    state must not be shared — observations fold into the owning pod);
    ``backend_factory`` is per-decision, as in ``EcoreService``.  Requests
    need cluster-unique uids (the owner map and each pod's inflight check
    key on them).

    ``pod_fail_after`` (optional) arms graceful degradation: after that
    many CONSECUTIVE failed completions a pod is marked dead
    (``mark_pod_failed``), masked out of shard selection, and every
    request that failed on it is RESUBMITTED to a surviving pod (the
    cluster then owns the returned future and resolves it from whichever
    pod finally answers; the owner map follows the move, so uid-keyed
    observations fold into the pod that actually served).  Off (None),
    behavior is identical to the non-degrading cluster: pod futures are
    returned directly and errors propagate untouched."""

    def __init__(self, policy_factory: Callable[[int], RoutingPolicy],
                 backend_factory: Callable[[RouteDecision], object], *,
                 pods: int = 2, shard: str = "least_loaded",
                 max_wait_ms: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic,
                 retain_results: bool = True,
                 pod_fail_after: Optional[int] = None,
                 max_pods: Optional[int] = None,
                 flusher: bool = True):
        if pods < 1:
            raise ValueError(f"pods={pods}: need at least one pod")
        if shard not in SHARD_MODES:
            raise ValueError(
                f"unknown shard mode {shard!r}; one of {SHARD_MODES}")
        self.max_pods = pods if max_pods is None else max_pods
        if self.max_pods < pods:
            raise ValueError(
                f"max_pods={max_pods} below initial pods={pods}")
        self.shard = shard
        # kept so add_pod() can stand up new pods with identical wiring
        self._policy_factory = policy_factory
        self._backend_factory = backend_factory
        self._max_wait_ms = max_wait_ms
        self._clock = clock
        self._retain = retain_results
        self._pod_flusher = flusher
        self.pods: List[EcoreService] = [
            self._make_pod(i) for i in range(pods)]
        self._lock = threading.Condition()
        #: live queue depth per pod (in-flight requests; shard input)
        self._depth = np.zeros(pods, np.int64)
        #: total requests ever assigned per pod (stats)
        self.shard_counts = np.zeros(pods, np.int64)
        self._owner: Dict[int, int] = {}
        self._owner_order: collections.deque = collections.deque()
        #: uid-keyed observations dropped because the owner was unknown
        self.stale_observations = 0
        self.pod_fail_after = pod_fail_after
        self._alive = np.ones(pods, bool)
        self._consec_errors = np.zeros(pods, np.int64)
        self.resubmitted = 0          # requests moved off a failed pod
        self._moving = 0              # resubmissions not yet re-enqueued
        #: pods drained by the autoscaler (alive=False but healthy — the
        #: first to revive on scale-up, unlike FAILED pods which stay dead)
        self._retired: set = set()
        # sized for the elastic ceiling: ThreadPoolExecutor cannot grow
        self._exec = ThreadPoolExecutor(max_workers=self.max_pods,
                                        thread_name_prefix="ecore-pod")
        self._closed = False

    def _make_pod(self, index: int) -> EcoreService:
        import jax  # lazy, like the shard kernels

        device = jax.devices()[index % jax.device_count()]
        factory = self._backend_factory

        def pod_factory(decision: RouteDecision):
            # the pod's backends are built with its device as the default,
            # so an engine.Backend commits its weights there
            with jax.default_device(device):
                return factory(decision)

        return EcoreService(self._policy_factory(index),
                            pod_factory,
                            max_wait_ms=self._max_wait_ms,
                            clock=self._clock,
                            retain_results=self._retain,
                            flusher=self._pod_flusher)

    # ------------------------------------------------------------ submit

    def _assign(self, uids: Sequence[int], batched: bool) -> np.ndarray:
        with self._lock:
            if not self._alive.any():
                raise NoLivePods(
                    f"all {len(self.pods)} pods are marked failed")
            # the mask only enters selection once degradation is armed (or
            # a pod actually died) — the unmasked kernels stay bit-
            # identical to the non-degrading cluster
            alive = None if self._alive.all() else self._alive
            picks = (select_pods if batched else select_pods_reference)(
                uids, self._depth, self.shard, alive=alive)
            np.add.at(self._depth, picks, 1)
            np.add.at(self.shard_counts, picks, 1)
            for uid, p in zip(uids, picks):
                if uid not in self._owner:
                    self._owner_order.append(uid)
                self._owner[uid] = int(p)
            while len(self._owner_order) > OWNER_LIMIT:
                self._owner.pop(self._owner_order.popleft(), None)
        return picks

    def _release(self, pod: int) -> None:
        with self._lock:
            self._depth[pod] -= 1

    def _watch(self, fut: "Future[Served]", pod: int) -> "Future[Served]":
        fut.add_done_callback(lambda _f: self._release(pod))
        return fut

    # ------------------------------------------------------- degradation

    def mark_pod_failed(self, pod: int) -> None:
        """Mask ``pod`` out of shard selection (manual override or called
        by the consecutive-error detector).  Its queued work is not
        recalled wholesale — each failed completion resubmits itself — but
        nothing NEW lands on it."""
        with self._lock:
            self._alive[pod] = False
            self._lock.notify_all()

    def _record_outcome(self, pod: int, failed: bool) -> None:
        """Consecutive-failure pod detector (degradation armed only)."""
        with self._lock:
            if failed:
                self._consec_errors[pod] += 1
                if (self.pod_fail_after is not None and self._alive[pod]
                        and self._consec_errors[pod] >= self.pod_fail_after):
                    self._alive[pod] = False
            else:
                self._consec_errors[pod] = 0
            self._lock.notify_all()

    def _guard(self, fut: "Future[Served]", pod: int, req: RouteRequest,
               outer: "Future[Served]", hops: int) -> None:
        """Bridge a pod future to the cluster-owned ``outer`` future,
        recording outcomes and resubmitting failures to survivors.  The
        pod resolves its futures while holding its OWN condition, so the
        resubmission (which must take another pod's condition) hops
        through the executor — pod-to-pod lock cycles are impossible."""
        def _done(f: "Future[Served]") -> None:
            self._release(pod)
            exc = f.exception()
            if exc is None:
                self._record_outcome(pod, failed=False)
                outer.set_result(f.result())
                return
            self._recover(pod, req, outer, exc, hops)
        fut.add_done_callback(_done)

    def _recover(self, pod: int, req: RouteRequest, outer: "Future[Served]",
                 exc: BaseException, hops: int) -> None:
        """One failed attempt on ``pod``: feed the detector, then either
        move the request to a survivor (pod is dead, hop budget left) or
        surface the error on the outer future."""
        self._record_outcome(pod, failed=True)
        with self._lock:
            can_move = (not self._alive[pod] and not self._closed
                        and hops + 1 < len(self.pods)
                        and self._alive.any())
            if can_move:
                self.resubmitted += 1
                self._moving += 1
        if can_move:
            self._exec.submit(self._resubmit, req, outer, hops + 1)
        else:
            outer.set_exception(exc)

    def _submit_guarded(self, pod: int, shard_reqs: List[RouteRequest],
                        outers: List["Future[Served]"]) -> None:
        """Armed-mode shard submission: one ``pod.submit`` per request, so
        an inline-flush backend error surfaces HERE for exactly the
        request that triggered it (co-batched failures come back through
        the futures ``_guard`` already watches) and recovery never loses a
        request the way a whole-shard ``submit_batch`` raise would."""
        for req, outer in zip(shard_reqs, outers):
            try:
                fut = self.pods[pod].submit(req)
            except Exception as exc:
                self._release(pod)
                self._recover(pod, req, outer, exc, hops=0)
            else:
                self._guard(fut, pod, req, outer, hops=0)

    def _resubmit(self, req: RouteRequest, outer: "Future[Served]",
                  hops: int) -> None:
        """Re-place one request that failed on a dead pod (executor
        thread: holds no lock while entering the survivor pod)."""
        try:
            try:
                pod = int(self._assign([req.uid], batched=False)[0])
            except Exception as exc:
                outer.set_exception(exc)
                return
            try:
                fut = self.pods[pod].submit(req)
            except Exception as exc:
                self._release(pod)
                outer.set_exception(exc)
                return
            self._guard(fut, pod, req, outer, hops)
        finally:
            with self._lock:
                self._moving -= 1
                self._lock.notify_all()

    def submit(self, req: RouteRequest) -> "Future[Served]":
        """Shard one request (scalar reference path) and submit it to its
        pod; the pod routes, queues and batches as usual.  If the pod's
        submit raises (inline-flush backend error, routing error), the
        request is un-counted from the depth accounting before the error
        propagates — same invariant as ``submit_batch``'s error path."""
        pod = int(self._assign([req.uid], batched=False)[0])
        if self.pod_fail_after is None:
            try:
                fut = self.pods[pod].submit(req)
            except Exception:
                with self._lock:
                    self._depth[pod] -= 1
                raise
            return self._watch(fut, pod)
        outer: "Future[Served]" = Future()
        try:
            fut = self.pods[pod].submit(req)
        except Exception as exc:
            self._release(pod)
            self._recover(pod, req, outer, exc, hops=0)
        else:
            self._guard(fut, pod, req, outer, hops=0)
        return outer

    def submit_batch(self, reqs: Sequence[RouteRequest]
                     ) -> List["Future[Served]"]:
        """One jitted shard-selection call for the whole batch, then each
        pod's shard is submitted CONCURRENTLY (thread pool) — pods route
        and serve in parallel.  Futures return in request order.

        Error semantics mirror ``EcoreService.submit_batch``: if a pod's
        inline flush raises, the error re-raises here AFTER every healthy
        pod's futures have their depth watchers attached and the failing
        pod's shard is released from the depth accounting (its service
        already failed the affected futures) — a blown backend must not
        skew least-loaded sharding for the cluster's lifetime."""
        reqs = list(reqs)
        if not reqs:
            return []
        picks = self._assign([r.uid for r in reqs], batched=True)
        shards: Dict[int, List[int]] = {}
        for i, p in enumerate(picks):
            shards.setdefault(int(p), []).append(i)
        if self.pod_fail_after is not None:
            # degradation armed: per-request pod submission (still batched
            # at the dispatch queues) so inline backend errors recover
            # per-request instead of losing a whole shard's futures
            outers: List["Future[Served]"] = [Future() for _ in reqs]
            tasks = [self._exec.submit(self._submit_guarded, pod,
                                       [reqs[i] for i in idxs],
                                       [outers[i] for i in idxs])
                     for pod, idxs in shards.items()]
            for t in tasks:
                t.result()
            return outers
        pending = {
            pod: self._exec.submit(self.pods[pod].submit_batch,
                                   [reqs[i] for i in idxs])
            for pod, idxs in shards.items()}
        out: List[Optional[Future]] = [None] * len(reqs)
        first_exc = None
        for pod, idxs in shards.items():
            try:
                futs = pending[pod].result()
            except Exception as exc:
                first_exc = first_exc or exc
                # nothing watchable came back, so un-count the whole shard.
                # This is an APPROXIMATION: requests the pod had already
                # enqueued on healthy queues before the flush blew up are
                # still in flight but no longer counted (they resolve at
                # drain without a watcher, so no double-decrement) — depth
                # errs toward routing TOWARD a blown pod until drain, never
                # permanently away from it.
                with self._lock:
                    self._depth[pod] -= len(idxs)
                continue
            if self.pod_fail_after is None:
                for i, fut in zip(idxs, futs):
                    out[i] = self._watch(fut, pod)
            else:
                for i, fut in zip(idxs, futs):
                    outer: "Future[Served]" = Future()
                    self._guard(fut, pod, reqs[i], outer, hops=0)
                    out[i] = outer
        if first_exc is not None:
            raise first_exc
        return out  # type: ignore[return-value]

    # -------------------------------------------------------- elasticity

    def can_add_pod(self) -> bool:
        """True when scale-up is possible: a retired pod can revive, or the
        fleet is still below ``max_pods``."""
        with self._lock:
            return bool(self._retired) or len(self.pods) < self.max_pods

    def add_pod(self) -> int:
        """Grow the fleet by one pod and return its index.  A RETIRED pod
        (drained by ``retire_pod``, still healthy) revives in place —
        lowest index first, so grow/shrink cycles reuse warm pods and their
        adapted policies — otherwise a fresh pod is appended, up to
        ``max_pods``."""
        with self._lock:
            if self._closed:
                raise RuntimeError("cluster is closed")
            if self._retired:
                pod = min(self._retired)
                self._retired.discard(pod)
                self._alive[pod] = True
                self._consec_errors[pod] = 0
                self._lock.notify_all()
                return pod
            pod = len(self.pods)
            if pod >= self.max_pods:
                raise RuntimeError(
                    f"cluster is at max_pods={self.max_pods}")
            self.pods.append(self._make_pod(pod))
            self._depth = np.append(self._depth, 0)
            self.shard_counts = np.append(self.shard_counts, 0)
            self._alive = np.append(self._alive, True)
            self._consec_errors = np.append(self._consec_errors, 0)
            self._lock.notify_all()
            return pod

    def retire_pod(self, pod: Optional[int] = None) -> int:
        """Shrink the fleet by one pod: mask it out of shard selection,
        remember it as retired (revivable), then DRAIN it so every queued
        request completes — a scale-down never drops work.  Default victim
        is the highest-index live pod; the last live pod is never retired."""
        with self._lock:
            live = [i for i, a in enumerate(self._alive) if a]
            if pod is None:
                if not live:
                    raise NoLivePods("no live pod to retire")
                pod = live[-1]
            if not (0 <= pod < len(self.pods)) or not self._alive[pod]:
                raise ValueError(f"pod {pod} is not live")
            if len(live) <= 1:
                raise ValueError("refusing to retire the last live pod")
            self._alive[pod] = False
            self._retired.add(pod)
            self._lock.notify_all()
        # outside the cluster lock: drain takes the pod's own condition and
        # resolves futures (whose callbacks may re-enter cluster state)
        self.pods[pod].drain()
        return pod

    def live_pods(self) -> List[int]:
        with self._lock:
            return [i for i, a in enumerate(self._alive) if a]

    def queue_depths(self) -> List[int]:
        """Live in-flight depth per pod (the shard-selection input)."""
        with self._lock:
            return self._depth.tolist()

    def owner_of(self, uid: int) -> Optional[int]:
        """Pod that owns ``uid``'s decision (None if unknown/evicted)."""
        with self._lock:
            return self._owner.get(uid)

    def next_deadline(self) -> Optional[float]:
        """Earliest ``max_wait_ms`` expiry across every pod's queues (the
        virtual-time driver's next flush event), or None."""
        deadlines = [d for p in list(self.pods)
                     if (d := p.next_deadline()) is not None]
        return min(deadlines) if deadlines else None

    def flush_due(self, now: Optional[float] = None) -> int:
        """Synchronously flush every pod queue whose deadline expired."""
        return sum(p.flush_due(now) for p in list(self.pods))

    # ----------------------------------------------------------- observe

    def observe(self, obs: Observation) -> None:
        """Fold a measurement into the OWNING pod's policy (by ``obs.uid``);
        an observation without a uid is pair-wide evidence and broadcasts
        to every pod.  A uid-keyed observation whose owner is UNKNOWN
        (evicted past ``OWNER_LIMIT``, or never routed here) is DROPPED and
        counted in ``stats()["stale_observations"]`` — pod-specific
        evidence must not be smeared across every pod's profile."""
        if obs.uid is not None:
            with self._lock:
                pod = self._owner.get(obs.uid)
                if pod is None:
                    self.stale_observations += 1
                    return
            self.pods[pod].observe(obs)
        else:
            for p in self.pods:
                p.observe(obs)

    # ----------------------------------------------------------- results

    def results(self) -> List[Served]:
        out: List[Served] = []
        for p in self.pods:
            out += p.results()
        return out

    def drain(self) -> List[Served]:
        """Drain every pod CONCURRENTLY; completions are merged.  The first
        pod error re-raises after all pods finished draining.  Under
        degradation a drained failure may RESUBMIT to a survivor, so the
        drain loops until no resubmission is still moving between pods
        (bounded: each request moves at most pods-1 times)."""
        out: List[Served] = []
        first_exc = None
        while True:
            futs = [self._exec.submit(p.drain) for p in self.pods]
            for f in futs:
                try:
                    out += f.result()
                except Exception as exc:
                    first_exc = first_exc or exc
            with self._lock:
                while self._moving:
                    self._lock.wait(timeout=1.0)
            if not any(p.pending_requests for p in self.pods):
                break
        if first_exc is not None:
            raise first_exc
        return out

    def close(self) -> None:
        if self._closed:
            return
        first_exc = None
        for f in [self._exec.submit(p.close) for p in self.pods]:
            try:
                f.result()
            except Exception as exc:
                first_exc = first_exc or exc
        self._closed = True
        self._exec.shutdown(wait=True)
        if first_exc is not None:
            raise first_exc

    def __enter__(self) -> "EcoreCluster":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def wake(self) -> None:
        for p in self.pods:
            p.wake()

    # ------------------------------------------------------------- stats

    def stats(self) -> Dict:
        per_pod = [p.stats() for p in self.pods]
        with self._lock:
            alive = self._alive.tolist()
            resubmitted = self.resubmitted
            retired = sorted(self._retired)
        return {
            "pods": len(self.pods),
            "max_pods": self.max_pods,
            "retired": retired,
            "shard_mode": self.shard,
            "shard_counts": self.shard_counts.tolist(),
            "backends": sum(s["backends"] for s in per_pod),
            "serve_calls": sum(s["serve_calls"] for s in per_pod),
            "served": sum(s["served"] for s in per_pod),
            "deadline_flushes": sum(s["deadline_flushes"] for s in per_pod),
            "stale_observations": self.stale_observations,
            "alive": alive,
            "availability": sum(alive) / len(alive),
            "resubmitted": resubmitted,
            "per_pod": per_pod,
        }


# ------------------------------------------------------------ autoscaler

class Autoscaler:
    """Queue-depth-driven fleet elasticity with hysteresis, entirely on the
    injectable clock — no background thread, no wall-clock sleeps.

    The owner of time (``repro.traffic.LoadDriver``, or any event loop)
    calls ``tick(backlog)`` whenever the backlog signal changes.  Backlog
    is normalized per LIVE pod and compared against two watermarks:

      * backlog/pod >= ``high_backlog_per_pod``  -> ``add_pod`` (revive a
        retired pod, else append, up to ``max_pods``);
      * backlog/pod <= ``low_backlog_per_pod``   -> ``retire_pod`` (drain
        the highest-index live pod, down to ``min_pods``).

    The gap between the watermarks plus ``cooldown_s`` between actions is
    the hysteresis: a backlog oscillating inside the band changes nothing,
    and a spike cannot flap the fleet faster than one pod per cooldown.
    Every action is appended to ``events`` (virtual timestamp, action, pod,
    backlog, resulting live count) — the bench's audit trail."""

    def __init__(self, cluster: EcoreCluster,
                 clock: Callable[[], float] = time.monotonic, *,
                 min_pods: int = 1, max_pods: Optional[int] = None,
                 high_backlog_per_pod: float = 8.0,
                 low_backlog_per_pod: float = 1.0,
                 cooldown_s: float = 2.0):
        if min_pods < 1:
            raise ValueError(f"min_pods={min_pods}: need >= 1")
        self.max_pods = (cluster.max_pods if max_pods is None
                         else min(max_pods, cluster.max_pods))
        if self.max_pods < min_pods:
            raise ValueError(
                f"max_pods={self.max_pods} below min_pods={min_pods}")
        if low_backlog_per_pod >= high_backlog_per_pod:
            raise ValueError(
                f"watermarks must leave a hysteresis band: "
                f"low={low_backlog_per_pod} >= high={high_backlog_per_pod}")
        self.cluster = cluster
        self.clock = clock
        self.min_pods = min_pods
        self.high = high_backlog_per_pod
        self.low = low_backlog_per_pod
        self.cooldown_s = cooldown_s
        self._last_action_t = -float("inf")
        self.events: List[Dict] = []

    def tick(self, backlog: int) -> Optional[str]:
        """Evaluate the watermarks against ``backlog``; returns "add",
        "retire", or None (in cooldown / inside the hysteresis band)."""
        now = self.clock()
        if now - self._last_action_t < self.cooldown_s:
            return None
        live = self.cluster.live_pods()
        n = len(live)
        per_pod = backlog / max(n, 1)
        if (per_pod >= self.high and n < self.max_pods
                and self.cluster.can_add_pod()):
            pod = self.cluster.add_pod()
            action = "add"
        elif per_pod <= self.low and n > self.min_pods:
            pod = self.cluster.retire_pod()
            action = "retire"
        else:
            return None
        self._last_action_t = now
        self.events.append({
            "t_s": now, "action": action, "pod": pod, "backlog": backlog,
            "live_pods": len(self.cluster.live_pods()),
        })
        return action
