"""EcoreService: ONE request-centric serving surface over any RoutingPolicy.

Maps the paper's Fig. 3 pipeline onto four typed stages:

  estimate + route  ``RoutingPolicy.decide`` / ``decide_batch`` turn a
                    ``RouteRequest`` (frame or prompt + complexity signal)
                    into a ``RouteDecision`` (the (model, device) pair, the
                    group it was routed under, profiled costs);
  dispatch          the service owns one ``DispatchQueue`` per routed
                    (model, device) pair and lazily builds backends through
                    ``backend_factory`` —
                    ``submit`` enqueues and returns a ``Future[Served]``
                    that resolves when the request's batch flushes;
  observe           ``observe(Observation)`` is the single feedback plane:
                    measured latency/energy/quality EWMA-fold into the
                    policy's profile (the ``ProfileState``-backed table
                    facade), closing the routing loop.  The scanned closed
                    loop folds its observations inside ``decide_scan``
                    instead and hands ``submit_batch`` pre-routed decisions.

Flushing is genuinely async: a background flusher thread watches the oldest
pending request of every queue and serves a PARTIAL batch the moment its
``max_wait_ms`` deadline expires — no cooperative ``poll()`` calls from the
driver, ever.  The clock is injectable: deterministic tests drive a manual
clock and call ``wake()`` after advancing it (the flusher also re-checks on
a small real-time tick, so a forgotten ``wake`` degrades to polling rather
than deadlocking).

``serve_batch`` runs under the service lock, so decisions, flushes and
observations are serialized — batching, not intra-service parallelism, is
the throughput lever (matching the paper's one-batch-at-a-time Locust loop).

Each stage is a ``jax.profiler.TraceAnnotation`` span: ``ecore.submit``
(metadata ``uid`` or ``requests``) around ``ecore.route``, the queue's
``ecore.flush`` and the engine's spans inside it, ``ecore.complete`` while
futures resolve, and ``ecore.hold`` while the flusher waits on a pending
deadline.  A running profiler records them; otherwise they cost under a
microsecond each.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from concurrent.futures import Future
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple

from jax.profiler import TraceAnnotation

from repro.core.policy import (Observation, RouteDecision, RouteRequest,
                               RoutingPolicy)
from repro.serving.engine import DispatchQueue, Request, Result


@dataclasses.dataclass
class Served:
    """One completed request: what was asked, where it went, what came back."""
    request: RouteRequest
    decision: RouteDecision
    result: Result


class ServiceClosed(RuntimeError):
    """The service was closed: raised by ``submit``/``submit_batch`` after
    ``close()``, and set on any future still pending when ``close()``
    finishes flushing — a structured terminal error callers can
    distinguish from a backend failure (nothing is retryable here)."""


class EcoreService:
    """Request-centric serving: ``submit -> Future``, ``results``,
    ``drain``, ``close``, with deadline-bounded threaded flushing."""

    #: real-time re-check tick for the flusher (safety net under fake clocks
    #: and the wake granularity under the real one)
    FLUSH_TICK_S = 0.05

    def __init__(self, policy: RoutingPolicy,
                 backend_factory: Callable[[RouteDecision], object], *,
                 max_wait_ms: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic,
                 retain_results: bool = True,
                 buffer_errors: bool = True,
                 flusher: bool = True):
        self.policy = policy
        self.max_wait_ms = max_wait_ms
        self._factory = backend_factory
        self._clock = clock
        # completions are buffered for results()/drain(); a driver that only
        # consumes futures should pass retain_results=False so a long-lived
        # service doesn't grow per-request state
        self._retain = retain_results
        # flusher-thread backend errors re-raise at drain()/close() so a
        # results()-driven driver can't lose a batch silently; a driver whose
        # ONLY consumption plane is futures (AsyncEcoreService) passes
        # buffer_errors=False — the futures already carry every error, and
        # re-raising at close would double-report it
        self._buffer_errors = buffer_errors
        self._cond = threading.Condition()
        #: one queue per ROUTED PAIR — the same model on two devices/meshes
        #: must not collapse onto one backend
        self._queues: Dict[Tuple[str, str], DispatchQueue] = {}
        #: uid -> (request, decision, future, queue key)
        self._inflight: Dict[int, Tuple[RouteRequest, RouteDecision,
                                        Future, Tuple[str, str]]] = {}
        self._completed: List[Served] = []
        # backend errors caught in the flusher thread: futures carry them,
        # but results()-driven drivers never look — re-raised at
        # drain()/close() so a lost batch cannot pass silently
        self._errors: Deque[Exception] = collections.deque(maxlen=16)
        self.flusher_passes = 0     # loop iterations (test observability)
        self._closed = False
        self._flusher: Optional[threading.Thread] = None
        # flusher=False keeps deadline semantics but hands WHEN to the
        # caller: a virtual-time driver (repro.traffic.LoadDriver) advances
        # its clock to next_deadline() and calls flush_due() itself, so
        # batch composition is a pure function of the workload
        if max_wait_ms is not None and flusher:
            self._flusher = threading.Thread(target=self._flush_loop,
                                             name="ecore-flusher",
                                             daemon=True)
            self._flusher.start()

    # ------------------------------------------------------------ submit

    def submit(self, req: RouteRequest) -> "Future[Served]":
        """Route one request and enqueue it on its backend's dispatch queue.
        The returned future resolves to a ``Served`` when the batch flushes
        (full batch, deadline expiry, ``drain`` or ``close``)."""
        with TraceAnnotation("ecore.submit", uid=req.uid), self._cond:
            self._ensure_open()
            with TraceAnnotation("ecore.route"):
                decision = self.policy.decide(req)
            fut = self._enqueue(req, decision)
            self._cond.notify_all()   # new deadline for the flusher
            return fut

    def submit_batch(self, reqs: Sequence[RouteRequest],
                     decisions: Optional[Sequence[RouteDecision]] = None
                     ) -> List["Future[Served]"]:
        """Route a whole workload in one ``decide_batch`` call (one XLA
        launch for batchable policies) and enqueue every request.

        ``decisions`` (optional, one per request) enqueues PRE-ROUTED
        requests instead: the scanned closed loop decides — and folds its
        observations — inside one jitted ``lax.scan``
        (``DetectionPolicy.decide_scan``), so the service must dispatch
        exactly those decisions rather than re-deciding against the
        already-updated profile."""
        with TraceAnnotation("ecore.submit", requests=len(reqs)), self._cond:
            self._ensure_open()
            if decisions is None:
                with TraceAnnotation("ecore.route"):
                    decisions = self.policy.decide_batch(list(reqs))
            elif len(decisions) != len(reqs):
                raise ValueError(
                    f"{len(decisions)} decisions for {len(reqs)} requests")
            futs = [self._enqueue(r, d) for r, d in zip(reqs, decisions)]
            self._cond.notify_all()
            return futs

    def observe(self, obs: Observation) -> None:
        """The single feedback plane: fold measured signals into the
        policy's profile (next decisions see them immediately)."""
        with self._cond:
            self.policy.observe(obs)

    # ------------------------------------------------------------ results

    def results(self) -> List[Served]:
        """Completed requests since the last ``results``/``drain`` call."""
        with self._cond:
            out, self._completed = self._completed, []
            return out

    def drain(self) -> List[Served]:
        """Flush every pending partial batch and return all unconsumed
        completions.  Raises the first backend error the flusher thread
        swallowed since the last drain — a results()-driven driver must not
        lose requests silently."""
        with self._cond:
            self._flush_all()
            if self._errors:
                raise self._errors.popleft()
            out, self._completed = self._completed, []
            return out

    def close(self) -> None:
        """Flush whatever is pending (no future is left dangling: results
        resolve, backend errors become future exceptions, anything still
        unresolved fails with ``ServiceClosed``), stop the flusher thread,
        then re-raise the first flush error.  Idempotent; completions
        remain readable via ``results()``."""
        exc = None
        with self._cond:
            if self._closed:
                return
            try:
                self._flush_all()
            except Exception as e:
                exc = e
            if exc is None and self._errors:
                exc = self._errors.popleft()
            # the flush resolved or failed every normal future; whatever is
            # STILL pending (a backend that returned a partial batch, a
            # cancelled flush) must not dangle past close
            for uid, (_, _, fut, _) in list(self._inflight.items()):
                del self._inflight[uid]
                fut.set_exception(ServiceClosed(
                    f"EcoreService closed with request uid {uid} unserved"))
            self._closed = True
            self._cond.notify_all()
        if self._flusher is not None:
            self._flusher.join(timeout=5.0)
        if exc is not None:
            raise exc

    def __enter__(self) -> "EcoreService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def wake(self) -> None:
        """Make the flusher re-check deadlines now (fake-clock tests call
        this after advancing their clock)."""
        with self._cond:
            self._cond.notify_all()

    def next_deadline(self) -> Optional[float]:
        """Earliest pending ``max_wait_ms`` expiry across all queues, or
        None when nothing is batched (or no deadline is configured).  A
        virtual-time driver advances its clock here, then ``flush_due``."""
        with self._cond:
            deadlines = [d for q in self._queues.values()
                         if (d := q.next_deadline()) is not None]
            return min(deadlines) if deadlines else None

    def flush_due(self, now: Optional[float] = None) -> int:
        """Flush every queue whose deadline has expired by ``now``
        (default: the injected clock) — the flusher thread's one pass,
        callable synchronously.  Returns the number of queues flushed;
        backend errors follow the same plane as the thread (buffered for
        drain()/close() when ``buffer_errors``, and the batch's futures
        always carry them)."""
        with self._cond:
            return self._flush_due_locked(self._clock() if now is None
                                          else now)

    @property
    def pending_requests(self) -> int:
        """Requests enqueued but not yet flushed (cluster drain uses this
        to decide whether resubmitted work still needs another pass)."""
        with self._cond:
            return sum(len(q.pending) for q in self._queues.values())

    @property
    def deadline_flushes(self) -> int:
        """Partial batches served because a deadline expired — counted on
        the queues, so inline (submit-path) and flusher-thread deadline
        flushes both register."""
        return sum(q.deadline_flushes for q in self._queues.values())

    def stats(self) -> Dict:
        """Counters summed over the queues.  ``decode_slots`` and
        ``tokens_asked`` are the LLM engine's (``engine.Backend``; 0 for
        other backends), each backend counted once however many routed
        pairs share it."""
        with self._cond:
            backends = {id(q.backend): q.backend
                        for q in self._queues.values()}.values()
            return {
                "backends": len(self._queues),
                "serve_calls": sum(q.calls for q in self._queues.values()),
                "served": sum(q.served for q in self._queues.values()),
                "deadline_flushes": self.deadline_flushes,
                "decode_slots": sum(getattr(b, "decode_slots", 0)
                                    for b in backends),
                "tokens_asked": sum(getattr(b, "tokens_asked", 0)
                                    for b in backends),
            }

    # ----------------------------------------------------------- internals

    def _ensure_open(self) -> None:
        if self._closed:
            raise ServiceClosed("EcoreService is closed")

    def _enqueue(self, req: RouteRequest,
                 decision: RouteDecision) -> "Future[Served]":
        if req.uid in self._inflight:
            raise ValueError(f"request uid {req.uid} is already in flight")
        key = decision.pair
        q = self._queues.get(key)
        if q is None:
            q = DispatchQueue(self._factory(decision),
                              max_wait_ms=self.max_wait_ms,
                              clock=self._clock)
            self._queues[key] = q
        fut: "Future[Served]" = Future()
        self._inflight[req.uid] = (req, decision, fut, key)
        self._dispatch(key, q.submit,
                       Request(uid=req.uid, prompt=req.payload,
                               max_new_tokens=req.max_new_tokens,
                               group=decision.group))
        return fut

    def _dispatch(self, key: Tuple[str, str], fn, arg) -> None:
        """Run one queue operation, ``fn(arg)``, that may serve a batch.  A
        backend error must not kill the flusher thread or dangle futures:
        every inflight future of the failing backend gets the exception
        (the flushed batch was already popped, and any same-flush
        sub-batch results are lost with it), then the error propagates to
        a direct caller."""
        try:
            self._complete(fn(arg))
        except Exception as exc:
            for uid, (_, _, fut, k) in list(self._inflight.items()):
                if k == key:
                    del self._inflight[uid]
                    fut.set_exception(exc)
            raise

    def _complete(self, results: List[Result]) -> None:
        if not results:
            return
        with TraceAnnotation("ecore.complete"):
            for res in results:
                req, decision, fut, _ = self._inflight.pop(res.uid)
                served = Served(request=req, decision=decision, result=res)
                if self._retain:
                    self._completed.append(served)
                fut.set_result(served)

    def _flush_all(self) -> None:
        first_exc = None
        for key, q in self._queues.items():
            try:
                self._dispatch(key, q.flush, "drain")
            except Exception as exc:  # futures already carry it; drain the
                first_exc = first_exc or exc        # healthy queues anyway
        if first_exc is not None:
            raise first_exc

    def _flush_loop(self) -> None:
        with self._cond:
            while not self._closed:
                self.flusher_passes += 1
                deadlines = [d for q in self._queues.values()
                             if (d := q.next_deadline()) is not None]
                if not deadlines:
                    # idle: submit()/close() notify, so no timed tick needed
                    self._cond.wait()
                    continue
                wait_s = min(deadlines) - self._clock()
                if wait_s > 0:
                    # a partial batch is held for its deadline
                    with TraceAnnotation("ecore.hold"):
                        self._cond.wait(min(wait_s, self.FLUSH_TICK_S))
                    continue
                self._flush_due_locked(self._clock())

    def _flush_due_locked(self, now: float) -> int:
        """Flush queues whose deadline expired by ``now``; caller holds
        ``_cond``.  Shared by the flusher thread and ``flush_due``."""
        flushed = 0
        for key, q in list(self._queues.items()):
            nd = q.next_deadline()
            if nd is not None and nd <= now:
                flushed += 1
                try:
                    self._dispatch(key, q.flush, "deadline")
                except Exception as exc:
                    # futures carry the backend error and drain()/
                    # close() re-raise it; flushing must survive
                    # to serve the other queues
                    if self._buffer_errors:
                        self._errors.append(exc)
        return flushed
