"""Batched serving engine: prefill + decode over any model backend.

A ``Backend`` wraps (config, params, jitted prefill/decode) and serves
batches of requests; the pool layer (pool.py) profiles backends and lets the
ECORE gateway route requests among them.  A backend runs on ONE device: its
weights are committed to the device that is the default when it is built
(``jax.default_device``; the first device otherwise), and its steps run
there.  Tests and ``launch/serve.py`` build reduced configs; ``chip_smoke.py``
serves qwen2.5-3b at its published width on one TPU chip.  No serving path
shards a model over a mesh.

The host loop is traced with ``jax.profiler.TraceAnnotation`` spans named
``ecore.*`` (``ecore.serve_batch`` and, inside it, ``ecore.pad``,
``ecore.prefill``, one ``ecore.decode_step`` per token after the first,
``ecore.decode_wait`` and ``ecore.gather``; ``ecore.flush`` around a
queue's flush).  They cost under a microsecond each when no profiler runs,
and land on the device trace's clock when one does.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import ModelConfig, decode_step, init_params, prefill
from repro.data.tokens import modality_inputs


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray          # [S] int32
    max_new_tokens: int = 8
    # complexity metadata (the serving analog of the paper's object count):
    group: Optional[int] = None


@dataclasses.dataclass
class Result:
    uid: int
    tokens: np.ndarray
    prefill_s: float            # wall time of the WHOLE batch's prefill
    decode_s: float             # wall time of the WHOLE batch's decode
    backend: str
    batch_size: int = 1         # divide the times by this for per-request cost
    # workload-specific extras (the detection face fills these; LLM serving
    # leaves them None): per-request (boxes, scores, classes) plus the
    # modeled device cost actually charged
    detections: Optional[tuple] = None
    time_ms: Optional[float] = None
    energy_mwh: Optional[float] = None


def step_programs(cfg: ModelConfig, max_seq: int):
    """The jitted ``(prefill, decode)`` programs a ``Backend`` serves.

    ``prefill(params, tokens, prefix_embeds)`` returns the last position's
    logits and a cache of ``max_seq`` slots; ``decode(params, token,
    cache)`` returns the next logits and the cache advanced by one token.
    ``decode`` takes its cache as a donated argument: it writes the new
    token's columns into that buffer and returns it as the next cache, so
    no step copies the whole cache, and the cache passed in is deleted.  A
    caller rebinds its cache to each step's output and never reads one it
    has passed on."""
    # named, so the device trace's program names say prefill or decode
    def prefill_step(p, t, pe):
        return prefill(p, cfg, t, pe, max_seq=max_seq)

    def decode_token(p, t, c):
        return decode_step(p, cfg, t, c)

    return jax.jit(prefill_step), jax.jit(decode_token, donate_argnums=(2,))


class Backend:
    """One (model x placement) pair exposing an inference API.

    Implements the ``ExecutionBackend`` protocol (serving/backend.py);
    registered under kind ``"llm"``."""

    def __init__(self, name: str, cfg: ModelConfig, params=None, *,
                 max_batch: int = 8, max_seq: int = 256, seed: int = 0):
        self.name = name
        self.cfg = cfg
        if params is None:
            params = init_params(cfg, jax.random.PRNGKey(seed))
        # committed, so the jitted steps run on this device whichever
        # thread serves the batch
        self.params = jax.device_put(
            params, jax.config.jax_default_device or jax.devices()[0])
        self.max_batch = max_batch
        self.max_seq = max_seq

        self._prefill, self._decode = step_programs(cfg, max_seq)
        self._rng = np.random.default_rng(seed)
        #: batch x its largest max_new_tokens, summed over serve_batch calls
        #: (the decode slots run), and each request's own max_new_tokens
        #: summed (the tokens asked for); their ratio is the share of
        #: decoded slots some request wanted
        self.decode_slots = 0
        self.tokens_asked = 0

    def serve_batch(self, requests: List[Request]) -> List[Result]:
        """Greedy-decode a batch of requests (piggybacked, like the paper's
        Locust loop: one batch at a time).

        Prompts should share ONE length: shorter prompts are right-padded
        and the first generated token comes from the batch-wide last
        position (prefill only returns last-position logits), so mixed
        lengths corrupt the shorter requests' outputs — ``DispatchQueue``
        groups by length automatically."""
        assert requests
        b = len(requests)
        max_prompt = max(len(r.prompt) for r in requests)
        max_new = max(r.max_new_tokens for r in requests)
        asked = sum(r.max_new_tokens for r in requests)
        with TraceAnnotation("ecore.serve_batch", batch=b,
                             prompt_len=max_prompt, max_new=max_new,
                             tokens_asked=asked):
            with TraceAnnotation("ecore.pad"):
                tokens = np.zeros((b, max_prompt), np.int32)
                for i, r in enumerate(requests):  # right-padded
                    tokens[i, :len(r.prompt)] = r.prompt % self.cfg.vocab_size
                extra = modality_inputs(self.cfg, b, self._rng)
                pe = extra.get("prefix_embeds")
                tokens = jnp.asarray(tokens)

            with TraceAnnotation("ecore.prefill"):
                t0 = time.perf_counter()
                logits, cache = self._prefill(self.params, tokens, pe)
                next_tok = jnp.argmax(logits[:, -1:],
                                      axis=-1).astype(jnp.int32)
                jax.block_until_ready(next_tok)
                t1 = time.perf_counter()

            out = [next_tok]
            for _ in range(max_new - 1):
                with TraceAnnotation("ecore.decode_step"):
                    logits, cache = self._decode(self.params, next_tok, cache)
                    next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                out.append(next_tok)
            with TraceAnnotation("ecore.decode_wait"):
                jax.block_until_ready(next_tok)
            t2 = time.perf_counter()

            with TraceAnnotation("ecore.gather"):
                gen = np.concatenate([np.asarray(t) for t in out], axis=1)
                results = [Result(uid=r.uid, tokens=gen[i], prefill_s=t1 - t0,
                                  decode_s=t2 - t1, backend=self.name,
                                  batch_size=b)
                           for i, r in enumerate(requests)]
        self.decode_slots += b * max_new
        self.tokens_asked += asked
        return results

    def profile_row(self) -> Dict[str, object]:
        return {"kind": "llm", "model": self.name,
                "num_layers": self.cfg.num_layers, "d_model": self.cfg.d_model,
                "max_batch": self.max_batch, "max_seq": self.max_seq}


class DispatchQueue:
    """Per-backend request queue with batched, latency-bounded flush.

    Requests accumulate until ``backend.max_batch`` is reached, then go out
    batched — the driver-side half of the engine's batching support (the
    engine always could batch; the serving loop never fed it more than one
    request at a time).  Each flush makes one ``serve_batch`` call per
    distinct prompt LENGTH: ``serve_batch`` right-pads to the longest prompt
    and reads the first generated token from the batch-wide last position,
    so a mixed-length batch would corrupt the shorter requests' outputs —
    homogeneous sub-batches keep batched results identical to solo serving.

    ``max_wait_ms`` bounds how long the OLDEST pending request waits for the
    batch to fill: once the deadline passes, the next ``submit`` or
    ``poll`` serves the partial batch instead of holding it for stragglers.
    The deadline is checked cooperatively (no background thread) — a serving
    loop calls ``poll()`` on its idle ticks.  ``clock`` is injectable for
    deterministic tests (defaults to ``time.monotonic``, seconds)."""

    def __init__(self, backend: Backend, *,
                 max_wait_ms: Optional[float] = None, clock=time.monotonic):
        self.backend = backend
        self.max_wait_ms = max_wait_ms
        self._clock = clock
        self._oldest: Optional[float] = None
        self.pending: List[Request] = []
        self.calls = 0
        self.served = 0
        #: partial batches served because the deadline expired — via submit,
        #: poll, OR the service's background flusher (each flushes with
        #: trigger ``deadline``), so the metric is path-independent
        self.deadline_flushes = 0

    def _deadline_passed(self) -> bool:
        return (self.max_wait_ms is not None and self._oldest is not None
                and (self._clock() - self._oldest) * 1e3 >= self.max_wait_ms)

    def next_deadline(self) -> Optional[float]:
        """Absolute clock time (seconds, same units as ``clock``) when the
        oldest pending request's wait bound expires; None when there is no
        deadline or nothing is pending.  The threaded flusher
        (``serving.service.EcoreService``) sleeps until the earliest of
        these instead of cooperatively polling."""
        if self.max_wait_ms is None or self._oldest is None or not self.pending:
            return None
        return self._oldest + self.max_wait_ms / 1e3

    def submit(self, req: Request) -> List[Result]:
        """Enqueue; returns flushed results when the batch fills (or the
        oldest pending request's deadline has passed), else []."""
        if not self.pending:
            self._oldest = self._clock()
        self.pending.append(req)
        if len(self.pending) >= self.backend.max_batch:
            return self.flush("full")
        if self._deadline_passed():
            return self.flush("deadline")
        return []

    def poll(self) -> List[Result]:
        """Serve the pending partial batch if it has waited past
        ``max_wait_ms``; [] otherwise.  No-op without a deadline."""
        if self.pending and self._deadline_passed():
            return self.flush("deadline")
        return []

    def flush(self, trigger: str = "drain") -> List[Result]:
        """Serve everything pending, one ``serve_batch`` per prompt length.
        ``trigger`` says why (``full``, ``deadline`` or ``drain``): a
        ``deadline`` flush counts in ``deadline_flushes``, and the
        ``ecore.flush`` span carries it with ``waited_ms``, the oldest
        request's wait from its submit to this flush."""
        if not self.pending:
            return []
        batch, self.pending = self.pending, []
        waited_ms = (self._clock() - self._oldest) * 1e3
        self._oldest = None
        if trigger == "deadline":
            self.deadline_flushes += 1
        by_len: Dict[int, List[Request]] = {}
        for r in batch:
            by_len.setdefault(len(r.prompt), []).append(r)
        results: List[Result] = []
        with TraceAnnotation("ecore.flush", trigger=trigger,
                             requests=len(batch), sub_batches=len(by_len),
                             waited_ms=waited_ms):
            for _, group in sorted(by_len.items()):
                self.calls += 1
                self.served += len(group)
                results += self.backend.serve_batch(group)
        return results
