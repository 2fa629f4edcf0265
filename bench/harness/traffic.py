"""The one traffic generator: reads a cell's traffic file
(``bench/workloads/<traffic>.json``) and the seed, and returns what the
window sends and when.

Arrival times and each request's sizes are drawn from the seed:
arrivals from ``arrivals.make_arrivals``, sizes independently from their
weighted ladders.  Keys of a traffic file:

* ``face``: ``llm``;
* ``arrivals``: ``{"kind": "closed"}``, or ``{"kind": "poisson" |
  "diurnal" | "flash", "rate": r}``;
* ``prompt_len`` and ``new_tokens`` as ``{"values": [...], "weights":
  [...]}``, ``max_batch``, ``max_seq``, ``max_wait_ms``, and ``sample``
  (requests the reference checks);
* ``trace``: where the traced sub-window lies;
* ``source``: where the sizes and rates come from (read by no code).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import arrivals

#: lengths are divided by this in the CPU rehearsal
REHEARSE_DIV = 16


@dataclasses.dataclass
class LLMRequest:
    prompt: np.ndarray
    new: int


@dataclasses.dataclass
class LLMTraffic:
    requests: List[LLMRequest]
    send_at: np.ndarray            # seconds after the window opens
    closed: bool
    max_batch: int
    max_seq: int
    max_wait_ms: Optional[float]
    warm_shapes: List[Tuple[int, int]]
    sample_n: int
    ref_rows: int
    trace_batch: int = 2           # closed loop: first traced batch
    trace_at: float = 0.0          # open loop: traced sub-window start
    trace_s: float = 0.0
    traced_decode_lengths: List[int] = dataclasses.field(default_factory=list)


def _sizes(spec: Dict, n: int, rng, div: int) -> np.ndarray:
    """``n`` sizes drawn independently from the weighted ladder."""
    vals = np.array([max(2, v // div) for v in spec["values"]])
    w = np.asarray(spec.get("weights", [1] * len(vals)), np.float64)
    return rng.choice(vals, size=n, p=w / w.sum())


def llm_traffic(tf: Dict, seed: int, seconds: float, vocab: int, *,
                rehearse: bool = False) -> LLMTraffic:
    div = REHEARSE_DIV if rehearse else 1
    # sizes from their own stream, apart from the arrivals'
    rng = np.random.default_rng([seed, 0x512E5])
    arr = tf["arrivals"]
    closed = arr["kind"] == "closed"
    if closed:
        # enough full batches for any window the run can be given
        n = tf["max_batch"] * int(np.ceil(seconds / tf["batch_s_floor"]) + 4)
        send = np.zeros(n)
    else:
        send = arrivals.make_arrivals(arr["kind"], arr["rate"], seconds,
                                      seed=seed)
        n = len(send)
    plen = _sizes(tf["prompt_len"], n, rng, div)
    new = _sizes(tf["new_tokens"], n, rng, div)
    reqs = [LLMRequest(rng.integers(0, vocab, p, dtype=np.int32), int(m))
            for p, m in zip(plen, new)]
    max_seq = max(8, tf["max_seq"] // div)
    b = tf["max_batch"]
    lengths = sorted({max(2, v // div) for v in tf["prompt_len"]["values"]})
    if closed:
        warm = [(b, p) for p in lengths]
    else:
        warm = [(k, p) for p in lengths for k in range(1, b + 1)]
    t = LLMTraffic(reqs, send, closed, b, max_seq, tf.get("max_wait_ms"),
                   warm, tf["sample"]["requests"], tf["sample"]["rows"])
    tr = tf.get("trace", {})
    if closed:
        t.trace_batch = tr.get("first_batch", 2)
        p, m = int(plen[0]), int(new[0])
        # two whole traced batches, each decoding to caches of p+1 .. p+m-1
        t.traced_decode_lengths = 2 * list(range(p + 1, p + m))
    else:
        t.trace_at = tr.get("at_s", 5.0)
        t.trace_s = tr.get("seconds", 5.0)
    return t


def sample_requests(t: LLMTraffic, served: Dict[int, np.ndarray],
                    seed: int) -> List[int]:
    """Requests the reference checks: the one with the most prompt and new
    tokens, and others drawn from the seed, among those served."""
    done = sorted(served)
    if not done:
        return []
    longest = max(done, key=lambda i: (len(t.requests[i].prompt)
                                       + t.requests[i].new, -i))
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng(seed ^ 0x5EED)
    k = min(t.sample_n - 1, len(rest))
    return [longest] + sorted(rng.choice(rest, k, replace=False).tolist())
