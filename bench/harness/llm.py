"""The LLM face: a Qwen2-style model served by ECORE's ``EcoreService``
over the program's ``engine.Backend``, driven closed or open loop.

Set-up builds the weights on the device from the seed, the service and
its one backend, and compiles every (batch, prompt length) the traffic can
form, through the backend's own ``serve_batch``.  The window submits
``RouteRequest``s through ``EcoreService.submit`` and counts each request
when its ``Served`` future resolves.  After the window the program's state
is freed and a sample of the finished requests is compared with the plain
float32 reference (``ref_llm``).
"""
from __future__ import annotations

import gc
import threading
import time
from typing import Dict, List

import numpy as np

from . import common, counts, traffic as gen
from . import trace as tracing


def model_config(cfg: Dict, rehearse: bool):
    """The program's ``ModelConfig`` from the Hugging Face style file."""
    from repro.models import ModelConfig

    if rehearse:
        cfg = dict(cfg, **cfg["rehearse"])
    s = counts.llm_shape(cfg)
    mc = ModelConfig(
        name=cfg["name"], family="dense", num_layers=s["layers"],
        d_model=s["d"], num_heads=s["heads"], num_kv_heads=s["kv_heads"],
        head_dim=s["head_dim"], d_ff=s["ff"], vocab_size=s["vocab"],
        qkv_bias=True, rope_theta=float(cfg["rope_theta"]),
        block_layout=("attn",), mlp_variant="swiglu",
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=cfg["torch_dtype"], activ_dtype=cfg["torch_dtype"],
        norm_eps=float(cfg["rms_norm_eps"]), remat=False,
        source=cfg["source"])
    return cfg, s, mc


def pool_table(name: str, device: str):
    """One backend for every prompt-length bucket: the router's table."""
    from repro.core.profiles import ProfileEntry, ProfileTable
    from repro.serving.pool import LENGTH_BUCKETS

    return ProfileTable([ProfileEntry(name, device, b, 50.0, 1.0, 1.0)
                         for _, _, b in LENGTH_BUCKETS])


class Window:
    """Counts what the window completes: each future's completion time and
    request, recorded by a done-callback."""

    def __init__(self):
        self.lock = threading.Lock()
        self.done: List = []          # (t_done, index, request, future)

    def watch(self, fut, i, req) -> None:
        def cb(f, i=i, req=req):
            with self.lock:
                self.done.append((time.perf_counter(), i, req, f))
        fut.add_done_callback(cb)

    def completed(self, lo: float, hi: float):
        """(t_done, index, request) of the requests served in [lo, hi]."""
        with self.lock:
            return [(td, i, r) for td, i, r, f in self.done
                    if lo <= td <= hi and f.exception() is None]


def run(args, manifest, cell, config, traffic, clock) -> int:
    jax, devices = common.setup(cell, args)
    import jax.numpy as jnp

    from repro.core.policy import PoolPolicy, RouteRequest
    from repro.serving.engine import Backend, Request
    from repro.serving.pool import ServingPool
    from repro.serving.service import EcoreService

    from . import llm_weights, ref_llm

    cfg, s, mc = model_config(config, args.rehearse)
    t = gen.llm_traffic(traffic, args.seed, args.seconds, s["vocab"],
                        rehearse=args.rehearse)
    compiles = common.CompileCounter()

    params = llm_weights.program_params(args.seed, s, jnp.dtype(mc.param_dtype))
    jax.block_until_ready(params)
    backend = Backend(cfg["name"], mc, params, max_batch=t.max_batch,
                      max_seq=t.max_seq, seed=args.seed)
    del params
    # every (batch, prompt length) the traffic can form, through the
    # backend's own entry: one prefill per shape, one decode per batch
    uid = -1
    for b, plen in t.warm_shapes:
        reqs = [Request(uid=uid - i, prompt=np.zeros(plen, np.int32),
                        max_new_tokens=2) for i in range(b)]
        uid -= b
        backend.serve_batch(reqs)
    policy = PoolPolicy(ServingPool(pool_table(cfg["name"], "tpu-v5e")))
    service = EcoreService(policy, lambda decision: backend,
                           max_wait_ms=t.max_wait_ms)
    n_setup_compiles = compiles.compiles
    setup_s = clock.since_start()
    common.log(f"{cell['name']} on {len(devices)} x {devices[0].device_kind}; "
               f"set-up {setup_s:.3f} s compiled {n_setup_compiles} programs "
               f"({compiles.compile_s:.1f} s); {len(t.requests)} requests "
               f"scheduled")

    win = Window()
    futures = []
    route_reqs = [RouteRequest(uid=i, payload=r.prompt,
                               complexity=len(r.prompt),
                               max_new_tokens=r.new)
                  for i, r in enumerate(t.requests)]
    trace_dir = str(common.OUT / cell["name"] / "trace")
    profiler = common.Profiler(trace_dir) if args.trace else None
    stats0 = service.stats()
    late = 0.0
    t0 = time.perf_counter()
    deadline = t0 + args.seconds
    if t.closed:
        # a closed queue kept full: the next batch is submitted as soon as
        # the last one is served (its last submit serves it inline)
        i, batches = 0, 0
        while time.perf_counter() < deadline and i < len(route_reqs):
            if profiler is not None and batches == t.trace_batch:
                profiler.start()
            with common.span("bench.submit"):
                for _ in range(t.max_batch):
                    futures.append(service.submit(route_reqs[i]))
                    win.watch(futures[-1], i, t.requests[i])
                    i += 1
            batches += 1
            if profiler is not None and batches == t.trace_batch + 2:
                profiler.stop()
    else:
        for i, rr in enumerate(route_reqs):
            due = t0 + t.send_at[i]
            now = time.perf_counter()
            if due >= deadline or now >= deadline:
                break
            if due > now:
                with common.span("bench.await_arrival"):
                    time.sleep(due - now)
            else:
                late = max(late, now - due)
            if profiler is not None:
                el = time.perf_counter() - t0
                if el >= t.trace_at + t.trace_s:
                    profiler.stop()
                elif el >= t.trace_at:
                    profiler.start()
            with common.span("bench.submit"):
                futures.append(service.submit(rr))
            win.watch(futures[-1], i, t.requests[i])
        # the window lasts its length also when the last arrival is early
        rest = deadline - time.perf_counter()
        if rest > 0:
            with common.span("bench.await_arrival"):
                time.sleep(rest)
    t_end = time.perf_counter()
    if profiler is not None and profiler.running:
        profiler.stop()
    stats1 = service.stats()
    window_compiles = compiles.compiles - n_setup_compiles
    window_s = t_end - t0
    done_in = win.completed(t0, t_end)
    service.close()
    failed = 0
    served = {}
    for i, f in enumerate(futures):
        try:
            res = f.result(timeout=120)
            served[i] = np.asarray(res.result.tokens)
        except Exception as exc:  # a failed request counts, and is reported
            failed += 1
            if failed <= 5:
                common.log(f"request {i} failed: {exc!r}")
    attempted = len(futures)
    out_tokens = sum(r.new for _, _, r in done_in)
    in_tokens = sum(len(r.prompt) for _, _, r in done_in)
    common.log(f"{attempted} requests submitted, {len(done_in)} completed in "
               f"the {window_s:.3f} s window; generator at most {late:.6f} s "
               f"late; {window_compiles} compilations inside the window")
    if not t.closed:
        lat = sorted((td - t0 - t.send_at[i], i) for td, i, _ in done_in)
        by_send = [x for _, x in sorted((t.send_at[i], l) for l, i in lat)]
        fifth = max(1, len(by_send) // 5)
        common.log(f"load: offered {len(futures) / args.seconds:.3f} req/s, "
                   f"completed {len(done_in) / window_s:.3f} req/s; latency "
                   f"p50 first fifth {np.median(by_send[:fifth]):.3f} s, last "
                   f"fifth {np.median(by_send[-fifth:]):.3f} s, p95 "
                   f"{common.percentile(by_send, 95):.3f} s")
    # the whole-step rate over the traced sub-window in a traced run (the
    # trace's own write-out lies inside the window), else the window
    lo, hi = ((profiler.t_start, profiler.t_stop)
              if profiler is not None and profiler.done else (t0, t_end))
    flops = sum(counts.request_flops(s, len(r.prompt), r.new)
                for _, _, r in win.completed(lo, hi))
    device = common.device_info(devices)

    e2e = {m["name"]: m for m in common.cell_end_to_end(manifest, cell["name"])}
    values = {"setup_s": setup_s,
              "offline_tok_per_s": out_tokens / window_s,
              "code_tok_per_s": (in_tokens + out_tokens) / window_s}
    counters = {
        "window_s": hi - lo, "model_flops": flops,
        "serve_calls": stats1["serve_calls"] - stats0["serve_calls"],
        "served": stats1["served"] - stats0["served"],
        "decode_batch": t.max_batch,
        "traced_decode_lengths": t.traced_decode_lengths,
    }

    # the program's state goes before the reference runs
    del service, backend, policy
    gc.collect()

    sample = gen.sample_requests(t, served, args.seed)
    pairs = [(t.requests[i].prompt, served[i][:t.requests[i].new])
             for i in sample]
    bad = sum(1 for i in sample
              if len(served[i]) < t.requests[i].new
              or not ((served[i] >= 0) & (served[i] < s["vocab"])).all())
    rows = ref_llm.compare(args.seed, s, cfg, [p for p in pairs
                                               if len(p[1])],
                           control=args.control, rows=t.ref_rows)
    gap = float(max(float(np.max(r["gap"])) for r in rows))
    common.log(f"checked {len(pairs)} requests, "
               f"{sum(len(p[1]) for p in pairs)} served tokens; greedy "
               f"agreement with the reference "
               f"{np.mean([r['agree'] for r in rows]):.4f}")
    limits = config["limits"]
    control = float(max(float(np.max(r["control_gap"])) for r in rows))
    if args.control:
        common.log(f"readings: program logit_gap {gap!r}, control logit_gap "
                   f"{control!r}")
        gap = control
    compared = {"bad_tokens": {"value": bad, "limit": 0},
                "logit_gap": {"value": gap, "limit": limits["logit_gap"]}}
    correct = (failed == 0 and attempted > 0 and bad == 0
               and gap <= limits["logit_gap"])

    breakdown = None
    if args.trace:
        tr = tracing.load(trace_dir)
        busy, win_ns = tracing.busy_ns(tr)
        device["busy_s"] = busy / 1e9
        device["window_s"] = win_ns / 1e9
        breakdown = {"device_ops": tracing.top_ops(tr),
                     "idle_gaps": tracing.idle_gaps(tr)}
        metrics = common.read_per_layer(manifest, cell, e2e, tr, counters,
                                        s, device)
    else:
        metrics = {n: {"value": values[n], "unit": m["unit"]}
                   for n, m in e2e.items()}
    common.emit(correct=correct, attempted=attempted, failed=failed,
                metrics=metrics, device=device, compared=compared,
                breakdown=breakdown)
    return 0
