"""Serving driver: one request plane streams ECORE-routed requests.

  PYTHONPATH=src python -m repro.launch.serve --requests 24 --delta 5
  PYTHONPATH=src python -m repro.launch.serve --requests 24 --pods 4
  PYTHONPATH=src python -m repro.launch.serve --requests 24 --async
  PYTHONPATH=src python -m repro.launch.serve --rate 20 --duration 5 \
      --pattern flash --pods 2 --max-wait-ms 25   # open-loop SLO replay

Backends are always REDUCED variants of the assigned archs (d_model <= 128,
one block; real prefill+decode runs, batched), on a CPU and on a TPU alike;
``chip_smoke.py`` is what serves qwen2.5-3b at its published width.  The
routing profile comes from the production dry-run roofline
(artifacts/dryrun.jsonl) when available, so the router makes the decisions
it would make over full-size backends.

The driver is a thin loop over ``EcoreService``: it builds a ``PoolPolicy``
(Algorithm 1 over prompt-length buckets), submits ``RouteRequest``s, and
handles ``Served`` completions — dispatch batching, per-backend queues and
the ``--max-wait-ms`` deadline all live inside the service.  With a static
profile the whole workload is routed in ONE tensorized ``decide_batch``
call (``submit_batch``); ``--adapt`` submits per request, since each
observation changes the table the next decision reads.  Deadline-expired
partial batches are served by the service's background flusher thread — the
driver never polls.

``--adapt`` closes the loop: each backend's measured per-request latency,
relative to its OWN first measurement (local CPU ms and pod-profile ms are
different scales, so only the relative slowdown transfers), rescales its
profiled time AND energy through the single ``Observation`` plane — so the
greedy argmin-energy routing reacts when a backend runs slower than its
profile claims.

``--pods N`` shards the stream over an ``EcoreCluster`` of N service pods
(each with its OWN PoolPolicy over a copy of the profile, so ``--adapt``
observations fold into the owning pod); ``--async`` drives a single pod
through the ``AsyncEcoreService`` asyncio facade instead of the sync API.

``--profile-out PATH`` persists the (possibly EWMA-adapted) routing profile
as json after the run — the same ``ProfileTable`` facade the
``ProfileState`` scan plane round-trips through, so a warm profile from one
session seeds the next (``pool_table_from_dryrun`` -> adapt -> json ->
``ProfileTable.from_json``).
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np

from repro.configs import get_config
from repro.core.policy import Observation, PoolPolicy, RouteRequest
from repro.core.profiles import ProfileEntry, ProfileTable
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.engine import Backend
from repro.serving.pool import (LENGTH_BUCKETS, ServingPool,
                                capability_score, pool_table_from_dryrun)
from repro.serving.service import EcoreService

DEFAULT_POOL = ("qwen2.5-3b", "llama3-8b", "mamba2-370m",
                "granite-moe-1b-a400m", "recurrentgemma-2b")

# reduced CPU backends cap the materialized prompt (routing still sees the
# full requested length)
PROMPT_CAP = 48


def synthetic_pool_table(archs) -> ProfileTable:
    """Fallback profile when no dry-run artifact exists (analytic)."""
    entries = []
    for arch in archs:
        cfg = get_config(arch)
        n = cfg.num_layers * cfg.d_model * cfg.d_model * 8  # rough
        for _, _, bucket in LENGTH_BUCKETS:
            entries.append(ProfileEntry(
                model=arch, device="pod-16x16", group=bucket,
                map_pct=capability_score(n, cfg.is_subquadratic, bucket),
                time_ms=n / 1e9, energy_mwh=n / 1e10))
    return ProfileTable(entries)


def _run_open_loop(args, table: ProfileTable, backend_factory) -> int:
    """--rate mode: replay a generated open-loop arrival stream through the
    virtual-time LoadDriver and report windowed SLOs.  Arrival times are
    virtual (the episode replays as fast as the backends serve); the
    modeled service times come from the routing profile, so queue growth
    reflects the PROFILED fleet capacity at this rate."""
    import repro.traffic as tr

    clock = tr.ManualClock()
    arrivals = tr.make_arrivals(args.pattern, args.rate, args.duration,
                                seed=args.seed)
    work = tr.merge_tenants([tr.llm_tenant(
        "pool", arrivals, seed=args.seed, deadline_ms=args.deadline_ms,
        prompt_cap=PROMPT_CAP, max_new_tokens=args.max_new)])
    if args.pods > 1:
        from repro.serving.cluster import EcoreCluster
        service = EcoreCluster(
            lambda i: PoolPolicy(ServingPool(table.copy(),
                                             delta=args.delta)),
            backend_factory, pods=args.pods, shard=args.shard,
            max_wait_ms=args.max_wait_ms, clock=clock,
            retain_results=False, flusher=False)
        plane = f"{args.pods}-pod cluster ({args.shard})"
    else:
        service = EcoreService(
            PoolPolicy(ServingPool(table, delta=args.delta)),
            backend_factory, max_wait_ms=args.max_wait_ms, clock=clock,
            retain_results=False, buffer_errors=False, flusher=False)
        plane = "service"

    driver = tr.LoadDriver(service, clock,
                           window_s=max(args.duration / 10.0, 1.0))
    t0 = time.time()
    try:
        done = driver.run(work)
    finally:
        service.close()
    wall_s = time.time() - t0

    print(f"\nopen-loop replay [{plane}]: {len(done)} requests, "
          f"pattern={args.pattern}, rate={args.rate:.1f}/s, "
          f"duration={args.duration:.0f}s virtual ({wall_s:.1f}s wall)")
    print("window_t_s,n,goodput_rps,p50_ms,p99_ms,queue_wait_p99_ms,"
          "joules_per_request")
    for w in driver.slo.window_records():
        print(f"{w['t_start_s']:.0f},{w['n']},{w['goodput_rps']:.1f},"
              f"{w['p50_ms']:.1f},{w['p99_ms']:.1f},"
              f"{w['queue_wait_p99_ms']:.1f},"
              f"{w['joules_per_request']:.4f}")
    s = driver.slo.summary()
    print(f"summary: p50={s['p50_ms']:.1f}ms p95={s['p95_ms']:.1f}ms "
          f"p99={s['p99_ms']:.1f}ms goodput={s['goodput_fraction']:.3f} "
          f"({s['goodput_rps']:.1f}/s) "
          f"J/req={s['joules_per_request']:.4f} "
          f"failed={s['failed']}")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="ECORE serving driver: closed-loop request stream by "
                    "default, open-loop load replay with --rate")

    serving = ap.add_argument_group(
        "serving", "workload shape, routing profile, dispatch batching")
    serving.add_argument("--requests", type=int, default=24)
    serving.add_argument("--delta", type=float, default=5.0)
    serving.add_argument("--archs", nargs="*", default=list(DEFAULT_POOL))
    serving.add_argument("--dryrun-artifact",
                         default="artifacts/dryrun.jsonl")
    serving.add_argument("--max-new", type=int, default=8)
    serving.add_argument("--max-batch", type=int, default=8)
    serving.add_argument("--max-wait-ms", type=float, default=None,
                         help="serve a partial batch once its oldest "
                              "request has waited this long (default: wait "
                              "for a full batch); honored by the service's "
                              "background flusher thread")
    serving.add_argument("--seed", type=int, default=0)
    serving.add_argument("--adapt", action="store_true",
                         help="EWMA-update the routing profile from "
                              "measured per-request latency (closed loop)")
    serving.add_argument("--profile-out", default=None,
                         help="write the routing profile (with any --adapt "
                              "updates folded in) to this json path after "
                              "the run, to warm-start a later session; "
                              "under --pods each pod adapts a PRIVATE copy, "
                              "so the shared source profile is written "
                              "unadapted")

    scale = ap.add_argument_group(
        "resilience / scale-out", "how many pods serve, and through which "
        "request plane")
    scale.add_argument("--pods", type=int, default=1,
                       help="shard the stream over an EcoreCluster of N "
                            "service pods (each pod: own policy over a "
                            "copy of the profile, own queues and backends)")
    scale.add_argument("--shard", default="least_loaded",
                       choices=["least_loaded", "rendezvous"],
                       help="cluster shard-selection policy (with "
                            "--pods > 1)")
    scale.add_argument("--async", dest="use_async", action="store_true",
                       help="drive one pod through the AsyncEcoreService "
                            "asyncio facade (incompatible with --pods > 1)")

    traffic = ap.add_argument_group(
        "traffic", "open-loop load replay (repro.traffic) — requests "
        "arrive at generated times on a virtual clock instead of the "
        "closed --requests loop")
    traffic.add_argument("--rate", type=float, default=None,
                         help="mean arrival rate in requests/s; turns the "
                              "driver into an open-loop LoadDriver replay")
    traffic.add_argument("--duration", type=float, default=None,
                         help="episode length in virtual seconds "
                              "(default 10; needs --rate)")
    traffic.add_argument("--pattern", default=None,
                         choices=["poisson", "diurnal", "flash"],
                         help="arrival process (default poisson; needs "
                              "--rate)")
    traffic.add_argument("--deadline-ms", type=float, default=None,
                         help="per-request SLO deadline for goodput "
                              "accounting (needs --rate)")

    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.pods < 1:
        ap.error(f"--pods {args.pods}: need at least one pod")
    if args.use_async and args.pods != 1:
        ap.error("--async drives a single pod; use --pods 1 with it")
    if args.rate is None:
        for flag, v in (("--duration", args.duration),
                        ("--pattern", args.pattern),
                        ("--deadline-ms", args.deadline_ms)):
            if v is not None:
                ap.error(f"{flag} is open-loop traffic shape; it needs "
                         f"--rate")
    else:
        if args.rate <= 0:
            ap.error(f"--rate {args.rate}: need > 0")
        if args.use_async:
            ap.error("--rate replays through the sync LoadDriver; "
                     "drop --async")
        if args.adapt:
            ap.error("--rate is an open-loop replay; --adapt's "
                     "per-request closed loop is not supported with it")
        args.duration = 10.0 if args.duration is None else args.duration
        args.pattern = args.pattern or "poisson"

    if os.path.exists(args.dryrun_artifact):
        table = pool_table_from_dryrun(args.dryrun_artifact)
        table = ProfileTable([e for e in table.entries
                              if e.model in args.archs])
        src = args.dryrun_artifact
    else:
        table = synthetic_pool_table(args.archs)
        src = "analytic fallback"
    pool = ServingPool(table, delta=args.delta)
    print(f"pool profile from {src}: {len(table.pairs())} backends")

    # (arch, batch_size, prompt_len) -> fastest local_ms: keyed per jit
    # shape, so a recompile for a new batch shape (or the compile-heavy
    # first batch) never masquerades as backend drift
    baselines = {}
    # observations rescale the PRISTINE profile (time/energy are
    # bucket-independent per arch), never the already-adapted one — basing
    # them on live decisions would compound drift and stop the profile from
    # recovering once a backend returns to its healthy speed
    pristine = {}
    for entry in table.entries:
        pristine.setdefault(entry.model, (entry.time_ms, entry.energy_mwh))
    totals = {"energy_mwh": 0.0, "time_ms": 0.0}
    t_start = time.time()

    def backend_factory(decision):
        cfg = get_config(decision.backend).reduced()
        return Backend(decision.backend, cfg, max_batch=args.max_batch,
                       max_seq=96, seed=args.seed)

    if args.rate is not None:
        return _run_open_loop(args, table, backend_factory)

    def handle(served):
        observed = set()  # one observation per serve_batch call, not result
        for s in served:
            d, res, plen = s.decision, s.result, s.request.complexity
            totals["energy_mwh"] += d.energy_mwh
            totals["time_ms"] += d.time_ms
            local_ms = (res.prefill_s + res.decode_s) * 1e3 / res.batch_size
            print(f"req {res.uid:3d} len={plen:6d} bucket={d.group} -> "
                  f"{d.backend:22s} score={d.score:5.1f} "
                  f"prof[t={d.time_ms:8.2f}ms e={d.energy_mwh:7.4f}mWh] "
                  f"local[{local_ms:6.1f}ms/req batch={res.batch_size}] "
                  f"tokens={res.tokens[:4]}")
            key = (d.backend, res.batch_size, min(plen, PROMPT_CAP))
            if args.adapt and key + (res.prefill_s,) not in observed:
                observed.add(key + (res.prefill_s,))
                base_ms = min(baselines.get(key, local_ms), local_ms)
                baselines[key] = base_ms
                slowdown = local_ms / max(base_ms, 1e-9)
                prof_t, prof_e = pristine[d.backend]
                # uid lets a cluster fold the observation into the pod
                # that actually made (and will remake) this decision
                service.observe(Observation(
                    pair=d.pair, uid=res.uid, time_ms=prof_t * slowdown,
                    energy_mwh=prof_e * slowdown))

    rng = np.random.default_rng(args.seed)
    plens = [int(rng.choice([32, 128, 1024, 4096, 40_000],
                            p=[.3, .3, .2, .1, .1]))
             for _ in range(args.requests)]
    reqs = [RouteRequest(uid=uid, complexity=plen,
                         payload=rng.integers(0, 1000,
                                              size=min(plen, PROMPT_CAP)),
                         max_new_tokens=args.max_new)
            for uid, plen in enumerate(plens)]

    if args.use_async:
        # asyncio facade: awaitable futures are the consumption plane
        import asyncio

        from repro.serving.aio import AsyncEcoreService

        async def drive_async():
            nonlocal service
            service = AsyncEcoreService(PoolPolicy(pool), backend_factory,
                                        max_wait_ms=args.max_wait_ms)
            try:
                if args.adapt:
                    # closed loop, same cadence as the sync driver: fold
                    # each batch's observations in as soon as it completes,
                    # BEFORE later requests are routed
                    pending = []
                    for req in reqs:
                        pending.append(service.submit_nowait(req))
                        await asyncio.sleep(0)  # let inline flushes land
                        done = [f for f in pending if f.done()]
                        pending = [f for f in pending if not f.done()]
                        handle([f.result() for f in done])
                    await service.drain()
                    handle(await asyncio.gather(*pending))
                else:
                    futs = service.submit_batch_nowait(reqs)
                    await service.drain()   # flush partials -> all resolve
                    handle(await asyncio.gather(*futs))
                return service.stats()
            finally:
                await service.close()

        service = None
        stats = asyncio.run(drive_async())
        plane = "async service"
    elif args.pods > 1:
        # sharded: each pod adapts its OWN copy of the profile
        from repro.serving.cluster import EcoreCluster
        service = EcoreCluster(
            lambda i: PoolPolicy(ServingPool(table.copy(), delta=args.delta)),
            backend_factory, pods=args.pods, shard=args.shard,
            max_wait_ms=args.max_wait_ms)
        plane = f"{args.pods}-pod cluster ({args.shard})"
    else:
        service = EcoreService(PoolPolicy(pool), backend_factory,
                               max_wait_ms=args.max_wait_ms)
        plane = "service"

    if not args.use_async:
        try:
            if args.adapt:
                # closed loop: route per request — each observation mutates
                # the table the next decision must read
                for req in reqs:
                    service.submit(req)
                    handle(service.results())
            else:
                # static profile: route the whole workload in one tensorized
                # XLA call (per pod, under a cluster)
                service.submit_batch(reqs)
                handle(service.results())
            handle(service.drain())
            stats = service.stats()
        finally:
            service.close()

    if args.profile_out:
        pool.table.to_json(args.profile_out)
        print(f"wrote adapted routing profile to {args.profile_out}")
    print(f"\n{args.requests} requests in {time.time()-t_start:.1f}s via "
          f"{stats['serve_calls']} serve_batch calls over "
          f"{stats['backends']} backends [{plane}] "
          f"(max_batch={args.max_batch}, "
          f"deadline_flushes={stats['deadline_flushes']}); "
          f"profiled totals: {totals['time_ms']:.1f}ms, "
          f"{totals['energy_mwh']:.3f}mWh "
          f"(delta={args.delta}, adapt={args.adapt})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
