#!/usr/bin/env python3
"""Bring-up smoke run of ECORE's served path on TPU chips.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the cluster phase on four chips

Every phase drives the entry points a user calls
(``RoutingPolicy`` -> ``EcoreService`` -> ``ExecutionBackend``) with weights
and data made from ``--seed``.  One chip runs, in one process:

* ``detection``: ``DetectionPolicy`` over ``DetectorBackend(edge_stage=True)``
  serves 64x64 scenes; the fused Canny stage and the detectors run on the
  chip, and every frame's served edge density must equal the oracle's.
* ``canny``: ``canny_edge_batch(impl="pallas")`` over a ragged batch of
  1080p and 4K frames must equal ``ref.canny_edge`` run on the chip, pixel
  for pixel.
* ``llm``: ``PoolPolicy`` over ``make_backend("llm", "qwen2.5-3b")`` at its
  published width and depth in bf16 serves 8 requests of two prompt
  lengths.
* ``llm_ref``: the same path on a 4-layer depth cut at full width, whose
  served logits are compared with a float32 ``models.forward`` over the same
  weights (a float32 copy of all 36 layers does not fit beside the bf16 one).

``--chips 4`` runs only the cluster phase: an ``EcoreCluster`` of four pods
serving qwen2.5-3b, one pod per chip, whose tokens must equal one pod's on
device 0.

There is no CPU branch: the script exits non-zero when JAX finds no TPU, when
a check fails, or when any request's future fails.  The timings it prints are
bring-up timings, not metrics.  The last line of stdout is a JSON status.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

#: bound on the relative RMS error of the served bf16 logits against the
#: float32 reference, over the 4-layer cut (see CHANGES.md for its basis)
LOGITS_REL_RMS_BOUND = 5e-2

LLM_ARCH = "qwen2.5-3b"
PROMPT_LENGTHS = (128, 512)
MAX_NEW_TOKENS = 8
MAX_SEQ = 1024
REF_LAYERS = 4


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def tpu_device():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        sys.exit(2)
    return dev


class Timings:
    """Per-phase wall seconds and backend-compile seconds (from JAX's own
    compile-duration events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.compile_s += duration

    def run(self, name, fn, *args):
        c0, t0 = self.compile_s, time.perf_counter()
        out = fn(*args)
        print(f"[bring-up timing, not a metric] {name}: "
              f"wall {time.perf_counter() - t0:.2f}s, "
              f"compile {self.compile_s - c0:.2f}s", flush=True)
        return out


def resolve(futures):
    """Every future's ``Served``; a failed future counts, and any failure
    fails the run."""
    served, failed = [], 0
    for fut in futures:
        try:
            served.append(fut.result())
        except Exception as exc:  # counted here, raised below
            failed += 1
            print(f"chip_smoke: request failed: {exc!r}", file=sys.stderr)
    check(failed == 0, f"{failed} of {len(futures)} futures failed")
    return served


# ------------------------------------------------------------------ phases

def detection_phase(seed: int) -> None:
    from repro.core.estimators import EdgeDetectionEstimator
    from repro.core.policy import DetectionPolicy, RouteRequest
    from repro.core.profiles import ProfileEntry, ProfileTable
    from repro.core.router import GreedyEstimateRouter
    from repro.detection import scenes as sc
    from repro.detection.detectors import DETECTOR_CONFIGS, init_detector
    from repro.detection.devices import DEVICES
    from repro.kernels.canny_fused import ref
    from repro.serving.backend import DetectorBackend
    from repro.serving.service import EcoreService

    rows = []
    for g in range(5):
        for m, d, mp in (("ssd_v1", "orin_nano", 60.0 - 3 * g),
                         ("yolov8_n", "pi5", 60.0)):
            flops = DETECTOR_CONFIGS[m].flops
            rows.append(ProfileEntry(m, d, g, mp, DEVICES[d].time_ms(flops),
                                     DEVICES[d].energy_mwh(flops)))
    table = ProfileTable(rows)
    policy = DetectionPolicy(GreedyEstimateRouter(table, 5.0), table,
                             EdgeDetectionEstimator())
    backends = []

    def factory(decision):
        model, device = decision.pair
        params = init_detector(DETECTOR_CONFIGS[model],
                               jax.random.PRNGKey(seed))
        backends.append(DetectorBackend(model, device, params, max_batch=4,
                                        edge_stage=True))
        return backends[-1]

    scenes = [sc.make_scene(np.random.default_rng(seed + i))
              for i in range(16)]
    reqs = [RouteRequest(uid=i, payload=s.image, true_complexity=s.count)
            for i, s in enumerate(scenes)]
    with EcoreService(policy, factory) as service:
        futures = service.submit_batch(reqs)
        service.drain()
    served = resolve(futures)
    check(len(served) == len(scenes), "detection: not every frame served")
    density = {}
    for be in backends:
        density.update(be.edge_density)
    maps = np.asarray(ref.canny_edge(jnp.asarray(
        np.stack([s.image for s in scenes]))))
    for s in served:
        uid = s.request.uid
        boxes, scores, classes = s.result.detections
        check(boxes.shape[1:] == (4,) and np.isfinite(scores).all(),
              f"detection: malformed detections for frame {uid}")
        check(density[uid] == float(np.mean(maps[uid])),
              f"detection: frame {uid} edge density {density[uid]} != "
              f"oracle {float(np.mean(maps[uid]))}")
    pairs = sorted({s.decision.pair_name for s in served})
    print(f"detection: {len(served)} frames served via {pairs}, "
          f"edge densities equal the oracle's "
          f"(mean {np.mean(list(density.values())):.4f})")


def canny_phase(seed: int) -> None:
    from repro.kernels.canny_fused import canny_edge_batch, ref

    rng = np.random.default_rng(seed)
    shapes = [(1080, 1920), (2160, 3840), (1080, 1920), (2160, 3840),
              (1080, 1920)]
    frames = [rng.random(s, np.float32) for s in shapes]
    maps = canny_edge_batch(frames, impl="pallas")
    for f, m in zip(frames, maps):
        want = np.asarray(ref.canny_edge(jnp.asarray(f)[None]))[0]
        wrong = int((m != want).sum())
        check(m.shape == f.shape and wrong == 0,
              f"canny: {wrong} pixels differ from the oracle at {f.shape}")
    print(f"canny: {len(frames)} ragged frames {sorted(set(shapes))} equal "
          f"the oracle exactly; edge share "
          f"{np.mean([m.mean() for m in maps]):.4f}")


def llm_requests(cfg, seed: int, lengths):
    from repro.core.policy import RouteRequest

    rng = np.random.default_rng(seed)
    return [RouteRequest(uid=1000 + i, complexity=n,
                         payload=rng.integers(0, cfg.vocab_size, n,
                                              dtype=np.int32),
                         max_new_tokens=MAX_NEW_TOKENS)
            for i, n in enumerate(lengths)]


def serve_llm(cfg, reqs, *, seed: int, max_batch: int, pods: int = 0):
    """Serve ``reqs`` through PoolPolicy -> EcoreService (or an
    EcoreCluster of ``pods`` pods) -> the registered "llm" backend.
    Returns ({uid: tokens}, [backends built])."""
    from repro.core.policy import PoolPolicy
    from repro.launch.serve import synthetic_pool_table
    from repro.serving.backend import make_backend
    from repro.serving.cluster import EcoreCluster
    from repro.serving.pool import ServingPool
    from repro.serving.service import EcoreService

    table = synthetic_pool_table([LLM_ARCH])
    backends = []

    def factory(decision):
        backends.append(make_backend("llm", decision.backend, cfg,
                                     max_batch=max_batch, max_seq=MAX_SEQ,
                                     seed=seed))
        return backends[-1]

    if pods:
        service = EcoreCluster(lambda i: PoolPolicy(ServingPool(table.copy())),
                               factory, pods=pods)
    else:
        service = EcoreService(PoolPolicy(ServingPool(table)), factory)
    with service:
        futures = service.submit_batch(reqs)
        service.drain()
    served = resolve(futures)
    tokens = {s.request.uid: np.asarray(s.result.tokens) for s in served}
    for r in reqs:
        t = tokens[r.uid]
        check(t.shape == (r.max_new_tokens,)
              and ((0 <= t) & (t < cfg.vocab_size)).all(),
              f"llm: bad tokens for request {r.uid}: {t}")
    return tokens, backends


def full_config():
    from repro.configs import get_config

    return dataclasses.replace(get_config(LLM_ARCH), param_dtype="bfloat16")


def llm_phase(seed: int) -> None:
    cfg = full_config()
    reqs = llm_requests(cfg, seed, [PROMPT_LENGTHS[0]] * 4
                        + [PROMPT_LENGTHS[1]] * 4)
    tokens, backends = serve_llm(cfg, reqs, seed=seed, max_batch=4)
    n_params = sum(x.size for x in jax.tree.leaves(backends[0].params))
    print(f"llm: {LLM_ARCH} ({cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, {n_params / 1e9:.2f} B bf16 parameters) served "
          f"{len(tokens)} requests, prompts {PROMPT_LENGTHS}, "
          f"{MAX_NEW_TOKENS} new tokens each")


def llm_ref_phase(seed: int) -> None:
    from repro.models import forward
    from repro.serving.engine import step_programs

    cfg = dataclasses.replace(full_config(), num_layers=REF_LAYERS)
    ref_cfg = dataclasses.replace(cfg, param_dtype="float32",
                                  activ_dtype="float32")
    reqs = llm_requests(cfg, seed + 1, [PROMPT_LENGTHS[0]] * 4
                        + [PROMPT_LENGTHS[1]] * 4)
    tokens, backends = serve_llm(cfg, reqs, seed=seed, max_batch=4)
    params = backends[0].params
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    run_prefill, run_decode = step_programs(cfg, MAX_SEQ)
    run_ref = jax.jit(lambda p, t: forward(p, ref_cfg, t))

    worst = 0.0
    for n in PROMPT_LENGTHS:
        group = [r for r in reqs if r.complexity == n]
        prompt = np.stack([r.payload for r in group])
        served = np.stack([tokens[r.uid] for r in group])  # [B, new]
        # the served logits, recomputed by the same steps the backend
        # runs, fed the served tokens
        logits, cache = run_prefill(params, jnp.asarray(prompt), None)
        steps = [logits[:, -1]]
        for k in range(MAX_NEW_TOKENS - 1):
            logits, cache = run_decode(params,
                                       jnp.asarray(served[:, k:k + 1]), cache)
            steps.append(logits[:, -1])
        got = jnp.stack(steps, axis=1).astype(jnp.float32)  # [B, new, V]
        check(np.array_equal(np.asarray(jnp.argmax(got, -1)), served),
              f"llm_ref: recomputed logits do not give the served tokens "
              f"(prompt {n})")
        with jax.default_matmul_precision("highest"):
            full = run_ref(params32, jnp.asarray(
                np.concatenate([prompt, served[:, :-1]], axis=1)))
        want = full[:, n - 1:]
        rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        max_abs = float(jnp.max(jnp.abs(got - want)))
        agree = float(jnp.mean(jnp.argmax(want, -1) == served))
        worst = max(worst, rel)
        print(f"llm_ref: prompt {n}: served bf16 logits vs float32 "
              f"forward ({REF_LAYERS}-layer cut): relative RMS {rel:.3e} "
              f"(bound {LOGITS_REL_RMS_BOUND:.0e}), max |diff| "
              f"{max_abs:.3e}, max |ref| "
              f"{float(jnp.max(jnp.abs(want))):.3e}, greedy agreement "
              f"{agree:.3f}")
    check(worst <= LOGITS_REL_RMS_BOUND,
          f"llm_ref: relative RMS {worst:.3e} > {LOGITS_REL_RMS_BOUND:.0e}")


def cluster_phase(seed: int, cfg=None) -> None:
    """Four pods, one per device, against one pod on device 0.  Request
    k has prompt length ``PROMPT_LENGTHS[(k % 4) // 2]``, so least-loaded
    sharding (request k -> pod k % 4) and the single pod's flushes at
    max_batch=2 both serve batches of two same-length prompts."""
    cfg = cfg or full_config()
    reqs = llm_requests(cfg, seed, [PROMPT_LENGTHS[(k % 4) // 2]
                                    for k in range(8)])
    solo, backends = serve_llm(cfg, reqs, seed=seed, max_batch=2)
    check({d for be in backends for d in _devices(be)} == {jax.devices()[0]},
          "cluster: the single pod is not on device 0")
    del backends
    gc.collect()
    pods = len(jax.devices())
    tokens, backends = serve_llm(cfg, reqs, seed=seed, max_batch=2,
                                 pods=pods)
    placed = [_devices(be) for be in backends]
    check(all(len(p) == 1 for p in placed)
          and len(set().union(*placed)) == pods,
          f"cluster: pod parameters on {placed}, want {pods} distinct "
          f"devices")
    for r in reqs:
        check(np.array_equal(tokens[r.uid], solo[r.uid]),
              f"cluster: request {r.uid} tokens {tokens[r.uid]} != "
              f"one pod's {solo[r.uid]}")
    print(f"cluster: {pods} pods on devices "
          f"{sorted(d.id for p in placed for d in p)} served {len(tokens)} "
          f"requests; tokens equal one pod's on device 0")


def _devices(backend):
    return set().union(*(x.devices() for x in jax.tree.leaves(backend.params)))


# -------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cluster phase, one pod per chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = tpu_device()
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    count = len(jax.devices())
    check(count >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, found {count}")
    print(f"device: {dev.device_kind} x{count} ({dev.platform}); "
          f"compile cache {cache_dir}", flush=True)
    timings = Timings()
    if args.chips == 4:
        phases = [("cluster", cluster_phase)]
    else:
        phases = [("detection", detection_phase), ("canny", canny_phase),
                  ("llm", llm_phase), ("llm_ref", llm_ref_phase)]
    t0 = time.perf_counter()
    for name, fn in phases:
        timings.run(name, fn, args.seed)
        gc.collect()
    print(f"[bring-up timing, not a metric] total: wall "
          f"{time.perf_counter() - t0:.2f}s, compile "
          f"{timings.compile_s:.2f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
