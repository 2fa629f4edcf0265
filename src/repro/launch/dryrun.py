import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# NOTE: the two lines above MUST run before any jax import (jax locks the
# device count at first init).  Everything else follows.
"""Multi-pod dry-run: lower + compile every (arch x input-shape) combination
for the production meshes, prove memory fit, and extract roofline terms.

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod --out artifacts/

Outputs one JSON row per combination (see repro.launch.roofline.Roofline.row)
plus the compiled memory analysis, appended to ``--out``/dryrun.jsonl.
"""
import argparse
import json
import sys
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, list_configs
from repro.launch import hlo_cost
from repro.launch import roofline as rl
from repro.launch import steps as st
from repro.launch.mesh import make_production_mesh
from repro.models.base import INPUT_SHAPES
from repro.optim.adamw import AdamWConfig
from repro.sharding import specs as sp
from repro.sharding import ctx

# per-arch gradient-accumulation factor for train_4k (keeps per-device
# activation memory ~<2 GB; see DESIGN.md §4)
MICROBATCHES = {
    "llava-next-34b": 16,
    "llama3-8b": 8, "llama3-8b-swa": 8,
    "gemma2-9b": 8, "gemma2-9b-swa": 8,
    "deepseek-7b": 8,
    "qwen2.5-3b": 4,
    "deepseek-v2-lite-16b": 4,
    "recurrentgemma-2b": 4,
    "mamba2-370m": 4,
    "granite-moe-1b-a400m": 8,
    "whisper-small": 16,
}


def skip_reason(cfg, shape) -> Optional[str]:
    if shape.name == "long_500k" and not cfg.is_subquadratic:
        return ("long_500k requires sub-quadratic attention; "
                f"{cfg.name} has unbounded full-attention layers "
                "(see DESIGN.md §5)")
    return None


def lower_combo(cfg, shape, mesh, *, microbatches: Optional[int] = None):
    """Returns the lowered step for one (arch, shape, mesh)."""
    baxes = sp.batch_axes(mesh)
    n_bshards = 1
    for a in baxes:
        n_bshards *= mesh.shape[a]
    mode = "train" if shape.kind == "train" else "serve"
    with ctx.activation_sharding(baxes, n_bshards, mesh=mesh, mode=mode):
        return _lower_combo(cfg, shape, mesh, baxes, microbatches)


def _lower_combo(cfg, shape, mesh, baxes, microbatches):
    if shape.kind == "train":
        mb = microbatches or MICROBATCHES.get(cfg.name, 4)
        step = st.make_train_step(cfg, AdamWConfig(), num_microbatches=mb,
                                  batch_axes=baxes)
        params = st.param_structs(cfg)
        pspecs = sp.param_specs(params, mode="train", mesh=mesh)
        opts = st.opt_structs(params)
        ospecs = st.OptState(step=P(), mu=pspecs, nu=pspecs)
        batch = st.batch_specs(cfg, shape)
        bspecs = {k: sp.batch_spec(mesh, shape.global_batch, v.ndim)
                  for k, v in batch.items()}
        fn = jax.jit(
            step,
            in_shardings=(sp.shard(mesh, pspecs), sp.shard(mesh, ospecs),
                          sp.shard(mesh, bspecs)),
            out_shardings=(sp.shard(mesh, pspecs), sp.shard(mesh, ospecs),
                           None),
            donate_argnums=(0, 1))
        with mesh:
            return fn.lower(params, opts, batch)
    if shape.kind == "prefill":
        step = st.make_prefill_step(cfg)
        params = st.param_structs(cfg, serve=True)
        pspecs = sp.param_specs(params, mode="serve", mesh=mesh)
        batch = st.batch_specs(cfg, shape)
        bspecs = {k: sp.batch_spec(mesh, shape.global_batch, v.ndim)
                  for k, v in batch.items()}
        fn = jax.jit(step,
                     in_shardings=(sp.shard(mesh, pspecs),
                                   sp.shard(mesh, bspecs)))
        with mesh:
            return fn.lower(params, batch)
    # decode
    step = st.make_decode_step(cfg)
    params = st.param_structs(cfg, serve=True)
    pspecs = sp.param_specs(params, mode="serve", mesh=mesh)
    token, cache = st.decode_input_specs(cfg, shape)
    tspec = sp.batch_spec(mesh, shape.global_batch, 2)
    cspecs = sp.cache_specs(cache, mesh, shape.global_batch)
    fn = jax.jit(step,
                 in_shardings=(sp.shard(mesh, pspecs),
                               NamedSharding(mesh, tspec),
                               sp.shard(mesh, cspecs)),
                 out_shardings=(None, sp.shard(mesh, cspecs)),
                 donate_argnums=(2,))
    with mesh:
        return fn.lower(params, token, cache)


def run_combo(arch: str, shape_name: str, *, multi_pod: bool = False,
              verbose: bool = True, save_hlo: Optional[str] = None):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    reason = skip_reason(cfg, shape)
    if reason:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skip", "reason": reason}
    t0 = time.time()
    lowered = lower_combo(cfg, shape, mesh)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    # trip-count-aware per-chip cost (XLA cost_analysis counts loop bodies
    # once; see repro.launch.hlo_cost)
    hc = hlo_cost.analyze(hlo)
    counts = rl.count_params(cfg)
    r = rl.Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name,
        chips=mesh.devices.size,
        flops=hc.flops,
        bytes_accessed=hc.bytes,
        coll_bytes=hc.coll_bytes,
        coll_by_kind=hc.coll,
        per_device_memory=rl.memory_bytes(mem),
        model_flops=rl.model_flops(cfg, shape, counts["total"],
                                   counts["active"]),
    )
    row = r.row()
    row.update(status="ok", lower_s=round(t1 - t0, 1),
               compile_s=round(t2 - t1, 1),
               params_total=counts["total"], params_active=counts["active"],
               xla_flops=float(cost.get("flops", 0.0)),
               xla_bytes=float(cost.get("bytes accessed", 0.0)))
    if verbose:
        print(f"--- {arch} x {shape_name} x {mesh_name} ---")
        print(f"memory_analysis: temp={getattr(mem,'temp_size_in_bytes',0)/2**30:.2f}GiB "
              f"args={getattr(mem,'argument_size_in_bytes',0)/2**30:.2f}GiB "
              f"out={getattr(mem,'output_size_in_bytes',0)/2**30:.2f}GiB")
        print(f"cost_analysis: flops/chip={r.flops:.3e} bytes/chip={r.bytes_accessed:.3e}")
        print(f"roofline: compute={r.t_compute*1e3:.2f}ms memory={r.t_memory*1e3:.2f}ms "
              f"collective={r.t_collective*1e3:.2f}ms -> {r.bottleneck}-bound; "
              f"useful-flops={r.useful_flops_ratio:.2f}")
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--include-variants", action="store_true")
    ap.add_argument("--out", default="artifacts")
    args = ap.parse_args(argv)

    archs = ([args.arch] if args.arch
             else list_configs(include_variants=args.include_variants))
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    out_path = os.path.join(args.out, "dryrun.jsonl")
    failures = 0
    with open(out_path, "a") as f:
        for arch in archs:
            for shape in shapes:
                for mp in meshes:
                    try:
                        row = run_combo(arch, shape, multi_pod=mp)
                    except Exception as e:  # a failure here is a bug: report
                        traceback.print_exc()
                        row = {"arch": arch, "shape": shape,
                               "mesh": "2x16x16" if mp else "16x16",
                               "status": "fail", "error": repr(e)}
                        failures += 1
                    f.write(json.dumps(row) + "\n")
                    f.flush()
    print(f"wrote {out_path}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
