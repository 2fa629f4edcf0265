#!/usr/bin/env python3
"""Compile a cell's LLM programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 bench/aot.py --workload qwen2.5-3b.code

For each (batch, prompt length) the cell's traffic can form it compiles
the program's prefill, and for each batch its decode step, at the
configuration's real size, and prints each program's memory as the TPU
compiler counts it beside the bf16 weights.  A program that the chip's
compiler refuses, or that does not fit, fails here and costs no chip time.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batches", default="",
                    help="comma-separated batch sizes (default: all)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.harness import common, counts, llm, traffic as gen
    from repro.models import decode_step, prefill

    jax.config.update("jax_enable_compilation_cache", False)
    _, cell, config, tf = common.find_cell(args.workload)
    cfg, s, mc = llm.model_config(config, False)
    t = gen.llm_traffic(tf, 0, 1.0, s["vocab"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    from bench.harness import llm_weights

    params = on(jax.eval_shape(
        lambda lo, hi: llm_weights.param_tree(lo, hi, s, jnp.bfloat16),
        jnp.uint32(0), jnp.uint32(0)))
    wbytes = counts.total_params(s) * 2
    want = {int(b) for b in args.batches.split(",") if b}
    ok = True
    for b, plen in t.warm_shapes:
        if want and b not in want:
            continue
        tok = jax.ShapeDtypeStruct((b, plen), jnp.int32, sharding=one)
        pf = jax.jit(lambda p, x: prefill(p, mc, x, None, max_seq=t.max_seq))
        comp = pf.lower(params, tok).compile()
        m = comp.memory_analysis()
        tmp = m.temp_size_in_bytes + m.output_size_in_bytes
        print(f"prefill b={b} L={plen}: temp+out {tmp / 1e9:.2f} GB, "
              f"weights {wbytes / 1e9:.2f} GB, total "
              f"{(tmp + wbytes) / 1e9:.2f} GB", flush=True)
        ok &= tmp + wbytes < 15.5e9
    for b in sorted({b for b, _ in t.warm_shapes}):
        if want and b not in want:
            continue
        tok = jax.ShapeDtypeStruct((b, t.warm_shapes[0][1]), jnp.int32,
                                   sharding=one)
        _, cache = jax.eval_shape(
            lambda p, x: prefill(p, mc, x, None, max_seq=t.max_seq),
            params, tok)
        one_tok = jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=one)
        dc = jax.jit(lambda p, x, c: decode_step(p, mc, x, c))
        comp = dc.lower(params, one_tok, on(cache)).compile()
        m = comp.memory_analysis()
        tmp = m.temp_size_in_bytes + m.output_size_in_bytes
        print(f"decode b={b}: temp+out {tmp / 1e9:.2f} GB (cache "
              f"included), total {(tmp + wbytes) / 1e9:.2f} GB", flush=True)
        ok &= tmp + wbytes < 15.5e9
    print("fits" if ok else "DOES NOT FIT")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
