"""Seeded weights for a Qwen2-style decoder, made on the device.

One function of (seed, layer) gives each layer's tensors, so the program's
weights (all layers at once, in the type they are served in) and the
plain reference (one layer at a time) are the same numbers without the
reference taking anything the program made.  Values are uniform with the
stated standard deviation, built from random bits with exact integer
arithmetic and one rounding multiply, so every path that calls
``layer_tensors`` gets the same bits.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

#: scale of the q/k/v biases and of the norm gains' offset from 1
BIAS_STD = 0.3
GAIN_STD = 0.1


def split_seed(seed: int):
    """A seed of up to 64 bits as two uint32 words."""
    return (jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32((seed >> 32) & 0xFFFFFFFF))


def _root(lo, hi):
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                              hi)


def _uniform(key, shape, std: float, dtype):
    bits = jax.random.bits(key, shape, jnp.uint32)
    n = (bits >> 8).astype(jnp.int32) - (1 << 23)       # [-2^23, 2^23)
    u = n.astype(jnp.float32) * jnp.float32(2.0 ** -23)  # exact, [-1, 1)
    return (u * jnp.float32(std * math.sqrt(3.0))).astype(dtype)


def layer_tensors(lo, hi, layer, s: Dict[str, int], dtype) -> Dict:
    """One layer's tensors, named as in the published checkpoint's layer:
    q/k/v/o projections ([in, out]), q/k/v biases, SwiGLU gate/up/down,
    and the two RMSNorm gains stored as ``gain - 1``."""
    key = jax.random.fold_in(_root(lo, hi), layer)
    d, q = s["d"], s["heads"] * s["head_dim"]
    kv, ff = s["kv_heads"] * s["head_dim"], s["ff"]
    shapes = [("wq", (d, q), d ** -0.5), ("wk", (d, kv), d ** -0.5),
              ("wv", (d, kv), d ** -0.5), ("wo", (q, d), q ** -0.5),
              ("bq", (q,), BIAS_STD), ("bk", (kv,), BIAS_STD),
              ("bv", (kv,), BIAS_STD),
              ("w_gate", (d, ff), d ** -0.5), ("w_up", (d, ff), d ** -0.5),
              ("w_down", (ff, d), ff ** -0.5),
              ("g1", (d,), GAIN_STD), ("g2", (d,), GAIN_STD)]
    return {name: _uniform(jax.random.fold_in(key, i), shape, std, dtype)
            for i, (name, shape, std) in enumerate(shapes)}


def embed_table(lo, hi, s: Dict[str, int], dtype):
    key = jax.random.fold_in(_root(lo, hi), 1_000_000)
    return _uniform(key, (s["vocab"], s["d"]), s["d"] ** -0.5, dtype)


def final_gain(lo, hi, s: Dict[str, int], dtype):
    key = jax.random.fold_in(_root(lo, hi), 1_000_001)
    return _uniform(key, (s["d"],), GAIN_STD, dtype)


def param_tree(lo, hi, s: Dict[str, int], dtype):
    """The program's parameter tree (``repro.models`` layout: stacked
    layers under ``blocks/s0``, gains stored as ``gain - 1``)."""
    layers = jax.lax.map(lambda i: layer_tensors(lo, hi, i, s, dtype),
                         jnp.arange(s["layers"]))
    attn = {k: layers[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")}
    mlp = {k: layers[k] for k in ("w_gate", "w_up", "w_down")}
    return {"embed": {"table": embed_table(lo, hi, s, dtype)},
            "final_norm": final_gain(lo, hi, s, dtype),
            "blocks": {"s0": {"norm1": layers["g1"], "attn": attn,
                              "norm2": layers["g2"], "mlp": mlp}}}


def program_params(seed: int, s: Dict[str, int], dtype):
    """``param_tree`` made in one jitted call on the default device."""
    make = jax.jit(lambda lo, hi: param_tree(lo, hi, s, dtype))
    return make(*split_seed(seed))
