"""Plain float32 reference of a Qwen2-style decoder, and its fp8 control.

It follows the published architecture (Qwen2: RMSNorm, q/k/v with bias,
rotary embeddings on half-split pairs, grouped-query causal attention,
SwiGLU, tied unembedding) in ``jax.numpy`` at ``highest`` matmul
precision, one layer at a time so that it fits beside nothing else.  It
imports nothing of the program: its weights come from
``llm_weights.layer_tensors`` and the seed.

``precision="fp8"`` is the control: every linear layer (projections, MLP,
unembedding) takes its inputs rounded to float8 e4m3 with one scale per
tensor, the step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import llm_weights as W

F8_MAX = 448.0


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    if fp8:
        x, w = _fp8(x), _fp8(w)
    return x @ w


def _rms(x, gain_minus_one, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gain_minus_one)


def _rope(x, positions, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[..., None].astype(jnp.float32) * inv      # [n, T, hd/2]
    sin, cos = jnp.sin(ang)[:, :, None], jnp.cos(ang)[:, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, chunk: int):
    """Causal grouped-query attention, in query chunks. q [n,T,H,D],
    k/v [n,T,KV,D]."""
    n, t, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(n, t, kvh, g, dh)
    kpos = jnp.arange(t)

    def one(c):
        qc = jax.lax.dynamic_slice_in_dim(qg, c * chunk, chunk, axis=1)
        s = jnp.einsum("nskgd,ntkd->nkgst", qc, k) / jnp.sqrt(jnp.float32(dh))
        qpos = c * chunk + jnp.arange(chunk)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("nkgst,ntkd->nskgd", p, v)

    out = jax.lax.map(one, jnp.arange(t // chunk))      # [C, n, chunk, ...]
    return jnp.moveaxis(out, 0, 1).reshape(n, t, h, dh)


@functools.partial(jax.jit, static_argnames=("s", "fp8", "chunk", "eps",
                                             "theta"))
def _layer(lo, hi, i, x, *, s, fp8, chunk, eps, theta):
    p = {k: v.astype(jnp.float32) for k, v in
         W.layer_tensors(lo, hi, i, dict(s), jnp.bfloat16).items()}
    s = dict(s)
    n, t, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(t), (n, t))
    h = _rms(x, p["g1"], eps)
    q = (_mm(h, p["wq"], fp8) + p["bq"]).reshape(n, t, s["heads"], -1)
    k = (_mm(h, p["wk"], fp8) + p["bk"]).reshape(n, t, s["kv_heads"], -1)
    v = (_mm(h, p["wv"], fp8) + p["bv"]).reshape(n, t, s["kv_heads"], -1)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    a = _attention(q, k, v, chunk).reshape(n, t, -1)
    x = x + _mm(a, p["wo"], fp8)
    h = _rms(x, p["g2"], eps)
    m = jax.nn.silu(_mm(h, p["w_gate"], fp8)) * _mm(h, p["w_up"], fp8)
    return x + _mm(m, p["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("s",))
def _embed(lo, hi, tokens, *, s):
    return W.embed_table(lo, hi, dict(s), jnp.bfloat16).astype(
        jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("s", "fp8", "eps"))
def _logits(lo, hi, x, *, s, fp8, eps):
    s = dict(s)
    table = W.embed_table(lo, hi, s, jnp.bfloat16).astype(jnp.float32)
    gain = W.final_gain(lo, hi, s, jnp.bfloat16).astype(jnp.float32)
    return _mm(_rms(x, gain, eps), table.T, fp8)


@jax.jit
def _gaps(logits, served, other):
    """Per position: how far the served token's logit, and ``other``'s,
    lie below the best logit."""
    best = jnp.max(logits, axis=-1)
    at = lambda t: jnp.take_along_axis(logits, t[..., None], -1)[..., 0]
    return best - at(served), best - at(other), jnp.argmax(logits, -1)


def _hidden(seed, tokens, s, cfg, precision, chunk):
    lo, hi = W.split_seed(seed)
    key = tuple(sorted(s.items()))
    x = _embed(lo, hi, jnp.asarray(tokens), s=key)
    for i in range(s["layers"]):
        x = _layer(lo, hi, jnp.int32(i), x, s=key, fp8=precision == "fp8",
                   chunk=chunk, eps=cfg["rms_norm_eps"],
                   theta=float(cfg["rope_theta"]))
    return x


def compare(seed: int, s: Dict[str, int], cfg: Dict, requests: Sequence,
            *, control: bool = False, rows: int = 4, chunk: int = 512):
    """Run the reference over each request's prompt plus its served
    tokens (all but the last), and return per request the gaps of the
    served tokens below the reference's best logit (and, with
    ``control``, the gaps of the tokens the fp8 control puts first).

    ``requests`` holds (prompt [P] int32, served [N] int32) pairs."""
    # rows of one group are padded to its longest, so group like lengths;
    # the answers come back in the order given
    order = sorted(range(len(requests)),
                   key=lambda i: len(requests[i][0]) + len(requests[i][1]))
    requests = [requests[i] for i in order]
    out: List[Dict] = [None] * len(requests)  # type: ignore[list-item]
    eps = cfg["rms_norm_eps"]
    lo, hi = W.split_seed(seed)
    key = tuple(sorted(s.items()))
    with jax.default_matmul_precision("highest"):
        for g0 in range(0, len(requests), rows):
            group = requests[g0:g0 + rows]
            lens = [len(p) + len(t) - 1 for p, t in group]
            tpad = -(-max(lens) // chunk) * chunk
            kmax = max(len(t) for _, t in group)
            toks = np.zeros((len(group), tpad), np.int32)
            sel = np.zeros((len(group), kmax), np.int32)
            served = np.zeros((len(group), kmax), np.int32)
            for r, (p, t) in enumerate(group):
                seq = np.concatenate([p, t[:-1]])
                toks[r, :len(seq)] = seq
                sel[r] = len(p) - 1 + np.minimum(np.arange(kmax), len(t) - 1)
                served[r, :len(t)] = t
                served[r, len(t):] = t[-1]
            pick = lambda x: jnp.take_along_axis(
                x, jnp.asarray(sel)[..., None], axis=1)
            other = jnp.asarray(served)
            if control:
                xc = _hidden(seed, toks, s, cfg, "fp8", chunk)
                other = jnp.argmax(_logits(lo, hi, pick(xc), s=key, fp8=True,
                                           eps=eps), -1)
                del xc
            x = _hidden(seed, toks, s, cfg, "f32", chunk)
            logits = _logits(lo, hi, pick(x), s=key, fp8=False, eps=eps)
            gs, go, best = (np.asarray(a) for a in
                            _gaps(logits, jnp.asarray(served), other))
            del x, logits
            for r, (_, t) in enumerate(group):
                k = len(t)
                out[order[g0 + r]] = {
                    "gap": gs[r, :k], "control_gap": go[r, :k],
                    "agree": float(np.mean(best[r, :k] == t))}
    return out
