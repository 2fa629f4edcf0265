"""Operations and bytes that the served work needs, from its shapes.

These are the algorithm's counts, not what the compiled program happens to
do, so the same work reads the same whatever implements it.  FLOPs count a
multiply-add as two."""
from __future__ import annotations

from typing import Dict

BF16 = 2  # bytes


# ----------------------------------------------------------------- LLM

def llm_shape(cfg: Dict) -> Dict[str, int]:
    """The sizes the counts need, from a Hugging Face style config."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"layers": cfg["num_hidden_layers"], "d": d, "heads": h,
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg.get("head_dim") or d // h,
            "ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"]}


def layer_params(s: Dict[str, int]) -> int:
    """Parameters of one decoder layer: q/k/v/o with q/k/v bias, SwiGLU,
    two norm gains."""
    q = s["heads"] * s["head_dim"]
    kv = s["kv_heads"] * s["head_dim"]
    attn = s["d"] * (q + 2 * kv) + (q + 2 * kv) + q * s["d"]
    mlp = 3 * s["d"] * s["ff"]
    return attn + mlp + 2 * s["d"]


def total_params(s: Dict[str, int]) -> int:
    """With tied embeddings: the table once, plus the final norm."""
    return s["layers"] * layer_params(s) + s["vocab"] * s["d"] + s["d"]


def layer_flops_per_token(s: Dict[str, int]) -> int:
    """Matmul FLOPs of one token through one layer, attention excluded."""
    q = s["heads"] * s["head_dim"]
    kv = s["kv_heads"] * s["head_dim"]
    return 2 * s["d"] * (q + 2 * kv) + 2 * q * s["d"] + 6 * s["d"] * s["ff"]


def request_flops(s: Dict[str, int], prompt: int, new: int) -> float:
    """Model FLOPs to serve one request greedily: the prompt and every
    generated token but the last go through the layers (token at position
    p attends to p + 1 keys), and each of the ``new`` tokens is read off
    the tied unembedding."""
    t = prompt + max(new - 1, 0)
    keys = t * (t + 1) / 2
    return (t * s["layers"] * layer_flops_per_token(s)
            + 4 * s["layers"] * s["heads"] * s["head_dim"] * keys
            + new * 2 * s["d"] * s["vocab"])


def decode_step_bytes(s: Dict[str, int], batch: int, length: int) -> float:
    """HBM bytes one greedy decode step must move at ``batch`` sequences
    whose caches hold ``length`` tokens after the step: every bf16 weight
    once (the tied table as the unembedding; the embedding gather reads
    ``batch`` rows), and each sequence's keys and values."""
    kv_per_token = s["layers"] * 2 * s["kv_heads"] * s["head_dim"] * BF16
    return (total_params(s) * BF16 + batch * s["d"] * BF16
            + batch * length * kv_per_token)
