"""Model FLOPs of one training step, from the configuration's shapes.

Forward and backward, no recomputation: 6 FLOPs per matrix-multiply weight
per token, and for causal attention over the whole sequence 12 * s * d per
token per layer (QK^T and PV, 2 * s * h * head_dim each forward, tripled
for the backward), counted over the full s x s score matrix as the program
computes it. The embedding is a lookup and counts nothing; the head is a
matrix multiply like any other.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    d, f, L, V = (cfg["d_model"], cfg["d_ff"], cfg["num_layers"],
                  cfg["vocab_size"])
    hq = cfg["num_heads"] * cfg["head_dim"]
    hkv = cfg["num_kv_heads"] * cfg["head_dim"]
    mlp = 3 if cfg["mlp"] == "swiglu" else 2
    per_layer = d * hq + 2 * d * hkv + hq * d + mlp * d * f
    return L * per_layer + d * V


def flops_per_token(cfg: dict, seq: int) -> int:
    attn = 12 * cfg["num_layers"] * seq * cfg["num_heads"] * cfg["head_dim"]
    return 6 * matmul_params(cfg) + attn


def flops_per_step(cfg: dict, batch: int, seq: int) -> int:
    return batch * seq * flops_per_token(cfg, seq)
