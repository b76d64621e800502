"""Operations and least bytes of one decode step of a dense decoder.

Kept with the benchmark, so that a change to the program cannot move them.
A step of a batch of B sequences whose caches hold `length` positions:

  operations   2 x (matmul parameters) x B
               + 4 x layers x heads x head_dim x (length + 1) x B   (QK and PV)
  least bytes  every weight once (matrices bfloat16, norm scales float32)
               + the K and V of the `length` positions written, read once
               + the new token's K and V, written once
               + the float32 logits, written once

with the published vocabulary and no padding. The least bytes are what the
step cannot avoid; a step that reads the whole provisioned cache moves more.
"""
from __future__ import annotations


def decode_step(cfg: dict, batch: int, length: int) -> tuple[float, float]:
    """(operations, least bytes) of one decode step."""
    L, d, H = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["num_attention_heads"]
    KV, hd, f, V = (cfg["num_key_value_heads"], cfg["head_dim"],
                    cfg["intermediate_size"], cfg["vocab_size"])
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f
    matmul = L * per_layer + V * d
    norms = L * (2 * d + 2 * hd) + d
    ops = 2.0 * matmul * batch + 4.0 * L * H * hd * (length + 1) * batch
    kv_per_pos = L * 2 * KV * hd * 2
    least = (2.0 * matmul + 4.0 * norms + batch * length * kv_per_pos
             + batch * kv_per_pos + batch * V * 4.0)
    return ops, least


def decode_call(cfg: dict, batch: int, steps: int) -> tuple[float, float]:
    """(operations, least bytes) of `steps` steps from an empty cache."""
    tot_ops = tot_bytes = 0.0
    for length in range(steps):
        ops, least = decode_step(cfg, batch, length)
        tot_ops += ops
        tot_bytes += least
    return tot_ops, tot_bytes
