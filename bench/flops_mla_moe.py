"""Operations and least bytes of one decode step of a latent-attention MoE
decoder (the DeepSeek-V3 block), as the paged decode cell runs it.

Kept with the benchmark, so that a change to the program cannot move them.
A step of a batch of B sequences whose caches hold `length` positions, with
L layers of which the first `first_k_dense_replace` have a dense MLP:

  operations   2 x (matmul parameters outside the routed experts) x B
               + 2 x heads x (kv_lora_rank + rope + kv_lora_rank)
                   x (length + 1) x B per layer   (absorbed QK and PV)
               + routed expert operations: 3 x hidden x expert width x 2 per
                 routed slot on a held expert (the program counts the slots:
                 `expert_ops`)
  least bytes  every held weight once (matrices bfloat16, norm scales and
               the correction bias float32; of the embedding only the B
               rows read)
               + the `length` latent rows (kv_lora_rank + rope, bfloat16)
                 of every layer, read once
               + the new token's row of every layer, written once
               + the float32 logits, written once

The matmuls outside the routed experts are, per token: the query, W_kv_a,
the absorbed W_UK (heads x nope x kv_lora_rank) and W_UV (heads x
kv_lora_rank x v), the output projection; the dense MLP of the first layers;
per MoE layer the router and the shared experts; the head. With the
published vocabulary and no padding. At the cell's batch every held expert
is hit in a step with probability 1 - 16 (58/64)^128, about 1 - 5e-5, so
every held expert's weights count.
"""
from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    return {"L": cfg["num_hidden_layers"], "nd": cfg["first_k_dense_replace"],
            "d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "r": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "vd": cfg["v_head_dim"],
            "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
            "E": cfg["n_routed_experts"], "held": cfg["n_routed_experts_held"],
            "shared": cfg["n_shared_experts"], "V": cfg["vocab_size"]}


def expert_ops(cfg: dict, slots: float) -> float:
    """Operations of `slots` routed (token, held expert) slots."""
    return 2.0 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * slots


def decode_step(cfg: dict, batch: int, length: int) -> tuple[float, float]:
    """(operations outside the routed experts, least bytes) of one step."""
    s = _sizes(cfg)
    L, nd, d, H, r = s["L"], s["nd"], s["d"], s["H"], s["r"]
    nm = L - nd
    attn = (d * H * (s["nope"] + s["rope"]) + d * (r + s["rope"])
            + H * s["nope"] * r + H * r * s["vd"] + H * s["vd"] * d)
    moe_rest = d * s["E"] + 3 * d * s["shared"] * s["fe"]
    matmul = L * attn + nd * 3 * d * s["f"] + nm * moe_rest + d * s["V"]
    absorbed = 2.0 * H * (r + s["rope"] + r) * (length + 1) * batch * L
    ops = 2.0 * matmul * batch + absorbed
    # held weights: attention (W_kv_b whole), dense MLP, router, held and
    # shared experts, head; norm scales and the correction bias
    attn_w = (d * H * (s["nope"] + s["rope"]) + d * (r + s["rope"])
              + r * H * (s["nope"] + s["vd"]) + H * s["vd"] * d)
    weights = (L * attn_w + nd * 3 * d * s["f"]
               + nm * (d * s["E"] + 3 * d * (s["held"] + s["shared"]) * s["fe"]) + d * s["V"])
    small = L * (2 * d + r) + d + nm * s["E"]
    row = L * (r + s["rope"]) * 2
    least = (2.0 * weights + 4.0 * small + 2.0 * batch * d + batch * length * row
             + batch * row + batch * s["V"] * 4.0)
    return ops, least


def latent_read(cfg: dict, batch: int, length: int) -> float:
    """Least bytes the latent attention kernel moves in one step: every
    layer's `length` rows of every sequence, read once."""
    s = _sizes(cfg)
    return 2.0 * s["L"] * (s["r"] + s["rope"]) * length * batch
