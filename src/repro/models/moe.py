"""Mixture-of-Experts with capacity-bounded sort/gather dispatch + shared experts.

Routing (`route`): softmax over the experts, or (DeepSeek-V3) sigmoid scores
whose top-k is chosen with a correction bias added; the top-k weights come
from the unbiased scores, renormalized if `moe_norm_topk`, times
`moe_routed_scale`.

Dispatch (Megablocks/MaxText-style, all static shapes):
  router top-k -> flatten (token, k) slots -> argsort by expert -> rank within
  expert via sorted-segment position -> scatter into [E, C, D] buffers (slots past
  capacity dropped) -> per-expert batched ffn -> gather back, weighted by gate.

Expert dim E is sharded over "model" (EP inside the TP axis); the token->expert
scatter/gather induces the all-to-all-equivalent resharding under GSPMD.

Decode (`apply_moe_held`): the layer holds only experts `moe_expert_offset`
onward, `moe_experts_held` of them (one chip's share under expert
parallelism). It routes over all experts, computes its own experts for every
token, each weighted by the token's routing weight (zero where the token did
not pick it), and drops no token; the absent experts' part is left out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import Params, apply_mlp, dtype_of, mlp_init, mlp_specs, normal_init


def _padded_experts(cfg, tp: int) -> int:
    e = cfg.moe_num_experts
    return ((e + tp - 1) // tp) * tp


def moe_init(cfg, key, tp: int, stacked: int | None = None) -> Params:
    dt = dtype_of(cfg)
    d, fe = cfg.d_model, cfg.moe_d_ff
    ep = _padded_experts(cfg, tp)
    held = cfg.moe_experts_held or ep
    lead = () if stacked is None else (stacked,)
    ks = jax.random.split(key, 6)
    scale_out = 0.02 / max(1.0, (2 * cfg.num_layers) ** 0.5)
    p = {
        "router": normal_init(ks[0], (*lead, d, ep), 0.02, jnp.float32),
        "wi": normal_init(ks[1], (*lead, held, d, fe), 0.02, dt),
        "wg": normal_init(ks[2], (*lead, held, d, fe), 0.02, dt),
        "wo": normal_init(ks[3], (*lead, held, fe, d), scale_out, dt),
    }
    if cfg.moe_scoring == "sigmoid":
        p["router_bias"] = jnp.zeros((*lead, ep), jnp.float32)
    if cfg.moe_num_shared:
        fs = cfg.moe_num_shared * fe
        p["shared"] = mlp_init(cfg, ks[4], d, fs, stacked=stacked)
    return p


def moe_specs(cfg, stacked: bool = False) -> Params:
    l = (None,) if stacked else ()
    p = {
        "router": P(*l, None, None),
        "wi": P(*l, "model", None, None),
        "wg": P(*l, "model", None, None),
        "wo": P(*l, "model", None, None),
    }
    if cfg.moe_scoring == "sigmoid":
        p["router_bias"] = P(*l, None)
    if cfg.moe_num_shared:
        p["shared"] = mlp_specs(cfg, stacked=stacked)
    return p


def route(cfg, p: Params, xt: jax.Array, tp: int = 1) -> tuple[jax.Array, jax.Array]:
    """xt [T, D] -> (weights f32[T, K], expert ids int32[T, K])."""
    e = _padded_experts(cfg, tp)
    k = cfg.moe_top_k
    logits = jnp.einsum(
        "td,de->te", xt.astype(jnp.float32), p["router"], preferred_element_type=jnp.float32
    )
    if e != cfg.moe_num_experts:  # mask padded experts out of routing
        logits = jnp.where(jnp.arange(e) < cfg.moe_num_experts, logits, -1e9)
    if cfg.moe_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores + p["router_bias"]
        if e != cfg.moe_num_experts:
            choice = jnp.where(jnp.arange(e) < cfg.moe_num_experts, choice, -1e9)
        _, idx = jax.lax.top_k(choice, k)
        gate = jnp.take_along_axis(scores, idx, axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, k)  # [T, K]
    if cfg.moe_norm_topk:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)  # renormalize
    if cfg.moe_routed_scale != 1.0:
        gate = gate * cfg.moe_routed_scale
    return gate, idx


def apply_moe_held(cfg, p: Params, x: jax.Array):
    """x [B, S, D] -> (routed part, shared part, routed slots on the held
    experts int32): the held experts' and the shared experts' parts of the
    layer's output, each [B, S, D] in x's dtype (the shared part zero when
    there are none). Phases run under the "route", "experts" and "shared"
    named scopes."""
    bsz, s, d = x.shape
    xt = x.reshape(bsz * s, d)
    held = cfg.moe_held
    with jax.named_scope("route"):
        gate, idx = route(cfg, p, xt)
        local = idx - cfg.moe_expert_offset
        mine = (local >= 0) & (local < held)
        # [T, held] routing weight of each held expert (0 if not picked);
        # one_hot of an id outside 0..held-1 is all zeros
        w = jnp.einsum("tk,tke->te", gate, jax.nn.one_hot(local, held, dtype=jnp.float32))
        slots = mine.sum(dtype=jnp.int32)
    with jax.named_scope("experts"):
        # every held expert over every token: at a decode batch the expert
        # matmuls are bound by reading their weights, not by the tokens
        acc = jnp.float32
        hi = jnp.einsum("td,edf->etf", xt, p["wi"], preferred_element_type=acc)
        hg = jnp.einsum("td,edf->etf", xt, p["wg"], preferred_element_type=acc)
        h = (jax.nn.silu(hg) * hi * w.T[:, :, None]).astype(x.dtype)
        routed = jnp.einsum("etf,efd->td", h, p["wo"], preferred_element_type=acc)
        routed = routed.astype(x.dtype).reshape(bsz, s, d)
    with jax.named_scope("shared"):
        shared = (apply_mlp(cfg, p["shared"], x) if cfg.moe_num_shared
                  else jnp.zeros_like(x))
    return routed, shared, slots


def apply_moe(cfg, p: Params, x: jax.Array, tp: int, sc=None) -> jax.Array:
    """x: [B, S, D] -> [B, S, D]."""
    if cfg.moe_experts_held:
        raise NotImplementedError("capacity dispatch computes every expert; "
                                  "a share of them decodes through apply_moe_held")
    bsz, s, d = x.shape
    t = bsz * s
    e = _padded_experts(cfg, tp)
    k = cfg.moe_top_k
    cap = int(t * k / e * cfg.moe_capacity_factor) + 1
    cap = min(cap, t)
    xt = x.reshape(t, d)
    gate, idx = route(cfg, p, xt, tp)

    # ---- sort-based dispatch ----
    flat_e = idx.reshape(-1)  # [T*K]
    order = jnp.argsort(flat_e)  # slots grouped by expert
    sorted_e = flat_e[order]
    # rank within expert = position - first position of that expert
    pos = jnp.arange(t * k)
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    rank = pos - seg_start[sorted_e]
    keep = rank < cap
    dst = jnp.where(keep, sorted_e * cap + rank, e * cap)  # overflow -> dropped row
    token_of_slot = order // k

    xe = jnp.zeros((e * cap + 1, d), x.dtype)
    xe = xe.at[dst].set(xt[token_of_slot], mode="drop")
    xe = xe[: e * cap].reshape(e, cap, d)
    if sc is not None:
        xe = sc(xe, P("model", None, None))

    # ---- per-expert gated ffn ----
    acc = jnp.float32
    hi = jnp.einsum("ecd,edf->ecf", xe, p["wi"], preferred_element_type=acc)
    hg = jnp.einsum("ecd,edf->ecf", xe, p["wg"], preferred_element_type=acc)
    h = (jax.nn.silu(hg) * hi).astype(x.dtype)
    ye = jnp.einsum("ecf,efd->ecd", h, p["wo"], preferred_element_type=acc).astype(x.dtype)

    # ---- combine: gather back and weight by gate ----
    ye_flat = jnp.concatenate([ye.reshape(e * cap, d), jnp.zeros((1, d), x.dtype)])
    slot_out = ye_flat[dst]  # [T*K, D] (dropped slots read zeros)
    gate_sorted = gate.reshape(-1)[order]
    contrib = slot_out * gate_sorted[:, None].astype(x.dtype)
    out = jnp.zeros((t, d), jnp.float32).at[token_of_slot].add(contrib.astype(jnp.float32))

    if cfg.moe_num_shared:
        out = out + apply_mlp(cfg, p["shared"], x, sc=sc).reshape(t, d)
    return out.astype(x.dtype).reshape(bsz, s, d)


def aux_load_balance_loss(cfg, logits: jax.Array, idx: jax.Array) -> jax.Array:
    """Switch-style load-balance auxiliary loss (optional training extra)."""
    e = logits.shape[-1]
    probs = jax.nn.softmax(logits, -1)
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0) / idx.size
    return e * (me * ce).sum()
