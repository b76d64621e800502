"""Shared model building blocks (pure JAX, no flax): norms, RoPE, MLPs, embeddings.

Conventions:
  * params are nested dicts of jnp arrays; layer-stacked leaves carry a leading L
    dim and are consumed by lax.scan.
  * matmuls run in the config compute dtype (bf16) with fp32 accumulation
    (preferred_element_type); norms and softmax run in fp32.
  * every init_* has a matching specs_* returning a PartitionSpec tree of the same
    structure ("model" = TP axis; batch/data axes are activation-only).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Params = dict[str, Any]


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


def normal_init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def norm_init(cfg, d: int, stacked: int | None = None) -> Params:
    shape = (d,) if stacked is None else (stacked, d)
    p = {"scale": jnp.ones(shape, jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros(shape, jnp.float32)
    return p


def norm_specs(cfg, stacked: bool = False) -> Params:
    spec = P(None, None) if stacked else P(None)
    p = {"scale": spec}
    if cfg.norm == "layernorm":
        p["bias"] = spec
    return p


def apply_norm(cfg, p: Params, x: jax.Array, eps: float | None = None) -> jax.Array:
    eps = cfg.norm_eps if eps is None else eps
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:  # rmsnorm
        var = (xf * xf).mean(-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return out.astype(x.dtype)


def rms_head_norm(scale: jax.Array, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Per-head RMS norm over head_dim (qwen3 qk_norm). x: [..., hd]."""
    xf = x.astype(jnp.float32)
    var = (xf * xf).mean(-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Apply RoPE. x: [B, S, H, hd]; positions: [B, S] or [S]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (gated SwiGLU or plain GELU)
# ---------------------------------------------------------------------------


def mlp_init(cfg, key, d: int, f: int, stacked: int | None = None) -> Params:
    ks = jax.random.split(key, 3)
    dt = dtype_of(cfg)
    lead = () if stacked is None else (stacked,)
    scale_in = 0.02
    scale_out = 0.02 / max(1.0, (2 * cfg.num_layers) ** 0.5)
    p = {
        "wi": normal_init(ks[0], (*lead, d, f), scale_in, dt),
        "wo": normal_init(ks[1], (*lead, f, d), scale_out, dt),
    }
    if cfg.gated_mlp:
        p["wg"] = normal_init(ks[2], (*lead, d, f), scale_in, dt)
    return p


def mlp_specs(cfg, stacked: bool = False) -> Params:
    l = (None,) if stacked else ()
    p = {"wi": P(*l, None, "model"), "wo": P(*l, "model", None)}
    if cfg.gated_mlp:
        p["wg"] = P(*l, None, "model")
    return p


def apply_mlp(cfg, p: Params, x: jax.Array, sc=None) -> jax.Array:
    acc = jnp.float32
    h = jnp.einsum("bsd,df->bsf", x, p["wi"], preferred_element_type=acc)
    if cfg.gated_mlp:
        g = jnp.einsum("bsd,df->bsf", x, p["wg"], preferred_element_type=acc)
        h = jax.nn.silu(g) * h if cfg.act == "silu" else jax.nn.gelu(g) * h
    else:
        h = jax.nn.gelu(h) if cfg.act == "gelu" else jax.nn.silu(h)
    h = h.astype(x.dtype)
    if sc is not None:
        h = sc(h, P(("pod", "data"), None, "model"))
    out = jnp.einsum("bsf,fd->bsd", h, p["wo"], preferred_element_type=acc)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding + LM head
# ---------------------------------------------------------------------------


def embed_init(cfg, key) -> Params:
    dt = dtype_of(cfg)
    vp = cfg.padded_vocab
    k1, k2 = jax.random.split(key)
    p = {"tok": normal_init(k1, (vp, cfg.d_model), 0.02, dt)}
    if not cfg.tie_embeddings:
        p["head"] = normal_init(k2, (cfg.d_model, vp), 0.02, dt)
    return p


def embed_specs(cfg) -> Params:
    p = {"tok": P("model", None)}
    if not cfg.tie_embeddings:
        p["head"] = P(None, "model")
    return p


def embed_lookup(cfg, p: Params, tokens: jax.Array) -> jax.Array:
    # rows follow the tokens' sharding, whatever the table's vocab sharding
    sh = jax.typeof(tokens).sharding
    out = None if sh.mesh.empty else sh.update(spec=P(*sh.spec, None))
    return p["tok"].at[tokens].get(out_sharding=out)


def lm_logits(cfg, p: Params, x: jax.Array) -> jax.Array:
    head = p["head"] if not cfg.tie_embeddings else p["tok"].T
    logits = jnp.einsum("bsd,dv->bsv", x, head, preferred_element_type=jnp.float32)
    # mask vocab padding so it can never win argmax / leak into the loss
    vp = logits.shape[-1]
    if vp != cfg.vocab_size:
        mask = jnp.arange(vp) < cfg.vocab_size
        logits = jnp.where(mask, logits, -1e9)
    return logits


def softmax_xent(logits: jax.Array, labels: jax.Array, mask: jax.Array) -> jax.Array:
    """Mean CE over mask==1 positions. logits fp32 [B,S,V]; labels int [B,S]."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
