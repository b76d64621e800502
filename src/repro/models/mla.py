"""Multi-head latent attention (DeepSeek-V2/V3 MLA), without a query LoRA.

Per token: q = x W_q splits per head into a part without rotary embedding
(`mla_nope_dim`) and a rotary part (`mla_rope_dim`); x W_kv_a gives the latent
c (`mla_kv_rank`), RMS-normed by `kv_norm`, and one rotary key shared by all
heads. W_kv_b maps c to each head's key part without rotary embedding (W_UK)
and its value (W_UV). Scores are (q_nope . k_nope + q_rope . k_rope) over
sqrt(nope + rope).

The full-sequence form (`attend_full_seq`) expands the latent into per-head
keys and values. The decode form caches only one row per token,
[c ++ k_rope ++ zeros] of `cfg.latent_width` lanes, and absorbs W_UK into the
query (`absorb_query`) and W_UV into the output (`absorb_output`): every head
then attends over the same rows, keys the whole row, values its first
`mla_kv_rank` lanes.

With `rope_interleave` the rotary lanes come in adjacent pairs (the
published DeepSeek-V3 layout); they are de-interleaved to the half-split
layout before the rotation, as the reference implementation does.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.layers import Params, dtype_of, normal_init, rope


# DeepSeek-V3 builds kv_a_layernorm without the config's rms_norm_eps, so
# it keeps the norm's default
KV_NORM_EPS = 1e-6


def attn_init(cfg, key, stacked: int | None = None) -> Params:
    dt = dtype_of(cfg)
    d, h, r = cfg.d_model, cfg.num_heads, cfg.mla_kv_rank
    nope, rdim, vd = cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
    lead = () if stacked is None else (stacked,)
    ks = jax.random.split(key, 5)
    scale_out = 0.02 / max(1.0, (2 * cfg.num_layers) ** 0.5)
    return {
        "wq": normal_init(ks[0], (*lead, d, h, nope + rdim), 0.02, dt),
        "wkv_a": normal_init(ks[1], (*lead, d, r + rdim), 0.02, dt),
        "kv_norm": jnp.ones((*lead, r), jnp.float32),
        "w_uk": normal_init(ks[2], (*lead, r, h, nope), 0.02, dt),
        "w_uv": normal_init(ks[3], (*lead, r, h, vd), 0.02, dt),
        "wo": normal_init(ks[4], (*lead, h, vd, d), scale_out, dt),
    }


def attn_specs(cfg, stacked: bool = False) -> Params:
    l = (None,) if stacked else ()
    return {
        "wq": P(*l, None, "model", None),
        "wkv_a": P(*l, None, None),
        "kv_norm": P(*l, None),
        "w_uk": P(*l, None, "model", None),
        "w_uv": P(*l, None, "model", None),
        "wo": P(*l, "model", None, None),
    }


def softmax_scale(cfg) -> float:
    return float(1.0 / np.sqrt(cfg.mla_nope_dim + cfg.mla_rope_dim))


def _rope(cfg, x, positions):
    if cfg.rope_interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return rope(x, positions, cfg.rope_theta)


def _rms(scale, x, eps):
    xf = x.astype(jnp.float32)
    var = (xf * xf).mean(-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def project(cfg, p: Params, x: jax.Array, positions):
    """x [B, S, D] -> (q_nope [B,S,H,nope], q_rope [B,S,H,rope] rotated,
    c [B,S,r] normed, k_rope [B,S,rope] rotated), in x's dtype."""
    acc = jnp.float32
    r, nope = cfg.mla_kv_rank, cfg.mla_nope_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"], preferred_element_type=acc).astype(x.dtype)
    kv = jnp.einsum("bsd,dk->bsk", x, p["wkv_a"], preferred_element_type=acc).astype(x.dtype)
    c = _rms(p["kv_norm"], kv[..., :r], KV_NORM_EPS)
    k_rope = _rope(cfg, kv[..., None, r:], positions)[..., 0, :]
    return q[..., :nope], _rope(cfg, q[..., nope:], positions), c, k_rope


def latent_row(cfg, c: jax.Array, k_rope: jax.Array) -> jax.Array:
    """The cached row of each token: [c ++ k_rope ++ zeros], latent_width lanes."""
    pad = cfg.latent_width - c.shape[-1] - k_rope.shape[-1]
    return jnp.concatenate([c, k_rope, jnp.zeros((*c.shape[:-1], pad), c.dtype)], axis=-1)


def absorb_query(cfg, p: Params, q_nope: jax.Array, q_rope: jax.Array) -> jax.Array:
    """Per-head query against latent rows: [q_nope W_UK^T ++ q_rope ++ zeros]
    [..., H, latent_width], in q's dtype."""
    q_lat = jnp.einsum("...hn,rhn->...hr", q_nope, p["w_uk"],
                       preferred_element_type=jnp.float32).astype(q_nope.dtype)
    pad = cfg.latent_width - q_lat.shape[-1] - q_rope.shape[-1]
    return jnp.concatenate(
        [q_lat, q_rope, jnp.zeros((*q_lat.shape[:-1], pad), q_lat.dtype)], axis=-1)


def absorb_output(cfg, p: Params, o_lat: jax.Array) -> jax.Array:
    """Latent attention output [..., H, kv_rank] -> residual update [..., D]
    through W_UV and the output projection."""
    o = jnp.einsum("...hr,rhv->...hv", o_lat, p["w_uv"],
                   preferred_element_type=jnp.float32).astype(o_lat.dtype)
    return jnp.einsum("...hv,hvd->...d", o, p["wo"],
                      preferred_element_type=jnp.float32).astype(o_lat.dtype)


def attend_full_seq(cfg, p: Params, x: jax.Array, positions, mask) -> jax.Array:
    """Causal MLA over a whole sequence, latent expanded per head.
    mask: bool broadcastable to [B, H, S, S]. Returns [B, S, D]."""
    acc = jnp.float32
    q_nope, q_rope, c, k_rope = project(cfg, p, x, positions)
    k_nope = jnp.einsum("bsr,rhn->bshn", c, p["w_uk"], preferred_element_type=acc)
    v = jnp.einsum("bsr,rhv->bshv", c, p["w_uv"], preferred_element_type=acc).astype(x.dtype)
    s = (jnp.einsum("bqhn,bshn->bhqs", q_nope, k_nope.astype(x.dtype),
                    preferred_element_type=acc)
         + jnp.einsum("bqhk,bsk->bhqs", q_rope, k_rope, preferred_element_type=acc))
    s = jnp.where(mask, s * softmax_scale(cfg), -2.0e38)
    a = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhqs,bshv->bqhv", a, v, preferred_element_type=acc).astype(x.dtype)
    return jnp.einsum("bqhv,hvd->bqd", o, p["wo"], preferred_element_type=acc).astype(x.dtype)
