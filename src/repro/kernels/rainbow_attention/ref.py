"""Pure-jnp oracle for the Rainbow paged decode attention kernel.

Semantics: single-token decode attention where KV blocks are read through the
two-tier translation, plus the per-block softmax mass the interval controller
observes. `vidx` is the translated block table: an entry below the number of
capacity blocks is the block's capacity-pool home, any other entry is the
capacity-block count plus the hot-pool slot of a resident block (the
translation itself, bitmap + remap -> vidx, is repro.core.remap.translate and
is tested separately). The fresh token (`k_new`, `v_new`, not yet appended)
attends too; it takes part in the normalization and not in the block mass.

Latent mode (`v_dim` > 0, multi-head latent attention): the V pools and
v_new are None, the K pools hold one latent row per token (KVS 1) that every
query head reads, values are the rows' first `v_dim` lanes, and scores are
scaled by `scale`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def paged_decode_attention_ref(
    q: jax.Array,  # [B, HP, hd]
    k_new: jax.Array,  # [B, KVS, hd]
    v_new: jax.Array,
    cap_k: jax.Array,  # [L, ncap, block, KVS, hd]
    cap_v: jax.Array,
    hot_k: jax.Array,  # [L, nhot, block, KVS, hd]
    hot_v: jax.Array,
    vidx: jax.Array,  # int32[B, nblk]
    layer: jax.Array,  # int32 scalar
    length: jax.Array,  # int32 history positions (uniform across batch)
    scale: float | None = None,
    v_dim: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """Returns (out [B, HP, hd] in q's dtype, block mass f32[B, nblk])."""
    b, hp, hd = q.shape
    nblk = vidx.shape[1]
    block, kvs = cap_k.shape[2], cap_k.shape[3]
    pool_k = jnp.concatenate([cap_k[layer], hot_k[layer]], axis=0)
    k = pool_k[vidx].reshape(b, nblk * block, kvs, hd)
    k = jnp.concatenate([k, k_new[:, None]], axis=1)
    if v_dim:
        v = k[..., :v_dim]
    else:
        pool_v = jnp.concatenate([cap_v[layer], hot_v[layer]], axis=0)
        v = pool_v[vidx].reshape(b, nblk * block, kvs, hd)
        v = jnp.concatenate([v, v_new[:, None]], axis=1)
    k = jnp.repeat(k, hp // kvs, axis=2)
    v = jnp.repeat(v, hp // kvs, axis=2)
    s = jnp.einsum("bhk,bshk->bhs", q, k, preferred_element_type=jnp.float32)
    s = s / np.sqrt(hd) if scale is None else s * scale
    pos = jnp.arange(nblk * block + 1)
    ok = (pos < length) | (pos == nblk * block)
    s = jnp.where(ok[None, None, :], s, -2.0e38)
    p = jax.nn.softmax(s, axis=-1)
    # garbage past the length must not leak through 0 * NaN
    v = jnp.where(ok[None, :, None, None], v, jnp.zeros_like(v))
    out = jnp.einsum(
        "bhs,bshk->bhk", p.astype(q.dtype), v, preferred_element_type=jnp.float32
    ).astype(q.dtype)
    mass = p[:, :, :-1].sum(axis=1).reshape(b, nblk, block).sum(-1)
    return out, mass
