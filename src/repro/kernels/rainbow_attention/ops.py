"""Dispatch wrapper: Pallas kernel on TPU, jnp oracle elsewhere."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.rainbow_attention.rainbow_attention import (
    latent_attention,
    rainbow_attention,
)
from repro.kernels.rainbow_attention.ref import paged_decode_attention_ref


def backend(block: int, head_dim: int, force: str | None = None) -> str:
    """The path paged_decode_attention takes: "pallas" on a TPU when K/V
    blocks sit on the bf16 (16, 128) tile, else "ref". force: "pallas",
    "interpret" or "ref" (tests)."""
    if force:
        return force
    on_tile = block % 16 == 0 and head_dim % 128 == 0
    return "pallas" if jax.default_backend() == "tpu" and on_tile else "ref"


def _merge_fresh(q, k_new, v_new, m, l, acc, blk_m, blk_l, scale=None):
    """Fold the fresh token into the kernel's history softmax.

    Returns the normalized output [B, HP, hd] (q's dtype) and the block mass
    [B, nblk]: each block's share of the softmax summed over query heads, the
    fresh token in the normalization and not in the mass. In the latent mode
    (`scale` given) v_new is the first acc-width lanes of the k_new row."""
    hd = q.shape[-1]
    rep = q.shape[1] // k_new.shape[1]
    kn = jnp.repeat(k_new, rep, axis=1)  # query head h reads kv head h // rep
    vn = jnp.repeat(v_new, rep, axis=1)
    s_new = jnp.einsum("bhk,bhk->bh", q, kn, preferred_element_type=jnp.float32)
    s_new = s_new / np.sqrt(hd) if scale is None else s_new * scale
    mf = jnp.maximum(m, s_new)
    alpha, e_new = jnp.exp(m - mf), jnp.exp(s_new - mf)
    lf = l * alpha + e_new
    out = (acc * alpha[..., None] + e_new[..., None] * vn.astype(jnp.float32))
    out = (out / lf[..., None]).astype(q.dtype)
    mass = (blk_l * jnp.exp(blk_m - mf[..., None]) / lf[..., None]).sum(axis=1)
    return out, mass


def paged_decode_attention(
    q, k_new, v_new, cap_k, cap_v, hot_k, hot_v, vidx, layer, length,
    force: str | None = None, *, scale: float | None = None, v_dim: int = 0,
):
    """Decode attention of one token through the translated pools.

    q [B, HP, hd]; k_new/v_new [B, KVS, hd]; stacked pools [L, n, block, KVS,
    hd]; vidx int32[B, nblk] (see ref.py). Returns (out [B, HP, hd], block
    mass f32[B, nblk]).

    Latent mode (multi-head latent attention; `v_dim` > 0): v_new, cap_v and
    hot_v are None, KVS is 1 and every query head attends over the latent
    rows of cap_k/hot_k with the given `scale`; values are the rows' first
    v_dim lanes and out is [B, HP, v_dim]. On the kernel path it runs as
    "latent_attention"."""
    mode = backend(cap_k.shape[2], q.shape[-1], force)
    if mode == "ref":
        return paged_decode_attention_ref(
            q, k_new, v_new, cap_k, cap_v, hot_k, hot_v, vidx, layer, length,
            scale=scale, v_dim=v_dim)
    if v_dim:
        with jax.named_scope("latent_attention"):
            stats = latent_attention(q, cap_k, hot_k, vidx, layer, length, scale=scale,
                                     v_dim=v_dim, interpret=mode == "interpret")
        return _merge_fresh(q, k_new, k_new[..., :v_dim], *stats, scale=scale)
    with jax.named_scope("paged_attention"):
        stats = rainbow_attention(q, cap_k, cap_v, hot_k, hot_v, vidx, layer,
                                  length, interpret=mode == "interpret")
    return _merge_fresh(q, k_new, v_new, *stats)
