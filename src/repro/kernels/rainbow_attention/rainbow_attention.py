"""Rainbow paged decode attention — Pallas TPU kernel.

The TPU-native form of the paper's split-TLB + bitmap + remap walk (Fig. 6):
block tables arrive as *scalar-prefetch* operands (SMEM — the TLB analogue);
each grid step's BlockSpec index_map dereferences the table to pull ONE KV
block from the [capacity ++ hot] pool straight into VMEM (the DMA the remap
pointer would trigger). Flash-decoding online softmax accumulates in VMEM
scratch across the block-grid.

Grid: (B, nblk). For step (b, i):
  k_blk = pool_k[vidx[b, i]]   (BlockSpec-managed HBM->VMEM DMA)
  scores = q[b] @ k_blk^T; online-softmax update of (m, l, acc) scratch
  at i == nblk-1: out[b] = acc / l
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0e38


def _kernel(
    # scalar-prefetch
    vidx_ref,  # int32[B, nblk]  (SMEM)
    length_ref,  # int32[1]        (SMEM)
    # inputs (VMEM blocks)
    q_ref,  # [1, HP, hd]
    k_ref,  # [1, block, KVS, hd]  selected by index_map via vidx
    v_ref,  # [1, block, KVS, hd]
    # output
    o_ref,  # [1, HP, hd]
    # scratch
    m_ref,  # f32[HP, 1]
    l_ref,  # f32[HP, 1]
    acc_ref,  # f32[HP, hd]
    *,
    block: int,
    nblk: int,
):
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # [HP, hd]
    k = k_ref[0]  # [block, KVS, hd]
    v = v_ref[0]
    hp = q.shape[0]
    kvs = k.shape[1]
    m_rep = hp // kvs

    # expand kv heads to match q heads (local consecutive repeat)
    k = jnp.repeat(k, m_rep, axis=1)  # [block, HP, hd]
    v = jnp.repeat(v, m_rep, axis=1)
    s = jnp.einsum("hd,thd->ht", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * (1.0 / np.sqrt(q.shape[-1]))

    # mask positions beyond the valid length
    base = i * block
    pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    s = jnp.where(pos < length_ref[0], s, NEG_INF)

    m_prev = m_ref[...][:, 0]
    l_prev = l_ref[...][:, 0]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * alpha + p.sum(axis=1)
    acc = acc_ref[...] * alpha[:, None] + jnp.einsum(
        "ht,thd->hd", p, v.astype(jnp.float32)
    )
    m_ref[...] = m_new[:, None]
    l_ref[...] = l_new[:, None]
    acc_ref[...] = acc

    @pl.when(i == nblk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rainbow_attention(
    q: jax.Array,  # [B, HP, hd]
    pool_k: jax.Array,  # [NPOOL, block, KVS, hd]
    pool_v: jax.Array,
    vidx: jax.Array,  # int32[B, nblk]
    length: jax.Array,  # int32 scalar
    *,
    interpret: bool,
) -> jax.Array:
    b, hp, hd = q.shape
    nblk = vidx.shape[1]
    block, kvs = pool_k.shape[1], pool_k.shape[2]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nblk),
        in_specs=[
            pl.BlockSpec((1, hp, hd), lambda bb, ii, vt, ln: (bb, 0, 0)),
            pl.BlockSpec(
                (1, block, kvs, hd), lambda bb, ii, vt, ln: (vt[bb, ii], 0, 0, 0)
            ),
            pl.BlockSpec(
                (1, block, kvs, hd), lambda bb, ii, vt, ln: (vt[bb, ii], 0, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, hp, hd), lambda bb, ii, vt, ln: (bb, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hp, 1), jnp.float32),
            pltpu.VMEM((hp, 1), jnp.float32),
            pltpu.VMEM((hp, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, block=block, nblk=nblk)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hp, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )(vidx, jnp.reshape(length, (1,)).astype(jnp.int32), q, pool_k, pool_v)
