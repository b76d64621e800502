"""Rainbow paged decode attention — Pallas TPU kernel.

The TPU-native form of the paper's split-TLB + bitmap + remap walk (Fig. 6):
the translated block table, the layer index and the current length arrive as
*scalar-prefetch* operands (SMEM — the TLB analogue). The stacked pools stay
in HBM (`pl.ANY`); for every live block the kernel DMAs ONE block of K and V
from the pool the table names for it — the hot pool slot of a resident block,
else the block's capacity-pool home — into VMEM. Nothing is concatenated and
no layer is sliced out of the stacked pools.

One program walks the batch. Per sequence, an in-kernel loop whose trip
count comes from SMEM visits chunks of `chunk` blocks, so the work grows
with the live length, not with the provisioned blocks; only blocks
0 .. (length-1)//block are read. The DMAs of the next chunk (of this or the
next sequence) run while the current chunk computes (double-buffered).

A chunk's K is viewed as rows [tokens*KVS, head_dim]: one 2-D dot
q[HP, hd] @ K^T gives every (query head, token, kv head) score, and pairs
whose kv head is not the query head's are masked away — grouped-query
attention without expanding K/V. bf16 operands, f32 accumulation and f32
softmax statistics; the probabilities are cast to the query dtype for PV.
Positions at or past the length are masked with `where` in the scores and
in V, so garbage there cannot leak (0 * NaN would).

Per sequence the kernel returns the running (m, l, acc) of the online
softmax over the history, and per query head and block the block's score
max and its exp-sum (blocks past the length: -inf-like max, sum 0). The
fresh token and the normalization are the caller's (ops.py). The per-block
statistics sit in lane-dense [HP, nblk] rows held in registers; a block's
entry is a select over its sequence's row (nblk / 128 vregs), the one cost
that grows with the provisioned blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
CHUNK_ROWS = 1024  # K rows (tokens x kv heads) per chunk


def _kernel(
    # scalar prefetch (SMEM)
    layer_ref,  # int32[1]
    length_ref,  # int32[1]  history length (positions already in the pools)
    vidx_ref,  # int32[B * nblk]  < ncap: capacity home; else ncap + hot slot
    # inputs
    q_ref,  # [B, HP, hd] (VMEM)
    gqa_ref,  # int32[HP, chunk*rows]: 1 where the row's kv head is the head's
    cap_k, cap_v,  # [L, ncap, rows, hd] (HBM)
    hot_k, hot_v,  # [L, nhot, rows, hd] (HBM)
    # outputs (VMEM)
    m_ref, l_ref,  # f32[B, HP, 1]
    acc_ref,  # f32[B, HP, hd]
    bm_ref, bl_ref,  # f32[B, HP, nrow >= nblk]  per-block max and exp-sum
    # scratch
    kbuf, vbuf,  # [2, chunk, rows, hd]
    sem,  # DMA semaphores [2 (k, v), 2 (slot)]
    *,
    block: int,
    chunk: int,
    nblk: int,
    ncap: int,
    scale: float,
):
    nb, hp, hd = q_ref.shape
    rows = kbuf.shape[2]
    kvs = rows // block
    width = chunk * rows
    layer = layer_ref[0]
    length = length_ref[0]
    live = (length + block - 1) // block  # blocks holding history
    nch = (live + chunk - 1) // chunk  # chunks per sequence

    def blocks_in(c):  # live blocks in chunk c
        return jnp.minimum(chunk, live - c * chunk)

    def start(b, c, slot):
        def go(j, _):
            idx = vidx_ref[b * nblk + c * chunk + j]

            @pl.when(idx < ncap)
            def _():
                pltpu.make_async_copy(
                    cap_k.at[layer, idx], kbuf.at[slot, j], sem.at[0, slot]).start()
                pltpu.make_async_copy(
                    cap_v.at[layer, idx], vbuf.at[slot, j], sem.at[1, slot]).start()

            @pl.when(idx >= ncap)
            def _():
                pltpu.make_async_copy(
                    hot_k.at[layer, idx - ncap], kbuf.at[slot, j], sem.at[0, slot]).start()
                pltpu.make_async_copy(
                    hot_v.at[layer, idx - ncap], vbuf.at[slot, j], sem.at[1, slot]).start()

            return 0

        jax.lax.fori_loop(0, blocks_in(c), go, 0)

    def wait(c, slot):
        def go(j, _):
            # a wait needs only the destination and the semaphore: every
            # block copy has the same size, whichever pool it came from
            pltpu.make_async_copy(cap_k.at[0, 0], kbuf.at[slot, j], sem.at[0, slot]).wait()
            pltpu.make_async_copy(cap_v.at[0, 0], vbuf.at[slot, j], sem.at[1, slot]).wait()
            return 0

        jax.lax.fori_loop(0, blocks_in(c), go, 0)

    gqa = gqa_ref[...] != 0  # [HP, width]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0)
    nrow = bm_ref.shape[2]
    lane = jax.lax.broadcasted_iota(jnp.int32, (hp, nrow), 1)

    start(0, 0, 0)

    def seq_body(b, _):
        q = q_ref[b]  # [HP, hd]

        def chunk_body(c, carry):
            m_prev, l_prev, acc_prev, bm_row, bl_row = carry
            slot = jax.lax.rem(b * nch + c, 2)

            # prefetch the next chunk: this sequence's, else the next one's
            last = c + 1 == nch
            b_next = jnp.where(last, b + 1, b)

            @pl.when(b_next < nb)
            def _():
                start(b_next, jnp.where(last, 0, c + 1), 1 - slot)

            wait(c, slot)
            k = kbuf[slot].reshape(width, hd)
            v = vbuf[slot].reshape(width, hd)
            # rows of this chunk below the length: row // kvs < length - base
            nvalid = (length - c * chunk * block) * kvs
            ok = gqa & (col < nvalid)
            v = jnp.where(row < nvalid, v, jnp.zeros_like(v))
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            s = jnp.where(ok, s * scale, NEG_INF)  # [HP, width]

            # each block's max and exp-sum land in the sequence's lane-dense
            # [HP, nblk] rows, kept in registers until the sequence ends
            for j in range(chunk):
                sj = s[:, j * rows:(j + 1) * rows]
                mj = sj.max(axis=1, keepdims=True)
                ej = jnp.where(ok[:, j * rows:(j + 1) * rows], jnp.exp(sj - mj), 0.0)
                at = lane == c * chunk + j
                bm_row = jnp.where(at, mj, bm_row)
                bl_row = jnp.where(at, ej.sum(axis=1, keepdims=True), bl_row)

            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            l_new = alpha * l_prev + p.sum(axis=1, keepdims=True)
            acc_new = alpha * acc_prev + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new, bm_row, bl_row

        init = (jnp.full((hp, 1), NEG_INF, jnp.float32),
                jnp.zeros((hp, 1), jnp.float32),
                jnp.zeros((hp, hd), jnp.float32),
                jnp.full((hp, nrow), NEG_INF, jnp.float32),
                jnp.zeros((hp, nrow), jnp.float32))
        # with no history (nch == 0) the initial state is the answer
        m, l, acc, bm_row, bl_row = jax.lax.fori_loop(0, nch, chunk_body, init)
        m_ref[b], l_ref[b], acc_ref[b] = m, l, acc
        bm_ref[b], bl_ref[b] = bm_row, bl_row
        return 0

    jax.lax.fori_loop(0, nb, seq_body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rainbow_attention(
    q: jax.Array,  # [B, HP, hd]
    cap_k: jax.Array,  # [L, ncap, block, KVS, hd]  stacked capacity pools
    cap_v: jax.Array,
    hot_k: jax.Array,  # [L, nhot, block, KVS, hd]  stacked hot pools
    hot_v: jax.Array,
    vidx: jax.Array,  # int32[B, nblk]  < ncap: home; else ncap + hot slot
    layer: jax.Array,  # int32 scalar
    length: jax.Array,  # int32 scalar: history positions per sequence
    *,
    interpret: bool,
):
    """(m, l, acc, block max, block exp-sum) of the history's attention:
    f32 [B, HP], [B, HP], [B, HP, hd], [B, HP, nblk], [B, HP, nblk]."""
    b, hp, hd = q.shape
    _, ncap, block, kvs, _ = cap_k.shape
    nblk = vidx.shape[1]
    rows = block * kvs
    chunk = max(1, min(nblk, CHUNK_ROWS // rows))  # blocks per chunk
    nrow = -(-nblk // 128) * 128  # lane-dense rows of per-block stats
    width = chunk * rows
    # rows are (token, kv head) pairs, kv head minor; query head h reads kv
    # head h // (HP // KVS)
    gqa = ((np.arange(width) % kvs)[None, :]
           == (np.arange(hp) // (hp // kvs))[:, None]).astype(np.int32)
    pools = [x.reshape(x.shape[0], x.shape[1], rows, hd)
             for x in (cap_k, cap_v, hot_k, hot_v)]

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    full = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i, *_: (0,) * len(shape))
    stat = jax.ShapeDtypeStruct((b, hp, nrow), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[full((b, hp, hd)), full((hp, width))] + [any_spec] * 4,
        out_specs=[full((b, hp, 1)), full((b, hp, 1)), full((b, hp, hd)),
                   full(stat.shape), full(stat.shape)],
        scratch_shapes=[
            pltpu.VMEM((2, chunk, rows, hd), cap_k.dtype),
            pltpu.VMEM((2, chunk, rows, hd), cap_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(
        _kernel, block=block, chunk=chunk, nblk=nblk, ncap=ncap,
        scale=float(1.0 / np.sqrt(hd)))
    m, l, acc, bm, bl = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, hp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, hp, hd), jnp.float32), stat, stat],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        jnp.reshape(length, (1,)).astype(jnp.int32),
        vidx.reshape(-1).astype(jnp.int32),
        q, jnp.asarray(gqa), *pools,
    )
    return m[..., 0], l[..., 0], acc, bm[..., :nblk], bl[..., :nblk]
